"""How close one decode step of the mixed-length cell comes to the time its
weights alone take to cross the memory bus: (bytes of held weights a step
over the window's mean live streams has to read / the chip's published
bandwidth) over the median device time of one decode step: the step's share
of its roofline, which is the bus.

The bytes are the architecture's ``counts.decode_weight_bytes``: attention,
the dense MLP, the routers, the shared experts and the head whole, and of
the 16 held experts a layer those that SOME live stream chose (an expert is
chosen by none of ``live`` tokens with probability (1 - 8/128) ** live:
12.7% at 32 streams), with ``live`` the window's mean, Δ``decode_tokens_
emitted`` / Δ``decode_steps_dispatched`` of ``LLMEngine.counters()`` (one
stream where the run has no such counters: the floor). The embedding's rows
and the cache's bytes are left out, so it cannot pass 100% while the time
covers the reads.

One execution of the decode dispatch runs up to ``decode_steps`` steps and
leaves early when every slot is done; its steps are counted in the trace, as
the executions of the GLOBAL layers' decode kernel inside it over the global
layers held (the window layers' calls have another name). The kernel is found
by the name the instruction itself has (the trace gives an op's whole HLO
text). 0.0 when the traced seconds hold no decode dispatch."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta
from benchmark.stats import median

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

MODULE = r"paged_decode"
STEP_OP = r"^%?paged_decode_attention[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"] or "loadgen" not in run:
        return None
    conf = run["config"]
    # the layers that keep every key: all of them where a file names no kinds
    layers = conf.get("layer_types_held", []).count("full_attention") \
        or conf["num_hidden_layers"]
    per_step = []
    for _, start, dur in tracing.module_events(trace, MODULE):
        n = len(tracing.ops_within(trace, start, start + dur, STEP_OP))
        if n >= layers:
            per_step.append(dur / (n / layers))
    if not per_step:
        return 0.0
    d = delta(run, "engine", "decode_tokens_emitted",
              "decode_steps_dispatched")
    live = d[0] / d[1] if d is not None and d[1] > 0 else 1.0
    need = architecture.part(conf, "counts").decode_weight_bytes(
        conf, run["weight_bytes_per_param"], live)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(per_step)
