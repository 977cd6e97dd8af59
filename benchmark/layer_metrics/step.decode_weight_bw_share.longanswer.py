"""How close one decode step of the long-answer cell comes to the time its
weights alone take to cross the memory bus: (bytes of weights a step must
read whatever its batch / the chip's published bandwidth) over the median
device time of one decode step: the step's share of its roofline, which is
the bus. A floor on purpose (the architecture's ``counts.
decode_weight_bytes``: the experts ONE token needs; a step over 64 slots
reads nearly every expert, eight times that, and the cache's and the conv
state's bytes are left out), so it cannot pass 100%.

One execution of the decode dispatch runs up to ``decode_steps`` steps and
leaves early when every slot is done; its steps are counted in the trace, as
the executions of the packed-row attention kernel inside it over the
attention layers held. The kernel is found by the name the instruction itself
has (the trace gives an op's whole HLO text). 0.0 when the traced seconds
hold no decode dispatch."""

from benchmark import architecture, tracing
from benchmark.stats import median

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

MODULE = r"paged_decode"
STEP_OP = r"^%?paged_packed_decode_attention[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"] or "loadgen" not in run:
        return None
    conf = run["config"]
    # the layers that attend: all of them where a file names no kinds
    layers = conf.get("layer_types_held", []).count("full_attention") \
        or conf["num_hidden_layers"]
    per_step = []
    for _, start, dur in tracing.module_events(trace, MODULE):
        n = len(tracing.ops_within(trace, start, start + dur, STEP_OP))
        if n >= layers:
            per_step.append(dur / (n / layers))
    if not per_step:
        return 0.0
    least = architecture.part(run["config"], "counts").decode_weight_bytes(
        run["config"], run["weight_bytes_per_param"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / median(per_step)
