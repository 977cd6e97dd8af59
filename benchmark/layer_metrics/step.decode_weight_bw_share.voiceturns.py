"""How close one decode step of the voice-turns cell comes to the time its
weights alone take to cross the memory bus, as
``step.decode_weight_bw_share.mixedlength`` reads it: this architecture's
``counts.decode_weight_bytes`` (the four published layers' eight attentions,
eight dense MLPs, routers and norms, the final norm and the head whole, and
of the 16 held experts a layer those that SOME of the window's mean live
streams are expected to choose: 53% at 48; a zero expert has no byte) over
the chip's bandwidth, over the median device time of a step. A step is EIGHT
executions of ``paged_latent_decode_attention``, two a published layer,
inside a decode-ONLY program: the steps that ride a chunk program
(``paged_mixed``) are another module and are not read. None where the run
has no trace; 0.0 when the traced seconds hold no decode-only step."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta
from benchmark.stats import median

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

MODULE = r"paged_decode"
STEP_OP = r"^%?paged_latent_decode_attention[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"] or "loadgen" not in run:
        return None
    programs = tracing.module_events(trace, MODULE)
    if not programs:
        return 0.0
    conf = run["config"]
    calls = 2 * conf["num_layers"]      # two attentions a published layer
    per_step = []
    for _, start, dur in programs:
        n = len(tracing.ops_within(trace, start, start + dur, STEP_OP))
        if n >= calls:
            per_step.append(dur / (n / calls))
    if not per_step:
        return 0.0
    d = delta(run, "engine", "decode_tokens_emitted",
              "decode_steps_dispatched")
    live = d[0] / d[1] if d is not None and d[1] > 0 else 1.0
    need = architecture.part(conf, "counts").decode_weight_bytes(
        conf, run["weight_bytes_per_param"], live)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(per_step)
