"""How close one decode step of the reasoning cell comes to the time its
weights alone take to cross the memory bus: the architecture's
``counts.decode_weight_bytes`` (all 3.85 B parameters: every layer of both
decoders and the tied head, dense, so the live streams move nothing) over
the bus's published bandwidth, over the median device time of a step. A
step is found as ``step.decode_weight_bw_share.mixedlength`` finds it, by the
executions of the global decode kernel inside a decode program, of which
THIS stack makes ``counts.decode_attention_calls`` a step (8: the one
full-attention layer and the seven cross layers that read its pages). The
states (0.19 GB a 32-stream step), the rings and the eight reads of one
layer's rows (2.7-4.6 GB a step at 2k-3.5k tokens a stream) are left out, so
the share falls as the contexts grow: at this cell's contexts the step moves
a third to a half as many bytes of cache as of weights (PERF.md section 5).
None where the run has no trace; 0.0 when the traced seconds hold no decode
dispatch."""

from benchmark import architecture, tracing
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
    counts = architecture.part(conf, "counts")
    # (an architecture whose counts do not say: one call a step)
    calls = getattr(counts, "decode_attention_calls", lambda _: 1)(conf)
    per_step = []
    for _, start, dur in tracing.module_events(trace, MODULE):
        n = len(tracing.ops_within(trace, start, start + dur, STEP_OP))
        if n >= calls:
            per_step.append(dur / (n / calls))
    if not per_step:
        return 0.0
    need = counts.decode_weight_bytes(conf, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(per_step)
