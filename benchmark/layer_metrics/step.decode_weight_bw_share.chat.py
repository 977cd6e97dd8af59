"""How close one decode step comes to the time the weights alone take to
cross the memory bus: (bytes of weights a step must read / the chip's
published bandwidth) over the median device time of one decode step. A floor
on purpose: the KV cache's bytes are left out, so it cannot pass 100%.

One execution of the decode dispatch runs up to ``decode_steps`` steps and
leaves early when every slot is done; its steps are counted in the trace, as
the executions of the paged-attention kernel inside it over the layers. 0.0
when the traced seconds hold no decode dispatch."""

from benchmark import architecture, tracing
from benchmark.stats import median

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "itl_p95_ms"}

MODULE = r"paged_decode"
STEP_OP = r"paged_decode_attention"


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"] or "loadgen" not in run:
        return None
    layers = run["config"]["num_hidden_layers"]
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
