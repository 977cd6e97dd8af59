"""How close one decode step of the agent-context cell comes to the time its
weights alone take to cross the memory bus, as
``step.decode_weight_bw_share.mixedlength`` reads it: this architecture's
``counts.decode_weight_bytes`` (the five layers' attention, indexers, dense
MLP, routers, shared experts, the final norm and the head whole, and of the
16 held experts a layer those that SOME of the window's mean live streams
chose) over the chip's bandwidth, over the median device time of a step. A
step is five executions of ``paged_latent_decode_attention``, one a layer,
inside a decode-ONLY program: the steps that ride a chunk program
(``paged_mixed``, most of this cell's) are another module and are not read.
The program's own copy of ``wqb`` (268 MB a step: PERF.md section 7) is in
the time and not in the bytes, so repairing it shows here. None where the
run has no trace; 0.0 when the traced seconds hold no decode-only step."""

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
    conf = run["config"]
    layers = conf["num_hidden_layers"]
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
