"""The kernel ``ssd_step``'s share of its roofline, the memory bus, in the
assistant cell: the bytes ONE call (one layer's SSD mixer in one decode step)
has to move for the window's mean live streams, over the chip's published
bandwidth, over the mean device time of a call.

Bytes: the architecture's ``counts.ssd_step_bytes``: a live stream's [32,
256, 128] float32 state read and written where it lies in the pool (8.4 MB)
and its convolution tail in and out; a dead row of the step moves nothing.
Live streams a step are the window's mean, Δ``decode_tokens_emitted`` /
Δ``decode_steps_dispatched`` of ``LLMEngine.counters()``, as
``kernel.kda_step_bw_share.longdoc`` takes them. Time: the events of
``ssd_step`` in the trace (five a step: one a layer held), found by the name
the instruction itself has. It cannot pass 100% while the time covers the
moves.

None where the run has no trace or the program no such counters. 0.0 when
the window dispatched no step or the traced seconds hold no call."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?ssd_step[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    d = delta(run, "engine", "decode_tokens_emitted",
              "decode_steps_dispatched")
    if d is None:
        return None
    tokens, steps = d
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    if steps <= 0 or not calls:
        return 0.0
    need = architecture.part(run["config"], "counts").ssd_step_bytes(
        run["config"], tokens / steps, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] \
        / (sum(calls) / len(calls))
