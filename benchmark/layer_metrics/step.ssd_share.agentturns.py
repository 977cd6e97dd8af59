"""Share of the device's busy time in the traced seconds of the agent-turns
cell that the Mamba layers' RECURRENCE took: the events of the kernels
``ssd_chunk`` (a chunk program's rows, five calls a program) and ``ssd_step``
(the live streams' one token, five a step, in a decode-only program or riding
a chunk program), found by the names the instructions themselves have, over
``tracing.busy_s``. What is left of a Mamba layer (its in- and out-projection,
the convolution, the gated norm) are matrix products and fusions of other
names and are not in it. Lower is better: the recurrence's work is the
model's (``kernel.ssd_chunk_roofline_share.agentturns`` and
``kernel.ssd_step_bw_share.agentturns`` say how near its roofline each kernel
runs), and what the share holds beyond it is the program's.

None where the run has no trace. 0.0 when the traced seconds hold no such
call."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "lower", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

KERNELS = r"^%?ssd_(chunk|step)[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    busy = tracing.busy_s(trace)
    if busy <= 0:
        return 0.0
    return 100.0 * sum(dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNELS)) / busy
