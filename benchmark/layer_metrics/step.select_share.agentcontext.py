"""Share of the device's busy time in the traced seconds of the agent-context
cell that the SELECTION took: the events of the kernel ``dsa_select``
(``ops/paged_attention.py::paged_select_keys``: each query's 2048 best-scored
keys, exactly, by counting against a threshold found bit by bit) over
``tracing.busy_s``. The trace's op names carry no ``jax.named_scope`` (PR
43's probe), so the selection is found by the name its kernel has; the scope
``dsa.select`` stands around it in the lowered text. What the selection
costs and buys nothing by itself: lower is better, and a faster selection (a
cheaper threshold, fewer passes, pages nobody can select skipped) shows here.

None where the run has no trace. 0.0 when the traced seconds hold no call of
the kernel (a program without an indexer)."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "lower", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?dsa_select[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    busy = tracing.busy_s(trace)
    if busy <= 0:
        return 0.0
    return 100.0 * sum(dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)) / busy
