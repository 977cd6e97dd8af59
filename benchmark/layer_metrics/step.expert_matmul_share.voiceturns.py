"""Share of the device's busy time in the traced seconds of the voice-turns
cell that the experts' GROUPED MATRIX PRODUCTS took: the events of the
Pallas grouped matmul (``gmm``: a chunk program's whole tiles of sorted
rows) and of XLA's ``ragged-dot`` (a decode-only step's few rows), three a
layer a program, over ``tracing.busy_s``. The trace's op names carry no
``jax.named_scope``, so the products are found by the names their
instructions have. One chip of 32 holds 2% of a layer's experts, and a third
of a token's choices are zero experts: what is left for the matrix unit is a
quarter of an expert a token, so this share says how much of a step the
routing's choices can move. Lower is better: the held experts' work is the
model's, and what the share holds beyond it (tiles of a few rows, weights
read for one row) is the program's. The sorted rows' gather and scatter
around the products are fusions of other names and are not in it.

None where the run has no trace. 0.0 when the traced seconds hold no such
product."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "lower", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

PRODUCTS = r"^%?(gmm|ragged-dot|ragged_dot)[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    busy = tracing.busy_s(trace)
    if busy <= 0:
        return 0.0
    return 100.0 * sum(dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), PRODUCTS)) / busy
