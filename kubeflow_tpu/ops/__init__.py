"""TPU kernel layer: attention and other hot ops with switchable impls.

Every op exposes a pure-XLA reference implementation (runs anywhere, used for
CPU tests and as the numerics oracle) and, where it pays, a Pallas TPU kernel
(`impl="pallas"`) or a distributed variant (ring attention). The seam keeps
models oblivious to which implementation runs — the op registry picks based
on platform and config.
"""

import jax


def auto_interpret() -> bool:
    """Whether a Pallas kernel whose caller did not say runs in the
    interpreter: everywhere except on a TPU backend. ONE rule for every
    kernel in this package (the interpreter is the CPU test path; a worker
    on the tpu platform has asserted its backend by the time it traces,
    runtime/bootstrap.py, so it can never reach interpret mode)."""
    return jax.default_backend() != "tpu"


# Fast (vector) memory one kernel may plan for. The TPU compiler holds a
# kernel to a 16 MiB scoped limit and counts what the pipeline keeps
# resident: every in/out block twice (double buffering) plus scratch.
# Block choosers keep that same sum under this budget; the 2 MiB left
# over is for the temporaries the compiler also charges (measured on a
# described v5e: 17.00M charged where the sum said 17.0, 17.86M where it
# said 16.3 — tests/test_chip_compile.py pins the shapes).
VMEM_BUDGET_BYTES = 14 * 2**20

from kubeflow_tpu.ops.attention import multi_head_attention  # noqa: E402

__all__ = ["VMEM_BUDGET_BYTES", "auto_interpret", "multi_head_attention"]
