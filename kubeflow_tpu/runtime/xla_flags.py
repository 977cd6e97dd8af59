"""XLA latency-hiding / async-collective flag set for TPU training.

What remains around the fused kernels is overlap: with an fsdp mesh the
per-layer all-gathers (ZeRO-3 param gathers) and the gradient
reduce-scatters sit on the critical path unless XLA's latency-hiding
scheduler is allowed to run them asynchronously under compute. These are
the ``--xla_tpu_enable_async_collective_fusion`` family plus the
windowed-einsum ("collective matmul") threshold that lets an all-gather
feeding a matmul decompose into overlap-friendly steps.

Contract:

- ``apply_xla_perf_flags()`` merges the set into ``$LIBTPU_INIT_ARGS``
  WITHOUT overriding any flag the operator already pinned there
  (name-level merge), and must run before the JAX backend initializes —
  callers are the worker bootstrap (tpu platform) and bench.py.
  ``$XLA_FLAGS`` is left alone: jaxlib parses it itself and aborts the
  process on a flag it does not know ("Unknown flag in XLA_FLAGS"),
  and these are libtpu's flags, not jaxlib's. libtpu reads
  ``LIBTPU_INIT_ARGS`` and rejects an unknown name there too, so a
  misspelt entry still fails loudly at backend start.
- Escape hatch: ``KFTPU_XLA_PERF_FLAGS=off`` (or ``0``/``none``) skips
  the whole set; any other non-empty value REPLACES it verbatim (an
  operator debugging a miscompile can pin the exact flag set without
  editing code). Unset means the default set below.

The flags are TPU-only, so callers apply them on the tpu platform only.
"""

from __future__ import annotations

import os
from typing import Optional

# The latency-hiding set, name -> value. Every entry is a documented XLA
# TPU flag; the async-collective-fusion family makes collectives
# schedulable under compute, the latency-hiding scheduler actually moves
# them, and the windowed-einsum threshold (0 MiB = always) turns
# all-gather+matmul pairs into collective matmuls for the fsdp axis.
PERF_FLAGS: dict[str, str] = {
    "--xla_tpu_enable_async_collective_fusion": "true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps": "true",
    "--xla_tpu_overlap_compute_collective_tc": "true",
    "--xla_enable_async_all_gather": "true",
    "--xla_tpu_enable_latency_hiding_scheduler": "true",
    "--xla_jf_spmd_threshold_for_windowed_einsum_mib": "0",
}

ESCAPE_ENV = "KFTPU_XLA_PERF_FLAGS"
# Where the installed stack accepts TPU-only flags (libtpu's own parser).
FLAGS_ENV = "LIBTPU_INIT_ARGS"


def xla_perf_flags(existing: str = "",
                   env_value: Optional[str] = None) -> str:
    """The merged ``LIBTPU_INIT_ARGS`` value: ``existing`` plus every PERF_FLAG
    whose name is not already present. Pure (testable) core of
    ``apply_xla_perf_flags``."""
    if env_value is not None and env_value.strip().lower() in (
            "off", "0", "none", "false"):
        return existing
    if env_value is not None and env_value.strip():
        extra = env_value.strip()
    else:
        have = {f.split("=", 1)[0] for f in existing.split() if f}
        extra = " ".join(f"{k}={v}" for k, v in PERF_FLAGS.items()
                         if k not in have)
    return f"{existing} {extra}".strip() if extra else existing


def apply_xla_perf_flags() -> bool:
    """Merge the latency-hiding flag set into ``$LIBTPU_INIT_ARGS``
    (idempotent, never overrides operator-pinned flags). Returns True when anything
    was added. Must run before the JAX backend initializes; no-op under
    the ``KFTPU_XLA_PERF_FLAGS=off`` escape hatch."""
    existing = os.environ.get(FLAGS_ENV, "")
    merged = xla_perf_flags(
        existing,
        # contract: operator-facing knob — set by the user, never by the tree
        os.environ.get(ESCAPE_ENV))
    if merged != existing:
        os.environ[FLAGS_ENV] = merged
        return True
    return False
