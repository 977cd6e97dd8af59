"""Device mesh construction from a ParallelismSpec.

Axis design (SURVEY.md §2.6 "TPU-native equivalent" column): one canonical
axis order, outermost → innermost by physical distance, so that
latency-sensitive collectives land on nearest ICI neighbors:

    dcn       — between slices (data-parallel over DCN; megascale-style)
    pipeline  — stages (ppermute to ICI neighbors)
    data      — replicated data parallel (gradient psum)
    fsdp      — sharded data parallel (all-gather/reduce-scatter of params)
    expert    — MoE expert parallel (all-to-all)
    seq       — sequence/context parallel (ring attention KV ppermute)
    model     — tensor parallel (per-layer psum/psum_scatter; innermost)

All seven axes always exist on the mesh (size-1 axes cost nothing and keep
PartitionSpec rules uniform). `jax.make_mesh` performs topology-aware device
assignment on real TPU; on CPU it degrades to row-major order.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from kubeflow_tpu.core.jobs import ParallelismSpec

MESH_AXES: tuple[str, ...] = (
    "dcn", "pipeline", "data", "fsdp", "expert", "seq", "model",
)


def build_mesh(
    axis_sizes: dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the canonical 7-axis mesh.

    ``axis_sizes`` maps axis name → degree; missing axes default to 1. The
    product must equal the device count."""
    sizes = tuple(int(axis_sizes.get(a, 1)) for a in MESH_AXES)
    n = int(np.prod(sizes))
    if devices is None:
        devices = jax.devices()
    if n != len(devices):
        raise ValueError(
            f"mesh axes {dict(zip(MESH_AXES, sizes))} product {n} "
            f"!= device count {len(devices)}"
        )
    # Multislice: devices spanning >1 TPU slice need the hybrid ICI×DCN
    # assignment — the per-slice torus solver can't see a 2-slice device
    # list as one physical mesh. The dcn axis (outermost by design) gets
    # the slice dimension; everything else stays within a slice, so only
    # dcn-axis collectives cross the data-center network (megascale-style).
    slice_ids = {getattr(d, "slice_index", 0) or 0 for d in devices}
    if len(slice_ids) > 1:
        if sizes[0] != len(slice_ids):
            raise ValueError(
                f"devices span {len(slice_ids)} slices but the dcn axis is "
                f"{sizes[0]}; set dcn == slice count so only dcn collectives "
                f"cross DCN")
        from jax.experimental import mesh_utils

        dcn_shape = (sizes[0],) + (1,) * (len(MESH_AXES) - 1)
        ici_shape = (1,) + sizes[1:]
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=list(devices),
            allow_split_physical_axes=True)
        return Mesh(dev_array, MESH_AXES)
    # Auto axis types = classic GSPMD propagation (annotate params/inputs,
    # XLA infers the rest and inserts collectives). JAX's default
    # Explicit mode rejects ops whose output sharding is ambiguous (sharded
    # attention einsums, vocab-parallel gathers), which is exactly the work
    # we delegate to the compiler.
    try:
        return jax.make_mesh(
            sizes, MESH_AXES, devices=devices,
            axis_types=(jax.sharding.AxisType.Auto,) * len(MESH_AXES))
    except NotImplementedError:
        # Topology-aware assignment needs each logical axis to be a product
        # of physical torus axes (e.g. fsdp=8 over a 4x4x4 pod wants a
        # split 4x2). Retry allowing physical-axis splits — still
        # locality-aware, unlike a raw reshape.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            sizes, devices=list(devices), allow_split_physical_axes=True)
        return Mesh(dev_array, MESH_AXES)


def mesh_from_parallelism(
    spec: ParallelismSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    return build_mesh(spec.axis_sizes(), devices)


def infer_parallelism(num_devices: int, *, prefer: str = "fsdp") -> ParallelismSpec:
    """Default policy when a job doesn't pin axes: put everything on one axis
    (fsdp by default — the right default for LLM pretraining at this scale)."""
    return ParallelismSpec(**{prefer: num_devices})


def batch_sharding_axes() -> tuple[str, ...]:
    """Mesh axes the global batch dimension is sharded over (pipeline is NOT
    one of them — microbatches flow through stages instead)."""
    return ("dcn", "data", "fsdp")
