"""TPU slice topology model.

The reference is topology-blind (SURVEY.md §2.6: `nvidia.com/gpu` resource
counts, no ICI awareness). TPU-native scheduling is slice-granular: a job
takes a whole sub-slice whose ICI torus shape determines the mesh. This module
models generations (v4/v5e/v5p/v6e), slices, and their host/chip structure,
and detects the local environment as a one-slice cluster.
"""

from __future__ import annotations

import dataclasses
import json
import math
import subprocess
import sys
from typing import Optional

from pydantic import BaseModel, ConfigDict

from kubeflow_tpu.runtime.procman import PLATFORMS, platform_env


class ChipGeneration(BaseModel):
    """Hardware constants of one chip generation, one row of ``CHIPS``."""

    model_config = ConfigDict(extra="forbid", frozen=True)

    name: str                    # the CRD-facing generation name
    device_kind: str             # what jax reports: devices()[0].device_kind
    hbm_gb: float
    # Peak dense bf16 TFLOP/s per chip; None where there is no published
    # peak (the CPU emulation platform), so no utilization is ever
    # computed against an invented one.
    bf16_tflops: Optional[float]
    chips_per_host: int
    torus_dims: int              # 3 for v4/v5p (3D torus), 2 for v5e/v6e
    source: str


# THE peaks table: one row per device the platform knows, keyed by the
# ``device_kind`` string JAX reports. A device that is not here is an
# error (``chip_for_device_kind``), never a default row.
_CLOUD = "Google Cloud TPU documentation, system architecture: "
CHIPS: dict[str, ChipGeneration] = {c.device_kind: c for c in (
    ChipGeneration(name="v4", device_kind="TPU v4", hbm_gb=32,
                   bf16_tflops=275, chips_per_host=4, torus_dims=3,
                   source=_CLOUD + "TPU v4"),
    ChipGeneration(name="v5e", device_kind="TPU v5 lite", hbm_gb=16,
                   bf16_tflops=197, chips_per_host=4,
                   torus_dims=2, source=_CLOUD + "TPU v5e"),
    ChipGeneration(name="v5p", device_kind="TPU v5", hbm_gb=95,
                   bf16_tflops=459, chips_per_host=4,
                   torus_dims=3, source=_CLOUD + "TPU v5p"),
    ChipGeneration(name="v6e", device_kind="TPU v6 lite", hbm_gb=32,
                   bf16_tflops=918, chips_per_host=4,
                   torus_dims=2, source=_CLOUD + "TPU v6e"),
    ChipGeneration(name="cpu", device_kind="cpu", hbm_gb=4,
                   bf16_tflops=None, chips_per_host=8,
                   torus_dims=2,
                   source="virtual devices for tests; no peak exists"),
)}
GENERATIONS: dict[str, ChipGeneration] = {c.name: c for c in CHIPS.values()}


def chip_for_device_kind(device_kind: str) -> ChipGeneration:
    """The peaks row for a device as JAX names it; unknown is an error."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            f"unknown device_kind {device_kind!r}: no peaks row in "
            f"runtime/topology.py CHIPS (known: {sorted(CHIPS)})") from None


class SliceTopology(BaseModel):
    """One TPU slice: a contiguous ICI domain (e.g. v5p 4x4x4, v5e 4x2)."""

    model_config = ConfigDict(extra="forbid")

    name: str
    generation: str = "v5e"
    dims: tuple[int, ...] = (1,)      # ICI torus/mesh dims, e.g. (4, 4, 4)

    @property
    def num_chips(self) -> int:
        return math.prod(self.dims)

    @property
    def gen(self) -> ChipGeneration:
        return GENERATIONS[self.generation]

    @property
    def num_hosts(self) -> int:
        return max(1, self.num_chips // self.gen.chips_per_host)

    @classmethod
    def parse(cls, name: str, spec: str, generation: str = "v5e") -> "SliceTopology":
        """Parse "4x4x4"-style topology strings (the CRD-facing format)."""
        dims = tuple(int(d) for d in spec.lower().split("x"))
        if not dims or any(d < 1 for d in dims):
            raise ValueError(f"bad topology spec {spec!r}")
        return cls(name=name, generation=generation, dims=dims)


@dataclasses.dataclass
class Cluster:
    """Inventory of slices available to the control plane."""

    slices: list[SliceTopology]

    @property
    def total_chips(self) -> int:
        return sum(s.num_chips for s in self.slices)

    def get_slice(self, name: str) -> Optional[SliceTopology]:
        for s in self.slices:
            if s.name == name:
                return s
        return None


_PROBE_SRC = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, "
    "'device_kind': d[0].device_kind, 'count': jax.local_device_count()}))")


def probe_devices(platform: str, timeout: float = 120.0) -> dict:
    """``{"platform", "device_kind", "count"}`` as a worker of ``platform``
    would see them, from a short child that has exited by the time this
    returns. A chip belongs to one process at a time, so the control
    plane, which only counts the chips and then starts the workers that
    use them, must never initialise the backend itself."""
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE_SRC], env=platform_env(platform),
            capture_output=True, text=True, timeout=timeout, check=True)
        info = json.loads(out.stdout.strip().splitlines()[-1])
    except subprocess.CalledProcessError as exc:
        last = (exc.stderr.strip().splitlines() or ["no output"])[-1]
        raise RuntimeError(
            f"device probe for platform {platform!r} failed "
            f"(rc={exc.returncode}): {last}") from exc
    except (subprocess.TimeoutExpired, OSError, ValueError,
            IndexError) as exc:
        raise RuntimeError(
            f"device probe for platform {platform!r} failed: {exc}") from exc
    if info["platform"] != platform:
        raise RuntimeError(
            f"device probe asked for platform {platform!r} and found "
            f"{info['platform']!r} ({info['device_kind']})")
    return info


def detect_local_cluster(num_chips: Optional[int] = None,
                         generation: Optional[str] = None,
                         platform: str = "cpu") -> Cluster:
    """The local environment as a one-slice cluster of ``platform`` devices.

    ``num_chips``/``generation`` override detection (a bigger virtual cpu
    cluster than physically present is explicitly allowed; for the tpu
    platform give both to skip the probe). The ``cpu`` platform counts
    this process's own virtual devices — the CPU backend is shareable.
    The ``tpu`` platform asks a probe child (``probe_devices``): the
    caller is the parent of the workers that will own the chips. An
    unknown device kind or a failed probe is an error, never a default
    cluster."""
    if platform not in PLATFORMS:
        raise ValueError(f"unknown platform {platform!r}; one of {PLATFORMS}")
    if platform == "cpu":
        generation = generation or "cpu"
        if num_chips is None:
            import jax

            num_chips = jax.local_device_count()
    elif num_chips is None or generation is None:
        info = probe_devices(platform)
        generation = generation or chip_for_device_kind(
            info["device_kind"]).name
        num_chips = num_chips or info["count"]
    # Factor chip count into a near-square 2D mesh shape (v5e-style).
    a = int(math.sqrt(num_chips))
    while a > 1 and num_chips % a:
        a -= 1
    dims = (a, num_chips // a) if a > 1 else (num_chips,)
    return Cluster(slices=[SliceTopology(name="local", generation=generation, dims=dims)])
