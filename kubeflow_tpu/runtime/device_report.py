"""What a worker actually ran on: device, memory, compile cache, kernels.

A worker's parent never touches the chip (one process per chip), so what
it knows of the device comes from the worker: the trainer writes
``device_report.json`` into its workdir when it finishes, and a serving
replica answers ``GET /debug/device``. ``chip_smoke.py`` reads both.

The kernel part is read from the PROGRAM, not from the config that asked
for it: ``kernel_calls`` counts the Mosaic custom calls in a jitted
step's lowered text by kernel name, so a layer that a shape guard sent
back to XLA, or a kernel that fell to the interpreter, shows as a
missing name.
"""

from __future__ import annotations

import collections
import json
import os
import re
from typing import Any, Optional

REPORT_FILE = "device_report.json"
_KERNEL_RE = re.compile(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"')


def kernel_calls(lowered_text: str) -> dict[str, int]:
    """``{kernel name: call sites}`` of the Pallas TPU kernels in a lowered
    (StableHLO) program text. A scanned layer stack counts once: these are
    call sites in the program, not executions."""
    return dict(collections.Counter(_KERNEL_RE.findall(lowered_text)))


def lowered_kernel_calls(jitted, *args) -> dict[str, int]:
    """``kernel_calls`` of ``jitted`` lowered for ``args``. Call it right
    before the first dispatch with the same arguments: the trace is shared
    with that call, so this costs one extra lowering and no compile."""
    return kernel_calls(jitted.lower(*args).as_text())


def device_report() -> dict[str, Any]:
    """Device identity as JAX reports it in THIS process, per-device
    memory counters where the backend keeps them, the persistent compile
    cache's directory, entry count and this process's hits, misses and
    compile seconds, and the flag variables the backend started with."""
    import jax

    from kubeflow_tpu.runtime.bootstrap import compile_cache_stats

    devices = jax.local_devices()
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}
        memory.append({"id": d.id,
                       "bytes_in_use": stats.get("bytes_in_use"),
                       "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                       "bytes_limit": stats.get("bytes_limit")})
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(jax.devices()),
        "memory": memory,
        "compile_cache": compile_cache_stats(),
        # Where the backend's flags came from: the perf set rides
        # LIBTPU_INIT_ARGS, and XLA_FLAGS must carry no TPU-only flag
        # (runtime/xla_flags.py).
        "flags": {name: os.environ.get(name, "")
                  for name in ("LIBTPU_INIT_ARGS", "XLA_FLAGS")},
    }


def write_device_report(workdir: str, **sections) -> None:
    """``device_report()`` plus the caller's ``sections`` (programs, mesh,
    …) as ``<workdir>/device_report.json``, written atomically."""
    path = os.path.join(workdir, REPORT_FILE)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump({**device_report(), **sections}, f)
    os.replace(tmp, path)


def read_device_report(workdir: str) -> Optional[dict]:
    try:
        with open(os.path.join(workdir, REPORT_FILE)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None
