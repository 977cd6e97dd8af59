"""The process's dealings with the device: set-up before the backend starts,
the refusal to run off the chip, the compile counter and the memory reading.
"""

from __future__ import annotations

import os
import time

from benchmark.manifest import ROOT

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class DeviceRefused(Exception):
    """No accelerator, another number of chips than the cell asks for, or a
    device the peak table does not know."""


def prepare_process(platform_is_tpu: bool) -> str | None:
    """Before JAX initialises its backend: the program's own TPU flag set
    and its persistent compile cache (``runtime/bootstrap.py``'s rule:
    ``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    as a worker of the platform gets them. Every program goes to the cache,
    however quickly it compiled, so a second run compiles nothing."""
    if platform_is_tpu:
        # libtpu keeps its own log under /tmp/tpu_logs unless told
        # otherwise; a run writes nothing outside its checkout.
        log_dir = os.path.join(ROOT, "benchmark_out", "tpu_logs")
        os.makedirs(log_dir, exist_ok=True)
        os.environ.setdefault("TPU_LOG_DIR", log_dir)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not platform_is_tpu:
        return None
    from kubeflow_tpu.runtime.bootstrap import enable_compilation_cache
    from kubeflow_tpu.runtime.xla_flags import apply_xla_perf_flags

    apply_xla_perf_flags()
    return enable_compilation_cache()


def require_devices(chips: int, *, allow_cpu: bool = False) -> dict:
    """The devices of this run as JAX reports them. Off the chip this
    raises: there is no CPU fallback (``allow_cpu`` is the CPU rehearsal's,
    passed by a test as a function argument and reachable from no flag or
    variable)."""
    import jax

    from benchmark.peaks import peaks_for

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not allow_cpu:
        raise DeviceRefused(f"JAX found platform {platform!r}, not a TPU")
    if len(devs) < chips:
        raise DeviceRefused(f"the cell asks for {chips} chips; JAX found "
                            f"{len(devs)}")
    info = {"platform": platform, "kind": kind, "count": chips,
            "peaks": None}
    if platform == "tpu":
        info["peaks"] = peaks_for(kind)       # unknown device: an error
    return info


class CompileCounter:
    """Counts backend compiles (cache retrievals included: either means a
    program the warm-up did not cover) between ``start`` and ``stop``."""

    def __init__(self):
        import jax

        self.count = 0
        self.names: list[str] = []
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self._on and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def start(self) -> None:
        self.count, self.names, self._on = 0, [], True

    def stop(self) -> int:
        self._on = False
        return self.count


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    does not report it, as the CPU's)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))
