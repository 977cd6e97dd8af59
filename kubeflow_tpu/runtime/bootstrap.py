"""Worker-side bootstrap: the TPU-native rendezvous protocol.

This replaces the reference's per-framework env rendezvous — MASTER_ADDR/
MASTER_PORT/RANK/WORLD_SIZE for PyTorchJob ((U) training-operator
pkg/controller.v1/pytorch/envvar.go SetClusterSpec), TF_CONFIG for TFJob, and
hostfile+ssh+mpirun for MPIJob — with a single env contract feeding
``jax.distributed.initialize`` (SURVEY.md §2.6 "Distributed communication
backend" row):

    KFTPU_COORDINATOR_ADDRESS  worker-0's host:port (the coordination service)
    KFTPU_NUM_PROCESSES        world size
    KFTPU_PROCESS_ID           this worker's rank
    KFTPU_JOB                  owning job "namespace/name"
    KFTPU_REPLICA_INDEX        replica index (== process id for JAXJob)
    KFTPU_ENTRYPOINT           registered entrypoint or "module:function"
    KFTPU_CONFIG_JSON          entrypoint config (JSON)
    KFTPU_PARALLELISM_JSON     mesh axis sizes (JSON)
    KFTPU_PLATFORM             "tpu" (the chips) | "cpu" (virtual devices)
    KFTPU_VIRTUAL_DEVICES      chips this worker's job asked for: made as virtual
                               devices on cpu, the first N local chips on tpu
    KFTPU_HEARTBEAT_FILE       file this worker touches every few seconds
    KFTPU_WORKDIR              working/checkpoint directory
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Optional

ENV_PREFIX = "KFTPU_"

# Exit-code contract (RestartPolicy=ExitCode semantics, matching the
# reference's convention: retryable >= 128, permanent < 128).
EXIT_OK = 0
EXIT_PERMANENT = 1
EXIT_CONFIG_ERROR = 2
EXIT_RETRYABLE = 128
EXIT_PREEMPTED = 143  # SIGTERM


@dataclasses.dataclass
class WorkerEnv:
    coordinator_address: str
    num_processes: int
    process_id: int
    job: str
    replica_index: int
    entrypoint: str
    config: dict[str, Any]
    parallelism: dict[str, int]
    platform: str = "cpu"
    virtual_devices: int = 1
    heartbeat_file: Optional[str] = None
    workdir: Optional[str] = None
    rendezvous_timeout_seconds: float = 60.0

    def to_env(self) -> dict[str, str]:
        return {
            "KFTPU_COORDINATOR_ADDRESS": self.coordinator_address,
            "KFTPU_NUM_PROCESSES": str(self.num_processes),
            "KFTPU_PROCESS_ID": str(self.process_id),
            "KFTPU_JOB": self.job,
            "KFTPU_REPLICA_INDEX": str(self.replica_index),
            "KFTPU_ENTRYPOINT": self.entrypoint,
            "KFTPU_CONFIG_JSON": json.dumps(self.config),
            "KFTPU_PARALLELISM_JSON": json.dumps(self.parallelism),
            "KFTPU_PLATFORM": self.platform,
            "KFTPU_VIRTUAL_DEVICES": str(self.virtual_devices),
            "KFTPU_RENDEZVOUS_TIMEOUT": str(self.rendezvous_timeout_seconds),
            **({"KFTPU_HEARTBEAT_FILE": self.heartbeat_file} if self.heartbeat_file else {}),
            **({"KFTPU_WORKDIR": self.workdir} if self.workdir else {}),
        }

    @classmethod
    def from_env(cls, env: Optional[dict[str, str]] = None) -> "WorkerEnv":
        e = env if env is not None else os.environ
        try:
            return cls(
                coordinator_address=e["KFTPU_COORDINATOR_ADDRESS"],
                num_processes=int(e["KFTPU_NUM_PROCESSES"]),
                process_id=int(e["KFTPU_PROCESS_ID"]),
                job=e.get("KFTPU_JOB", "default/unknown"),
                replica_index=int(e.get("KFTPU_REPLICA_INDEX", e["KFTPU_PROCESS_ID"])),
                entrypoint=e["KFTPU_ENTRYPOINT"],
                config=json.loads(e.get("KFTPU_CONFIG_JSON", "{}")),
                parallelism=json.loads(e.get("KFTPU_PARALLELISM_JSON", "{}")),
                platform=e.get("KFTPU_PLATFORM", "cpu"),
                virtual_devices=int(e.get("KFTPU_VIRTUAL_DEVICES", "1")),
                heartbeat_file=e.get("KFTPU_HEARTBEAT_FILE"),
                workdir=e.get("KFTPU_WORKDIR"),
                rendezvous_timeout_seconds=float(e.get("KFTPU_RENDEZVOUS_TIMEOUT", "60")),
            )
        except (KeyError, ValueError) as exc:
            raise SystemExit(EXIT_CONFIG_ERROR) from exc


class Heartbeat:
    """Touches a file every ``interval`` seconds from a daemon thread.

    The failure detector: the controller declares a worker dead when the file
    mtime goes stale (coordinator heartbeats in jax.distributed cover the
    collective path; this covers the hung-Python / wedged-host case)."""

    def __init__(self, path: str, interval: float = 2.0):
        self.path = path
        self.interval = interval
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        self.beat()
        self._thread = threading.Thread(target=self._run, daemon=True, name="heartbeat")
        self._thread.start()

    def beat(self) -> None:
        with open(self.path, "w") as f:
            f.write(str(time.time()))

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.beat()
            except OSError:
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval + 1.0)
            self._thread = None


def bootstrap_worker(wenv: Optional[WorkerEnv] = None):
    """Initialize JAX distributed + build the mesh. Returns (env, mesh).

    Must be called before any JAX device access in the worker process."""
    wenv = wenv or WorkerEnv.from_env()

    if wenv.platform == "cpu":
        # Force this worker's own virtual-device count, replacing any
        # inherited flag (e.g. the test runner's 8-device setting). Set
        # before any jax import so the CPU client sees it.
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={wenv.virtual_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    if wenv.num_processes == 1 and not wenv.parallelism:
        # Control-plane-only worker (noop/sleep/fail…): skip the jax import
        # entirely — fast start, and SIGTERM isn't masked by native loads.
        return wenv, None

    import jax

    apply_platform(wenv, init_backend=False)

    if wenv.num_processes > 1:
        try:
            jax.distributed.initialize(
                coordinator_address=wenv.coordinator_address,
                num_processes=wenv.num_processes,
                process_id=wenv.process_id,
                initialization_timeout=int(wenv.rendezvous_timeout_seconds),
            )
        except Exception as exc:
            # A partial gang (missing peer, dead coordinator) is transient at
            # the job level: exit retryable so RestartPolicy=ExitCode re-gangs
            # instead of failing the job (SURVEY.md §2.6 failure semantics).
            # NOTE: the coordination client may LOG(FATAL) (process abort)
            # before Python sees an exception — the operator therefore also
            # treats ANY worker death before the gang reaches Running as a
            # retryable gang failure, regardless of exit code.
            print(f"rendezvous failed: {exc}", flush=True)
            raise SystemExit(EXIT_RETRYABLE)

    _require_platform(wenv.platform)
    from kubeflow_tpu.runtime.mesh import build_mesh

    mesh = build_mesh(wenv.parallelism) if wenv.parallelism else None
    return wenv, mesh


# Where the persistent compile cache lives when $JAX_COMPILATION_CACHE_DIR
# does not place it: ONE fixed path inside the checkout. The path is part
# of the cache key's context, so it is never derived from ~, a temporary
# name, a pid or the time — a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
# What JAX says of its compiles (``jax.monitoring``; the names of JAX 0.9):
# two counts and four durations, folded into six running totals. On a hit
# of the persistent cache the backend-compile event fires all the same, with
# the retrieval inside it: ``backend_compile_s`` holds ``retrieval_s``, and
# ``backend_compiles`` counts hits, misses and the compiles no cache was
# asked for alike (each is a program this process had not loaded before).
_COMPILE_COUNTS = {"/jax/compilation_cache/cache_hits": "hits",
                   "/jax/compilation_cache/cache_misses": "misses"}
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_DURATIONS = {
    _BACKEND_COMPILE_EVENT: "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    _TRACE_EVENT: "trace_lower_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower_s"}
# Process-wide like the JAX caches they count: THIS process's compiles.
_compile_totals: Optional[dict[str, float]] = None
_compile_lock = threading.Lock()
_cache_enabled = False


def watch_compiles() -> dict[str, float]:
    """Register, once a process however often it is called, the ONE set of
    listeners that folds JAX's compile events into running totals, and
    return the live totals: ``hits`` / ``misses`` of the persistent cache,
    ``backend_compiles`` and ``backend_compile_s`` (XLA's compile or the
    cache's retrieval, which is also ``retrieval_s`` by itself), and
    ``trace_lower_s`` (tracing to a jaxpr and lowering it to a module; a
    jitted function traced inside another's trace is inside that one's
    seconds and is counted there alone). ``enable_compilation_cache`` and
    the constructors of ``LLMEngine`` and ``Trainer`` call it, so the
    totals count on the CPU and with the cache off too."""
    global _compile_totals
    with _compile_lock:
        if _compile_totals is not None:
            return _compile_totals
        totals = _compile_totals = {
            "hits": 0, "misses": 0, "backend_compiles": 0,
            "backend_compile_s": 0.0, "retrieval_s": 0.0,
            "trace_lower_s": 0.0}
    import jax

    tracing = threading.local()     # .depth: traces open on this thread

    def _count(event: str, **_kw) -> None:
        name = _COMPILE_COUNTS.get(event)
        if name is not None:
            with _compile_lock:
                totals[name] += 1

    def _begin(event: str, _value, **_kw) -> None:
        if event == _TRACE_EVENT:       # JAX writes a scalar as it begins
            tracing.depth = getattr(tracing, "depth", 0) + 1

    def _duration(event: str, seconds: float, **_kw) -> None:
        name = _COMPILE_DURATIONS.get(event)
        if name is None:
            return
        if event == _TRACE_EVENT:
            tracing.depth = max(getattr(tracing, "depth", 1) - 1, 0)
            if tracing.depth:           # inside another trace's seconds
                return
        with _compile_lock:
            totals[name] += seconds
            if event == _BACKEND_COMPILE_EVENT:
                totals["backend_compiles"] += 1

    jax.monitoring.register_event_listener(_count)
    jax.monitoring.register_scalar_listener(_begin)
    jax.monitoring.register_event_duration_secs_listener(_duration)
    return totals


def enable_compilation_cache() -> str:
    """Persistent XLA compilation cache, shared by every process that
    compiles for the chip (gang workers, light-start trainers, serving
    replicas, bench.py). Returns the directory in use.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and no
    directory is set in code; otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. Errors propagate: a cache that cannot
    be placed is a mis-set path, not something to run without."""
    global _cache_enabled
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    watch_compiles()
    _cache_enabled = True
    return jax.config.jax_compilation_cache_dir


def compile_cache_stats() -> Optional[dict]:
    """``{"dir", "entries"}`` of this process's persistent compile cache
    beside ``watch_compiles``' totals; None when
    ``enable_compilation_cache`` never ran here. ``entries`` counts the
    executables now in the directory; hits/misses count this process's
    cacheable compiles (a miss is a compile that was written)."""
    if not _cache_enabled:
        return None
    import jax

    path = jax.config.jax_compilation_cache_dir
    entries = (sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
               if os.path.isdir(path) else 0)
    return {"dir": path, "entries": entries, **watch_compiles()}


def compile_counters() -> dict[str, float]:
    """``watch_compiles``' totals under the names ``counters()`` of the
    engine and the trainer carry them by: process-wide, so two engines of
    one process read the same numbers."""
    t = watch_compiles()
    return {"compile_backend_sum_s": t["backend_compile_s"],
            "compile_backend_n": t["backend_compiles"],
            "compile_retrieval_sum_s": t["retrieval_s"],
            "compile_trace_lower_sum_s": t["trace_lower_s"],
            "compile_cache_hits": t["hits"],
            "compile_cache_misses": t["misses"]}


def _require_platform(platform: str) -> None:
    """No fallback that hides the device: a ``tpu`` worker that finds any
    other backend exits with a config error instead of training or
    serving on the host and reporting success."""
    if platform != "tpu":
        return
    import jax

    found = jax.devices()[0].platform
    if found != "tpu":
        print(f"kftpu: worker platform is 'tpu' but jax initialised "
              f"{found!r}; refusing to run on it", flush=True)
        raise SystemExit(EXIT_CONFIG_ERROR)


def apply_platform(wenv: Optional["WorkerEnv"], *,
                   init_backend: bool = True) -> None:
    """Platform selection for a worker, before its first device access.

    bootstrap_worker returns before touching JAX for single-worker
    no-parallelism jobs (fast start for control-plane probes), so every
    entrypoint that initializes JAX itself calls this first — trainers on
    the light-start path and serving replicas. ``cpu``: the backend is
    pinned to the CPU. ``tpu``: the perf flags (runtime/xla_flags.py) and
    the persistent compile cache go on before the backend initializes,
    then the backend must BE a TPU. ``init_backend=False`` defers that
    check (a gang initializes jax.distributed first)."""
    if wenv is None:
        return
    if wenv.platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    elif wenv.platform == "tpu":
        from kubeflow_tpu.runtime.xla_flags import apply_xla_perf_flags

        apply_xla_perf_flags()
        enable_compilation_cache()
        if init_backend:
            _require_platform(wenv.platform)
    else:
        print(f"kftpu: unknown worker platform {wenv.platform!r} "
              "(cpu|tpu)", flush=True)
        raise SystemExit(EXIT_CONFIG_ERROR)


def single_worker_mesh(wenv: Optional["WorkerEnv"], axis: str = "data"):
    """apply_platform + a 1-axis local mesh (the training entrypoints'
    light-start path) over the chips the job asked for. On the cpu
    platform those are all of this process's virtual devices; a tpu
    worker opens every chip of its host, and a one-chip job on a
    four-chip host must still train on one."""
    import jax

    apply_platform(wenv)
    from kubeflow_tpu.runtime.mesh import build_mesh

    devices = jax.local_devices()
    if wenv is not None:
        devices = devices[:wenv.virtual_devices]
    return build_mesh({axis: len(devices)}, devices)


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
