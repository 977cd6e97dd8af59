"""The seams between the platform and the chip (ISSUE 21), CPU-only and fast.

What these pin: one process per chip (the control plane of tpu workers never
initialises a backend, a worker's platform comes from ITS env and not the
parent's), no fallback that hides the device (a tpu worker on a CPU is a
config error, an unknown device kind has no peaks), a compile cache that can
be placed from outside, and a smoke script that cannot pass off the chip.
Anything that would initialise a backend runs in a child: this process
already has the CPU one (conftest.py).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, *, env=None, cwd=REPO, timeout=120):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    full = dict(os.environ)
    full["PYTHONPATH"] = REPO
    full.update(env or {})
    for k, v in list(full.items()):
        if v is None:
            del full[k]
    return subprocess.run(argv, env=full, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


# -- one process per chip ------------------------------------------------------

def test_control_plane_of_tpu_workers_initialises_no_backend():
    """ControlPlane(platform="tpu") with no cluster given learns the chips
    from the probe child (stubbed here: the sandbox has none) and must come
    up with NO JAX backend in its own process."""
    out = _run("""
import kubeflow_tpu.runtime.topology as topo
asked = []
def fake_probe(platform, timeout=120.0):
    asked.append(platform)
    return {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 4}
topo.probe_devices = fake_probe
from kubeflow_tpu.operator.control_plane import ControlPlane, ControlPlaneConfig
import tempfile
cp = ControlPlane(ControlPlaneConfig(base_dir=tempfile.mkdtemp(),
                                     platform="tpu", launch_processes=False))
from jax._src import xla_bridge
s = cp.cluster.slices[0]
print(asked, s.generation, s.num_chips, xla_bridge.backends_are_initialized())
cp.stop()
""", env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("\n")[-2] == "['tpu'] v5e 4 False"


def test_probe_child_reports_the_platform_it_was_given():
    from kubeflow_tpu.runtime.topology import probe_devices

    info = probe_devices("cpu")
    assert info["platform"] == "cpu" and info["device_kind"] == "cpu"
    assert info["count"] >= 1


def test_failed_probe_is_an_error_not_a_default_cluster(monkeypatch):
    from kubeflow_tpu.runtime import topology

    def boom(*a, **kw):
        raise subprocess.CalledProcessError(
            1, a, stderr="RuntimeError: Unable to initialize backend 'tpu'")

    monkeypatch.setattr(topology.subprocess, "run", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        topology.detect_local_cluster(platform="tpu")


def test_unknown_device_kind_has_no_peaks():
    from kubeflow_tpu.runtime.topology import (
        CHIPS, GENERATIONS, chip_for_device_kind,
    )

    v5e = chip_for_device_kind("TPU v5 lite")
    assert (v5e.name, v5e.bf16_tflops, v5e.hbm_gb) == ("v5e", 197, 16)
    assert all(c.source for c in CHIPS.values())
    assert "sim" not in GENERATIONS
    assert chip_for_device_kind("cpu").bf16_tflops is None
    with pytest.raises(ValueError, match="unknown device_kind 'TPU v9'"):
        chip_for_device_kind("TPU v9")


@pytest.mark.parametrize("parent,platform", [
    ("cpu", "tpu"),      # a parent kept off the chip must not drag the
    (None, "tpu"),       # worker onto the CPU with it
    ("tpu", "cpu"),      # and a cpu worker never reaches for the chip
    (None, "cpu"),
])
def test_child_platform_comes_from_worker_env(monkeypatch, parent, platform):
    from kubeflow_tpu.runtime import procman
    from kubeflow_tpu.runtime.bootstrap import WorkerEnv

    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    launched = {}

    class FakePopen:
        pid = 1

        def __init__(self, argv, env, **kw):
            launched.update(env)

        def poll(self):
            return 0

    monkeypatch.setattr(procman.subprocess, "Popen", FakePopen)
    procman.LocalProcessManager().launch("w", WorkerEnv(
        coordinator_address="127.0.0.1:0", num_processes=1, process_id=0,
        job="default/j", replica_index=0, entrypoint="noop", config={},
        parallelism={}, platform=platform))
    assert launched["JAX_PLATFORMS"] == platform
    assert launched["KFTPU_PLATFORM"] == platform


def test_unknown_platform_is_refused():
    from kubeflow_tpu.runtime.procman import platform_env

    with pytest.raises(ValueError, match="unknown platform"):
        platform_env("gpu")


def test_tpu_runtime_refuses_a_second_worker_on_the_host(tmp_path):
    """examples/jaxjob.yaml (2 workers x 2 chips) on the tpu platform: the
    second worker is refused at once, by name, with a config error — both
    would have opened every chip."""
    from kubeflow_tpu.core.jobs import (
        TPUResourceSpec, Worker, WorkerPhase, WorkerSpec, WorkerStatus,
        WorkloadSpec,
    )
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.store import ObjectStore
    from kubeflow_tpu.operator.worker_runtime import WorkerRuntime
    from kubeflow_tpu.runtime.bootstrap import EXIT_CONFIG_ERROR

    class FakeProcman:
        def __init__(self):
            self.launched = []

        def alive(self):
            return list(self.launched)

        def get(self, name):
            return None

        def launch(self, name, wenv, extra_env=None):
            self.launched.append(name)
            return type("H", (), {"pid": 7})()

        def shutdown(self):
            pass

    store = ObjectStore()
    procman = FakeProcman()
    rt = WorkerRuntime(store, procman, base_dir=str(tmp_path),
                       platform="tpu", heartbeat_timeout=None)
    for i in range(2):
        store.create(Worker(
            metadata=ObjectMeta(name=f"demo-worker-{i}"),
            spec=WorkerSpec(job="default/demo", replica_index=i,
                            num_workers=2,
                            template=WorkloadSpec(entrypoint="noop"),
                            resources=TPUResourceSpec(tpu_chips=2)),
            status=WorkerStatus(phase=WorkerPhase.PENDING)))
    rt.step()
    first, second = (store.get(Worker, f"demo-worker-{i}") for i in range(2))
    assert procman.launched == ["default.demo-worker-0"]
    assert first.status.phase == WorkerPhase.RUNNING
    assert second.status.phase == WorkerPhase.FAILED
    assert second.status.exit_code == EXIT_CONFIG_ERROR
    assert "default.demo-worker-0 already holds this host's chips" \
        in second.status.message
    rt.shutdown()


# -- no fallback that hides the device -----------------------------------------

def test_tpu_worker_that_finds_a_cpu_is_a_config_error(tmp_path):
    """Both paths to a worker's first device access — the light-start
    entrypoint's ``apply_platform`` and the gang's ``bootstrap_worker`` —
    end in EXIT_CONFIG_ERROR, which worker_main turns into the exit code."""
    from kubeflow_tpu.runtime.bootstrap import EXIT_CONFIG_ERROR

    out = _run("""
from kubeflow_tpu.runtime.bootstrap import (
    WorkerEnv, apply_platform, bootstrap_worker)
codes = []
for parallelism in ({}, {"fsdp": 1}):
    wenv = WorkerEnv(
        coordinator_address="127.0.0.1:0", num_processes=1, process_id=0,
        job="default/j", replica_index=0, entrypoint="llm_pretrain",
        config={}, parallelism=parallelism, platform="tpu")
    try:
        apply_platform(wenv) if not parallelism else bootstrap_worker(wenv)
        codes.append(None)
    except SystemExit as exc:
        codes.append(exc.code)
print(codes)
""", env={"JAX_PLATFORMS": "cpu",
          "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == \
        str([EXIT_CONFIG_ERROR, EXIT_CONFIG_ERROR])
    assert out.stdout.count(
        "worker platform is 'tpu' but jax initialised 'cpu'") == 2


def test_light_start_mesh_spans_the_chips_the_job_asked_for():
    """A tpu worker opens every chip of its host; a one-chip job on a
    four-chip host must still train on one device."""
    import jax

    from kubeflow_tpu.runtime.bootstrap import WorkerEnv, single_worker_mesh

    wenv = WorkerEnv(
        coordinator_address="127.0.0.1:0", num_processes=1, process_id=0,
        job="default/j", replica_index=0, entrypoint="llm_pretrain",
        config={}, parallelism={}, platform="cpu", virtual_devices=2)
    assert len(jax.devices()) > 2
    mesh = single_worker_mesh(wenv, axis="fsdp")
    assert mesh.shape["fsdp"] == 2 and mesh.size == 2


# -- the compile cache ---------------------------------------------------------

_CACHE_SRC = """
import json, jax
from kubeflow_tpu.runtime import bootstrap
used = bootstrap.enable_compilation_cache()
print(json.dumps({"used": used, "config": jax.config.jax_compilation_cache_dir,
                  "stats": bootstrap.compile_cache_stats()}))
"""


def test_cache_dir_from_outside_is_left_alone(tmp_path):
    outside = str(tmp_path / "cache")
    out = _run(_CACHE_SRC, env={"JAX_COMPILATION_CACHE_DIR": outside,
                                "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["used"] == got["config"] == outside
    assert got["stats"] == {"dir": outside, "entries": 0, "hits": 0,
                            "misses": 0, "backend_compiles": 0,
                            "backend_compile_s": 0.0, "retrieval_s": 0.0,
                            "trace_lower_s": 0.0}


def test_default_cache_dir_is_one_fixed_path_in_the_checkout():
    from kubeflow_tpu.runtime.bootstrap import DEFAULT_COMPILE_CACHE_DIR

    # This process, and another started from another directory: one path,
    # made from nothing that differs between processes.
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    out = _run(_CACHE_SRC, cwd=os.path.dirname(REPO),
               env={"JAX_COMPILATION_CACHE_DIR": None,
                    "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["used"] == got["config"] == DEFAULT_COMPILE_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_the_removed_cache_knob_is_gone():
    out = _run(["grep", "-rn", "KFTPU_JAX_CACHE_DIR", "kubeflow_tpu",
                "bench.py", "chip_smoke.py", "README.md"])
    assert out.stdout == ""


# -- what the worker reports ---------------------------------------------------

def test_kernel_calls_reads_the_lowered_text():
    from kubeflow_tpu.runtime.device_report import kernel_calls

    text = '''
    %1:2 = stablehlo.custom_call @tpu_custom_call(%arg0, %0) {backend_config = "{\\22custom_call_config\\22: {}}", kernel_name = "rmsnorm_fwd", operand_layouts = []}
    %2 = stablehlo.custom_call @tpu_custom_call(%1) {backend_config = "", kernel_name = "glu_fwd"}
    %3 = stablehlo.custom_call @tpu_custom_call(%2) {backend_config = "", kernel_name = "rmsnorm_fwd"}
    %4 = stablehlo.custom_call @Sharding(%3) {backend_config = ""}
    '''
    assert kernel_calls(text) == {"rmsnorm_fwd": 2, "glu_fwd": 1}
    assert kernel_calls("func.func @main() { return }") == {}


def test_trainer_writes_a_device_report(tmp_path):
    """The trainer's parent never touches the device; the run says what it
    ran on (Trainer.run writes this when it finishes — checked end to end
    by the text-training test and by chip_smoke.py; here the writer alone,
    on a stand-in trainer, so nothing compiles). On the CPU the kernels
    interpret, so the step lists none."""
    import types

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from kubeflow_tpu.runtime.device_report import read_device_report
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.train.trainer import Trainer

    assert read_device_report(str(tmp_path)) is None
    mesh = build_mesh({"fsdp": 2}, jax.devices()[:2])
    big = jax.device_put(jnp.zeros((8, 4)),
                         NamedSharding(mesh, PartitionSpec("fsdp")))
    Trainer._write_device_report(types.SimpleNamespace(
        task=types.SimpleNamespace(state={"params": {
            "embed": big, "norm": jnp.zeros((4,))}}),
        step_kernels={}, mesh=mesh, workdir=str(tmp_path),
        start_phase_seconds=lambda: {"build": 1.5, "resume": 0.0,
                                     "first_step": 2.5}))
    rep = read_device_report(str(tmp_path))
    assert rep["start"] == {"phases": {"build": 1.5, "resume": 0.0,
                                       "first_step": 2.5}}
    assert rep["platform"] == "cpu" and rep["device_kind"] == "cpu"
    assert rep["device_count"] == len(jax.devices())
    assert rep["programs"] == {"train_step": {}}
    assert rep["mesh"] == {"fsdp": 2}
    assert rep["largest_param"] == {"shape": [8, 4], "shard_shape": [4, 4],
                                    "devices": [0, 1]}
    assert rep["compile_cache"] is None


def test_config_error_fails_the_job_at_once(tmp_path):
    """Exit code 2 (bad entrypoint, a tpu worker on a CPU, a refused second
    worker) is deterministic: even before Running, and under the default
    restart policy, no restart — the worker's message is the job's."""
    from kubeflow_tpu.core.jobs import (
        JAXJob, JAXJobSpec, ReplicaSpec, TPUResourceSpec, Worker,
        WorkerPhase, WorkloadSpec,
    )
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )
    from kubeflow_tpu.runtime.bootstrap import EXIT_CONFIG_ERROR
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology

    cp = ControlPlane(ControlPlaneConfig(
        base_dir=str(tmp_path), launch_processes=False,
        metrics_sync_interval=None,
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="v5e",
                                              dims=(2, 2))])))
    cp.submit(JAXJob(metadata=ObjectMeta(name="job"), spec=JAXJobSpec(
        replica_specs={"worker": ReplicaSpec(
            replicas=2, template=WorkloadSpec(entrypoint="noop"),
            resources=TPUResourceSpec(tpu_chips=1))})))
    cp.step()
    w = cp.store.list(Worker)[1]
    w.status.phase = WorkerPhase.FAILED
    w.status.exit_code = EXIT_CONFIG_ERROR
    w.status.message = "platform tpu: worker x already holds this host's chips"
    cp.store.update_status(w)
    cp.step()
    job = cp.get_job("job")
    assert job.status.phase == "Failed" and job.status.restart_count == 0
    assert "already holds this host's chips" in \
        job.status.get_condition("Failed").message
    cp.stop()


# -- the native library is tied to the committed source ------------------------

def test_native_store_rebuilds_when_the_source_differs(monkeypatch):
    from kubeflow_tpu.pipelines import metadata

    if metadata.native_library() is None:
        pytest.skip("no toolchain on this host: the pure-Python backend")
    with open(metadata._STAMP_PATH) as f:
        good = f.read()
    assert good == metadata._source_digest()
    with open(metadata._STAMP_PATH, "w") as f:
        f.write("built from some other source")
    built = os.path.getmtime(metadata._LIB_PATH)
    assert metadata._try_build_native()
    with open(metadata._STAMP_PATH) as f:
        assert f.read() == good
    assert os.path.getmtime(metadata._LIB_PATH) >= built


def test_failed_native_build_is_an_error_not_a_fallback(tmp_path, monkeypatch):
    from kubeflow_tpu.pipelines import metadata

    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no toolchain on this host")
    src = tmp_path / "metadata_store"
    shutil.copytree(metadata._SRC_DIR, src)
    (src / "metadata_store.cc").write_text("this is not C++\n")
    lib = tmp_path / "_native" / "libmetadata_store.so"
    monkeypatch.setattr(metadata, "_SRC_DIR", str(src))
    monkeypatch.setattr(metadata, "_LIB_PATH", str(lib))
    monkeypatch.setattr(metadata, "_STAMP_PATH", str(lib) + ".src-sha256")
    monkeypatch.setattr(metadata, "_native_tried", False)
    monkeypatch.setattr(metadata, "_native_lib", None)
    with pytest.raises(metadata.NativeBuildError, match="metadata_store.cc"):
        metadata.MetadataStore(str(tmp_path / "m.db"))
    assert not lib.exists()


# -- the smoke script cannot pass off the chip ---------------------------------

def test_chip_smoke_held_to_the_cpu_exits_nonzero_at_once():
    import time

    t0 = time.monotonic()
    out = _run([sys.executable, "chip_smoke.py"],
               env={"JAX_PLATFORMS": "cpu"}, timeout=30)
    assert out.returncode != 0
    assert time.monotonic() - t0 < 10
    assert '"ok"' not in out.stdout and out.stdout.strip() == ""
    assert "holds the program off the TPU" in out.stderr


def test_chip_smoke_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
               env={"JAX_PLATFORMS": None, "PYTHONPATH": None}, timeout=60)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not beside this script" in out.stderr


def test_bench_without_a_chip_exits_nonzero_and_prints_no_result():
    out = _run([sys.executable, "bench.py"], env={
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": os.path.join(REPO, ".jax_cache")},
        timeout=120)
    assert out.returncode != 0
    assert "tokens/sec" not in out.stdout and out.stdout.strip() == ""
    assert "bench.py needs a TPU" in out.stderr
