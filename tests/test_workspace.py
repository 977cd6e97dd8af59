"""Workspace subsystem: notebook sessions (real kernel processes + culling),
PodDefault injection, profile quota enforcement — the notebook-controller /
admission-webhook / profile-controller behaviors of SURVEY.md §2.1, §3.5."""

import os
import time

import pytest

from kubeflow_tpu.core.jobs import (
    JAXJob, JAXJobSpec, ReplicaSpec, TPUResourceSpec, WorkloadSpec,
)
from kubeflow_tpu.core.object import ObjectMeta
from kubeflow_tpu.core.workspace_specs import (
    Notebook, NotebookSpec, PodDefault, PodDefaultSpec, Profile, ProfileSpec,
    QuotaSpec, apply_pod_defaults,
)
from kubeflow_tpu.operator.control_plane import ControlPlane, ControlPlaneConfig
from kubeflow_tpu.runtime.topology import Cluster, SliceTopology
from kubeflow_tpu.workspace.notebook_controller import WAKE_ANNOTATION
from kubeflow_tpu.workspace.profile_controller import (
    add_contributor, can_access, remove_contributor,
)
from kubeflow_tpu.workspace.session_main import exec_code


def make_cp(tmp_path, launch=False) -> ControlPlane:
    return ControlPlane(ControlPlaneConfig(
        base_dir=str(tmp_path),
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="v5e",
                                              dims=(2, 2))]),
        launch_processes=launch,
        metrics_sync_interval=None,
    ))


class TestNotebookSession:
    """Real kernel process: spawn, exec, cull, wake."""

    @pytest.fixture()
    def cp(self, tmp_path):
        plane = make_cp(tmp_path, launch=True)
        plane.start()
        yield plane
        plane.stop()

    def wait_phase(self, cp, name, phase, timeout=30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            nb = cp.store.try_get(Notebook, name)
            if nb is not None and nb.status.phase == phase:
                return nb
            time.sleep(0.1)
        raise TimeoutError(f"{name} never reached {phase}: "
                           f"{nb.status.phase if nb else None}")

    @staticmethod
    def _wait_session(sock, timeout=20):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                if exec_code(sock, "0")["ok"]:
                    return
            except OSError:
                time.sleep(0.1)
        raise TimeoutError(f"session at {sock} never answered")

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_spawn_exec_cull_wake(self, cp):
        cp.submit(PodDefault(
            metadata=ObjectMeta(name="inject"),
            spec=PodDefaultSpec(selector={"team": "ml"},
                                env={"INJECTED_FLAG": "yes"})))
        cp.submit(Notebook(
            metadata=ObjectMeta(name="nb", labels={"team": "ml"}),
            spec=NotebookSpec(env={"OWN_VAR": "1",
                                   "KFTPU_NB_PREIMPORT": "0"},
                              # generous: under full-suite load the spawn
                              # itself can take seconds; the cull-wait below
                              # tolerates up to 60s
                              idle_cull_seconds=10.0)))
        nb = self.wait_phase(cp, "nb", "Running")
        assert nb.status.url.startswith("unix://")
        sock = nb.status.url[len("unix://"):]
        self._wait_session(sock)

        # The session is a live REPL...
        res = exec_code(sock, "x = 20 + 22\nprint(x)")
        assert res["ok"] and res["output"].strip() == "42"
        res = exec_code(sock, "x * 2")
        assert res["ok"] and res["output"].strip() == "84"
        # ...with PodDefault env injected (admission-webhook analog)
        res = exec_code(sock, "import os; print(os.environ['INJECTED_FLAG'], os.environ['OWN_VAR'])")
        assert res["output"].strip() == "yes 1"
        # errors surface without killing the session
        res = exec_code(sock, "1/0")
        assert not res["ok"] and "ZeroDivisionError" in res["error"]
        assert exec_code(sock, "print('alive')")["ok"]

        # Idle culling: stop talking to it for > idle_cull_seconds.
        nb = self.wait_phase(cp, "nb", "Culled", timeout=60)
        assert nb.status.pid is None

        # Wake: the "open notebook" action.
        nb.metadata.annotations[WAKE_ANNOTATION] = "true"
        cp.store.update(nb, check_version=False)
        nb = self.wait_phase(cp, "nb", "Running")
        sock = nb.status.url[len("unix://"):]
        self._wait_session(sock)
        assert exec_code(sock, "print('back')")["ok"]


def test_socket_path_longer_than_an_af_unix_address(tmp_path):
    """A notebook directory deeper than the 107 bytes an AF_UNIX address
    holds (pytest-xdist's ``popen-gwN`` level was enough): the session
    binds and the client connects through the directory's descriptor."""
    import threading

    from kubeflow_tpu.workspace import session_main as sm

    deep = tmp_path / ("d" * 120)
    deep.mkdir()
    sock = str(deep / "kernel.sock")
    assert len(sock) > 107
    with sm.unix_address(sock) as address:
        srv = sm._Server(address, sm._Handler)
    srv.activity_file = str(deep / "activity")
    srv.user_globals = {}
    threading.Thread(target=srv.serve_forever,
                     kwargs={"poll_interval": 0.05}, daemon=True).start()
    try:
        assert os.path.exists(sock)
        res = exec_code(sock, "20 + 22")
        assert res["ok"] and res["output"].strip() == "42"
    finally:
        srv.shutdown()
        srv.server_close()


class TestPodDefaults:
    def test_merge_semantics(self):
        pds = [
            PodDefault(metadata=ObjectMeta(name="a"),
                       spec=PodDefaultSpec(selector={"t": "x"},
                                           env={"A": "1", "B": "pd"})),
            PodDefault(metadata=ObjectMeta(name="b"),
                       spec=PodDefaultSpec(selector={"t": "y"},
                                           env={"C": "never"})),
        ]
        merged = apply_pod_defaults({"t": "x"}, {"B": "own"}, pds)
        assert merged == {"A": "1", "B": "own"}  # explicit env wins


def job_of(name, chips=1):
    return JAXJob(
        metadata=ObjectMeta(name=name, namespace="team-a"),
        spec=JAXJobSpec(replica_specs={"worker": ReplicaSpec(
            replicas=1, template=WorkloadSpec(entrypoint="noop"),
            resources=TPUResourceSpec(tpu_chips=chips))}))


class TestProfileQuota:
    @pytest.fixture()
    def cp(self, tmp_path):
        return make_cp(tmp_path, launch=False)

    def test_quota_suspends_and_resumes(self, cp):
        cp.submit(Profile(
            metadata=ObjectMeta(name="team-a"),
            spec=ProfileSpec(owner="alice", quota=QuotaSpec(max_jobs=1))))
        cp.submit(job_of("j1"))
        cp.submit(job_of("j2"))
        cp.step()
        j1 = cp.store.get(JAXJob, "j1", "team-a")
        j2 = cp.store.get(JAXJob, "j2", "team-a")
        assert not j1.spec.run_policy.suspend
        assert j2.spec.run_policy.suspend  # newest over quota
        # j1 finishes → j2 resumes
        j1.status.set_condition("Succeeded", True, reason="Done")
        cp.store.update_status(j1)
        cp.step()
        j2 = cp.store.get(JAXJob, "j2", "team-a")
        assert not j2.spec.run_policy.suspend

    def test_chip_quota(self, cp):
        cp.submit(Profile(
            metadata=ObjectMeta(name="team-a"),
            spec=ProfileSpec(owner="alice",
                             quota=QuotaSpec(max_tpu_chips=3))))
        cp.submit(job_of("big", chips=2))
        cp.submit(job_of("small", chips=2))   # 4 > 3 → suspended
        cp.step()
        assert not cp.store.get(JAXJob, "big", "team-a").spec.run_policy.suspend
        assert cp.store.get(JAXJob, "small", "team-a").spec.run_policy.suspend
        prof = cp.store.get(Profile, "team-a")
        assert prof.status.chips_in_use == 2

    def test_user_suspend_not_overridden(self, cp):
        cp.submit(Profile(
            metadata=ObjectMeta(name="team-a"),
            spec=ProfileSpec(owner="alice", quota=QuotaSpec(max_jobs=5))))
        j = job_of("j1")
        j.spec.run_policy.suspend = True   # user's own suspend
        cp.submit(j)
        cp.step()
        assert cp.store.get(JAXJob, "j1", "team-a").spec.run_policy.suspend

    def test_contributors(self, cp):
        cp.submit(Profile(metadata=ObjectMeta(name="team-a"),
                          spec=ProfileSpec(owner="alice")))
        add_contributor(cp.store, "team-a", "bob")
        p = cp.store.get(Profile, "team-a")
        assert can_access(p, "alice") and can_access(p, "bob")
        assert not can_access(p, "eve")
        remove_contributor(cp.store, "team-a", "bob")
        assert not can_access(cp.store.get(Profile, "team-a"), "bob")


class TestKernelProfiles:
    """The example-notebook-servers image family (SURVEY.md §2.1#11): each
    kernel profile spawns with its own preimported stack."""

    @pytest.fixture()
    def cp(self, tmp_path):
        plane = make_cp(tmp_path, launch=True)
        plane.start()
        yield plane
        plane.stop()

    def _spawn(self, cp, name, image):
        cp.submit(Notebook(metadata=ObjectMeta(name=name),
                           spec=NotebookSpec(image=image,
                                             idle_cull_seconds=None)))
        deadline = time.time() + 120
        while time.time() < deadline:
            nb = cp.store.try_get(Notebook, name)
            if nb is not None and nb.status.phase in ("Running", "Failed"):
                break
            time.sleep(0.1)
        return cp.store.get(Notebook, name)

    def test_base_has_no_preloads_jax_notebook_has_jax(self, cp):
        nb = self._spawn(cp, "nb-base", "base")
        assert nb.status.phase == "Running"
        sock = nb.status.url.removeprefix("unix://")
        TestNotebookSession._wait_session(sock)
        from kubeflow_tpu.workspace.session_main import exec_code
        res = exec_code(sock, "print('jax' in dir())")
        assert res["ok"] and res["output"].strip() == "False"

        nb2 = self._spawn(cp, "nb-jax", "jax-notebook")
        sock2 = nb2.status.url.removeprefix("unix://")
        TestNotebookSession._wait_session(sock2, timeout=120)
        res = exec_code(sock2, "print(jax.__name__, numpy.__name__)",
                        timeout=90)
        assert res["ok"] and res["output"].strip() == "jax numpy"

    def test_full_profile_preloads_stack(self, cp):
        nb = self._spawn(cp, "nb-full", "jax-full")
        assert nb.status.phase == "Running"
        sock = nb.status.url.removeprefix("unix://")
        TestNotebookSession._wait_session(sock, timeout=120)
        from kubeflow_tpu.workspace.session_main import exec_code
        res = exec_code(sock, "print(flax.__name__, optax.__name__)",
                        timeout=90)
        assert res["ok"] and res["output"].strip() == "flax optax"

    def test_unknown_image_fails_with_event(self, cp):
        nb = self._spawn(cp, "nb-bogus", "pytorch-notebook")
        assert nb.status.phase == "Failed"
        assert nb.status.has_condition("Running", status=False)
        evs = cp.recorder.for_object(nb)
        assert any(e.reason == "UnknownImage" for e in evs)
