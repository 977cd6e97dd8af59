"""Control plane assembly: store + allocator + controllers + worker runtime.

One object wires the whole platform the way a kubeflow deployment wires
apiserver + controllers + kubelet (SURVEY.md §2 layer map L3-L5). In-process
by design: a single-host TPU-slice control plane has no network hop to hide.

Usage:

    cp = ControlPlane(ControlPlaneConfig(base_dir=...))
    cp.start()
    job = cp.submit(jaxjob)
    cp.wait_for(job, "Succeeded", timeout=120)
    cp.stop()

Test mode: skip ``start()`` and call ``step()`` to pump controllers and the
runtime deterministically (or construct with ``config.launch_processes=False``
and drive Worker statuses by hand, envtest-style).
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
import threading
import time
from typing import Optional

from kubeflow_tpu.core.events import EventRecorder
from kubeflow_tpu.core.jobs import JAXJob
from kubeflow_tpu.core.object import ApiObject
from kubeflow_tpu.core.store import ObjectStore
from kubeflow_tpu.operator.controller import Controller
from kubeflow_tpu.operator.jaxjob_controller import JAXJobController
from kubeflow_tpu.operator.worker_runtime import WorkerRuntime
from kubeflow_tpu.runtime.allocator import GangAllocator
from kubeflow_tpu.runtime.topology import Cluster, detect_local_cluster


@dataclasses.dataclass
class ControlPlaneConfig:
    base_dir: Optional[str] = None          # default: a fresh temp dir
    platform: str = "cpu"                   # worker JAX platform ("cpu"|"tpu")
    # default: detect local. For "tpu" that is a probe child which exits
    # before any worker starts — this process never initialises a JAX
    # backend, because a chip belongs to one process at a time.
    cluster: Optional[Cluster] = None
    heartbeat_timeout: Optional[float] = 30.0
    rendezvous_timeout: float = 60.0
    launch_processes: bool = True           # False = envtest mode (no runtime)
    runtime_poll_interval: float = 0.1
    metrics_sync_interval: Optional[float] = 1.0  # None: event-driven only


class ControlPlane:
    def __init__(self, config: Optional[ControlPlaneConfig] = None):
        self.config = config or ControlPlaneConfig()
        if self.config.base_dir is None:
            self.config.base_dir = tempfile.mkdtemp(prefix="kftpu-")
        os.makedirs(self.config.base_dir, exist_ok=True)
        self.store = ObjectStore()
        self.recorder = EventRecorder()
        self.cluster = self.config.cluster or detect_local_cluster(
            platform=self.config.platform)
        self.allocator = GangAllocator(self.cluster)
        self.jaxjob_reconciler = JAXJobController(
            self.store, self.allocator,
            base_dir=self.config.base_dir, recorder=self.recorder,
            metrics_sync_interval=self.config.metrics_sync_interval)
        from kubeflow_tpu.serve.isvc_controller import ISVCController

        self.isvc_reconciler = ISVCController(self.store, recorder=self.recorder)
        from kubeflow_tpu.tune.experiment_controller import ExperimentController
        from kubeflow_tpu.tune.trial_controller import TrialController

        self.experiment_reconciler = ExperimentController(
            self.store, recorder=self.recorder)
        # Durable observation history (katib db-manager analog): trials
        # write every collected point into the native metadata store, so
        # cross-experiment queries survive object GC.
        from kubeflow_tpu.pipelines.metadata import MetadataStore
        from kubeflow_tpu.tune.observations import ObservationLog

        self.observation_store = MetadataStore(
            os.path.join(self.config.base_dir, "observations.db"))
        self.observations = ObservationLog(self.observation_store)
        # gRPC front (db-manager protocol surface): lets separate-process
        # workers write observations directly; workers find it via the
        # KFTPU_OBS_TARGET env the runtime injects.
        self.observation_service = None
        try:
            from kubeflow_tpu.tune.observation_service import (
                ObservationGRPCServer,
            )

            self.observation_service = ObservationGRPCServer(
                self.observations)
            self.observation_service.start()
        except ImportError:
            pass   # grpcio not installed: in-process reporting only
        self.trial_reconciler = TrialController(
            self.store, base_dir=self.config.base_dir, recorder=self.recorder,
            observations=self.observations)
        from kubeflow_tpu.pipelines.controller import (
            PipelineRunController, ScheduledRunController,
        )

        self.pipelinerun_reconciler = PipelineRunController(
            self.store, base_dir=os.path.join(self.config.base_dir, "pipelines"),
            recorder=self.recorder)
        self.schedule_reconciler = ScheduledRunController(
            self.store, recorder=self.recorder)
        from kubeflow_tpu.workspace.notebook_controller import NotebookController
        from kubeflow_tpu.workspace.profile_controller import ProfileController
        from kubeflow_tpu.workspace.tensorboard_controller import (
            TensorboardController,
        )

        self.notebook_reconciler = NotebookController(
            self.store, base_dir=self.config.base_dir,
            recorder=self.recorder,
            launch_processes=self.config.launch_processes)
        self.profile_reconciler = ProfileController(
            self.store, recorder=self.recorder)
        self.tensorboard_reconciler = TensorboardController(
            self.store, recorder=self.recorder,
            launch_processes=self.config.launch_processes)
        self.controllers: list[Controller] = [
            Controller(self.store, self.jaxjob_reconciler, name="jaxjob"),
            Controller(self.store, self.isvc_reconciler, name="isvc"),
            Controller(self.store, self.experiment_reconciler, name="experiment"),
            Controller(self.store, self.trial_reconciler, name="trial"),
            Controller(self.store, self.pipelinerun_reconciler, name="pipelinerun"),
            Controller(self.store, self.schedule_reconciler, name="schedule"),
            Controller(self.store, self.notebook_reconciler, name="notebook"),
            Controller(self.store, self.profile_reconciler, name="profile"),
            Controller(self.store, self.tensorboard_reconciler, name="tensorboard"),
        ]
        self.runtime: Optional[WorkerRuntime] = None
        if self.config.launch_processes:
            self.runtime = WorkerRuntime(
                self.store,
                base_dir=self.config.base_dir,
                platform=self.config.platform,
                heartbeat_timeout=self.config.heartbeat_timeout,
                rendezvous_timeout=self.config.rendezvous_timeout,
                recorder=self.recorder)
            if self.observation_service is not None:
                # Workers report observations straight to the store's gRPC
                # front (the db-manager path), not through the controller.
                # contract: read by the out-of-process observation reporter (tests/obs_worker.py), outside the lint scan
                self.runtime.service_env["KFTPU_OBS_TARGET"] = \
                    self.observation_service.target
            # artifact:// resolution in worker processes (model servers
            # loading a published model, trainers staging a published
            # dataset): point every worker at the platform artifact store.
            from kubeflow_tpu.pipelines.artifacts import ROOT_ENV

            self.runtime.service_env[ROOT_ENV] = \
                self.pipelinerun_reconciler.artifacts.root
        self._stop = threading.Event()
        self._runtime_thread: Optional[threading.Thread] = None

    # -- controller registration (serve/tune/pipelines plug in here) -----------

    def add_controller(self, reconciler, *, name: Optional[str] = None) -> Controller:
        c = Controller(self.store, reconciler, name=name)
        self.controllers.append(c)
        if self._runtime_thread is not None:   # already started: run it now
            c.start()
        return c

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        for c in self.controllers:
            c.start()
        self._runtime_thread = threading.Thread(
            target=self._runtime_loop, daemon=True, name="worker-runtime")
        self._runtime_thread.start()

    def _runtime_loop(self) -> None:
        while not self._stop.is_set():
            if self.runtime is not None:
                self.runtime.step()
            time.sleep(self.config.runtime_poll_interval)

    def stop(self) -> None:
        self._stop.set()
        for c in self.controllers:
            c.stop()
        if self._runtime_thread is not None:
            self._runtime_thread.join(timeout=5.0)
            self._runtime_thread = None
        if self.runtime is not None:
            self.runtime.shutdown()
        self.isvc_reconciler.shutdown()
        self.pipelinerun_reconciler.shutdown()
        self.notebook_reconciler.shutdown()
        self.tensorboard_reconciler.shutdown()
        if self.observation_service is not None:
            self.observation_service.stop()
        self.observation_store.close()

    def step(self) -> int:
        """Deterministic single-threaded pump (test mode)."""
        n = 0
        for c in self.controllers:
            n += c.step(advance_past_delays=True)
        if self.runtime is not None:
            self.runtime.step()
            for c in self.controllers:   # runtime status writes → more events
                n += c.step(advance_past_delays=True)
        return n

    # -- user surface (the SDK analog) ----------------------------------------

    @property
    def artifact_store(self):
        """The platform artifact store (pipelines outputs, published models,
        artifact:// resolution) — one store, every subsystem."""
        return self.pipelinerun_reconciler.artifacts

    def submit(self, obj: ApiObject) -> ApiObject:
        return self.store.create(obj)

    def apply(self, obj: ApiObject) -> ApiObject:
        return self.store.apply(obj)

    def get_job(self, name: str, namespace: str = "default") -> Optional[JAXJob]:
        return self.store.try_get(JAXJob, name, namespace)

    def wait_for(self, obj: ApiObject, condition: str, *,
                 timeout: float = 60.0, poll: float = 0.1,
                 stepped: bool = False) -> ApiObject:
        """Wait until ``obj`` has ``condition`` true. ``stepped``: pump the
        control plane from this thread (when start() wasn't called)."""
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            if stepped:
                self.step()
            cur = self.store.try_get(type(obj), obj.metadata.name,
                                     obj.metadata.namespace)
            if cur is None:
                # Deleted mid-wait (e.g. TTL reaped a finished job right
                # after the condition landed): the last observation decides.
                if last is not None and last.status.has_condition(condition):
                    return last
                raise RuntimeError(f"{obj.key} disappeared while waiting")
            status = getattr(cur, "status", None)
            if status is not None and status.has_condition(condition):
                return cur
            last = cur
            time.sleep(poll)
        seen = ([c.type for c in last.status.conditions if c.status]
                if last is not None else "never observed")
        raise TimeoutError(
            f"{obj.key}: condition {condition} not reached in {timeout}s; "
            f"conditions={seen}")
