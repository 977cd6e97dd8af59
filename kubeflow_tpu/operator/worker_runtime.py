"""Worker runtime: materializes Worker objects as local processes.

The kubelet analog (SURVEY.md §3.1 '‖proc‖ kubelet starts container'): watches
Worker objects, launches ``worker_main`` subprocesses with the KFTPU_*
rendezvous env via LocalProcessManager, reports phase/pid/exit-code back to
Worker status, and enforces the heartbeat lease — the platform's liveness
failure detector (a hung worker is killed and marked failed with no exit code,
which the JAXJob controller treats as retryable infrastructure failure).

Separation of concerns mirrors the reference: the controller never touches
processes, the runtime never makes policy — it observes and reports. Swap
LocalProcessManager for an SSH/TPU-VM-agent backend and nothing above changes.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

from kubeflow_tpu.core.events import EventRecorder, default_recorder
from kubeflow_tpu.core.jobs import Worker, WorkerPhase
from kubeflow_tpu.core.object import utcnow
from kubeflow_tpu.core.store import NotFoundError, ObjectStore, EventType, Watch
from kubeflow_tpu.runtime.bootstrap import EXIT_CONFIG_ERROR, WorkerEnv
from kubeflow_tpu.runtime.procman import LocalProcessManager

logger = logging.getLogger("kubeflow_tpu.operator.runtime")


class WorkerRuntime:
    """Drives Worker objects to processes and processes back to status."""

    def __init__(self, store: ObjectStore, procman: Optional[LocalProcessManager] = None, *,
                 base_dir: str, platform: str = "cpu",
                 heartbeat_timeout: Optional[float] = 30.0,
                 heartbeat_startup_grace: float = 15.0,
                 rendezvous_timeout: float = 60.0,
                 recorder: Optional[EventRecorder] = None):
        self.store = store
        self.base_dir = base_dir
        self.platform = platform
        self.heartbeat_timeout = heartbeat_timeout
        # Extra allowance before the FIRST heartbeat: interpreter startup on a
        # busy host. A worker wedged before its first beat must still be
        # caught (heartbeat_age()=None forever), so absence of the file falls
        # back to process age against timeout+grace.
        self.heartbeat_startup_grace = heartbeat_startup_grace
        self.rendezvous_timeout = rendezvous_timeout
        self.recorder = recorder or default_recorder
        # Platform services advertised to every worker (e.g. the
        # observation-log gRPC target) — merged into launch env.
        self.service_env: dict[str, str] = {}
        self.procman = procman or LocalProcessManager(
            log_dir=os.path.join(base_dir, "logs"))
        self._watch: Watch = store.watch(kinds=[Worker.KIND])
        # Worker-object uid per launched name: a recreated worker (same name,
        # new uid, e.g. next gang attempt) must kill the old process first.
        self._launched_uid: dict[str, str] = {}

    # -- stepping --------------------------------------------------------------

    def step(self) -> int:
        """Process watch events + poll processes once. Returns event count."""
        n = 0
        if self._watch.ended:
            self._watch = self.store.watch(kinds=[Worker.KIND])
        for ev in self._watch.drain():
            self._handle_event(ev.type, ev.object)
            n += 1
        self._poll_all()
        return n

    def _handle_event(self, etype: EventType, w: Worker) -> None:
        name = self._proc_name(w)
        if etype == EventType.DELETED:
            self._teardown(name)
            return
        if w.status.phase == WorkerPhase.PENDING and self._owns_launch(w, name):
            self._launch(w, name)

    def _owns_launch(self, w: Worker, name: str) -> bool:
        uid = w.metadata.uid or ""
        if name in self._launched_uid:
            if self._launched_uid[name] == uid:
                return False        # already launched this incarnation
            self._teardown(name)    # stale incarnation still around
        return True

    # -- launch ----------------------------------------------------------------

    def _proc_name(self, w: Worker) -> str:
        return f"{w.metadata.namespace}.{w.metadata.name}"

    def _chips_holder(self) -> Optional[str]:
        """On the tpu platform, the live worker that already holds this
        host's chips. Nothing confines a worker to the chips the allocator
        gave its job: every worker process opens ALL local chips, and a
        chip belongs to one process at a time — so a second concurrent
        worker on the host could only fail or hang inside libtpu. The
        runtime refuses it up front, by name."""
        if self.platform != "tpu":
            return None
        alive = self.procman.alive()
        return alive[0] if alive else None

    def _launch(self, w: Worker, name: str) -> None:
        holder = self._chips_holder()
        if holder is not None:
            w.status.phase = WorkerPhase.FAILED
            w.status.exit_code = EXIT_CONFIG_ERROR
            w.status.message = (
                f"platform tpu: worker {holder} already holds this host's "
                "chips. Workers are not confined to their allocated chips, "
                "so one host runs one worker process at a time: use one "
                "worker with all the chips (replicas: 1, tpu_chips: N) "
                "instead of several workers per host")
            w.status.finish_time = utcnow()
            self._update_status(w)
            self.recorder.warning(w, "ChipsHeld", w.status.message)
            return
        tmpl = w.spec.template
        workdir = tmpl.working_dir or os.path.join(
            self.base_dir, w.metadata.namespace, w.metadata.name)
        hb_file = None
        if self.heartbeat_timeout is not None:
            hb_file = os.path.join(self.base_dir, "hb",
                                   f"{name}.{w.metadata.uid}")
        wenv = WorkerEnv(
            coordinator_address=w.spec.coordinator_address or "127.0.0.1:0",
            num_processes=w.spec.num_workers,
            process_id=w.spec.replica_index,
            job=w.spec.job,
            replica_index=w.spec.replica_index,
            entrypoint=tmpl.entrypoint,
            config=tmpl.config,
            parallelism=w.spec.parallelism,
            platform=self.platform,
            # On the CPU emulation platform each worker fabricates its chip
            # count as virtual XLA devices; on the tpu platform the worker
            # opens every chip of the host (see _chips_holder).
            virtual_devices=max(1, w.spec.resources.tpu_chips),
            heartbeat_file=hb_file,
            workdir=workdir,
            rendezvous_timeout_seconds=self.rendezvous_timeout,
        )
        # Workers must import this framework regardless of their workdir:
        # prepend the package root (absolute) to PYTHONPATH.
        import kubeflow_tpu
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(kubeflow_tpu.__file__)))
        extra = {**self.service_env, **(tmpl.env or {})}
        extra["PYTHONPATH"] = os.pathsep.join(
            p for p in (pkg_root, extra.get("PYTHONPATH"),
                        os.environ.get("PYTHONPATH")) if p)
        try:
            h = self.procman.launch(name, wenv, extra_env=extra)
        except Exception as exc:
            logger.exception("launch %s failed", name)
            w.status.phase = WorkerPhase.FAILED
            w.status.message = f"launch failed: {exc}"
            self._update_status(w)
            return
        self._launched_uid[name] = w.metadata.uid or ""
        w.status.phase = WorkerPhase.RUNNING
        w.status.pid = h.pid
        w.status.start_time = utcnow()
        self._update_status(w)
        self.recorder.normal(w, "Started", f"pid {h.pid}")

    # -- observe ---------------------------------------------------------------

    def _poll_all(self) -> None:
        for name in list(self._launched_uid):
            h = self.procman.get(name)
            if h is None:
                self._launched_uid.pop(name, None)
                continue
            rc = h.poll()
            if rc is None:
                if self.heartbeat_timeout is not None:
                    age = h.heartbeat_age()
                    if age is None:  # never beat: measure from process start
                        age = (time.time() - h.started_at
                               - self.heartbeat_startup_grace)
                    if age > self.heartbeat_timeout:
                        logger.warning("%s heartbeat stale (%.1fs); killing",
                                       name, age)
                        self.procman.kill(name, grace_seconds=2.0)
                        self._report_exit(name, None, "heartbeat stale; killed")
                continue
            self._report_exit(name, rc, "")

    def _report_exit(self, name: str, rc: Optional[int], message: str) -> None:
        if rc is not None and rc < 0:
            # Popen reports signal death as -N; normalize to the shell's
            # 128+N so the ExitCode retry contract sees it (SIGKILL -> 137).
            rc = 128 - rc
        uid = self._launched_uid.pop(name, None)
        try:
            self.procman.reap(name)
        except RuntimeError:
            pass
        namespace, wname = name.split(".", 1)
        w = self.store.try_get(Worker, wname, namespace)
        if w is None or (uid is not None and (w.metadata.uid or "") != uid):
            return  # object gone or a newer incarnation; nothing to report to
        if rc == 0:
            w.status.phase = WorkerPhase.SUCCEEDED
        else:
            w.status.phase = WorkerPhase.FAILED
        w.status.exit_code = rc
        w.status.message = message
        w.status.finish_time = utcnow()
        self._update_status(w)

    def _update_status(self, w: Worker) -> None:
        try:
            self.store.update_status(w)
        except NotFoundError:
            pass

    # -- teardown --------------------------------------------------------------

    def _teardown(self, name: str) -> None:
        self._launched_uid.pop(name, None)
        if self.procman.get(name) is not None:
            self.procman.kill(name, grace_seconds=2.0)
            try:
                self.procman.reap(name)
            except RuntimeError:
                pass

    def shutdown(self) -> None:
        self._watch.close()
        self.procman.shutdown()
