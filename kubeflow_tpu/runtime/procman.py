"""Worker process manager: spawn, observe, and kill worker processes.

The kubelet analog. The operator creates Worker objects in the store; this
manager materializes them as subprocesses running
``python -m kubeflow_tpu.runtime.worker_main`` with the KFTPU_* rendezvous
env, and reports their lifecycle (running / exit code / heartbeat staleness).

Isolation seam (SURVEY.md §7 hard-part 6): the interface is process-shaped
(launch/poll/signal) so a real multi-host backend — SSH, GKE pods, TPU-VM
agents — can replace LocalProcessManager without touching the operator.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import time
from typing import Optional

from kubeflow_tpu.runtime.bootstrap import WorkerEnv


@dataclasses.dataclass
class ProcHandle:
    name: str                    # worker object name
    popen: subprocess.Popen
    heartbeat_file: Optional[str]
    log_path: Optional[str]
    started_at: float = dataclasses.field(default_factory=time.time)

    @property
    def pid(self) -> int:
        return self.popen.pid

    def poll(self) -> Optional[int]:
        return self.popen.poll()

    def heartbeat_age(self) -> Optional[float]:
        if not self.heartbeat_file or not os.path.exists(self.heartbeat_file):
            return None
        return time.time() - os.path.getmtime(self.heartbeat_file)


PLATFORMS = ("cpu", "tpu")


def platform_env(platform: str) -> dict[str, str]:
    """This process's environment with the JAX platform of a child pinned
    to ``platform``, in both directions: a ``cpu`` child never reaches
    for the chip, and a ``tpu`` child does not inherit a parent's
    ``JAX_PLATFORMS=cpu`` and quietly run on the host (it fails at
    backend start instead when there is no chip)."""
    if platform not in PLATFORMS:
        raise ValueError(f"unknown platform {platform!r}; one of {PLATFORMS}")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    return env


class LocalProcessManager:
    """Spawns workers as local subprocesses."""

    def __init__(self, log_dir: Optional[str] = None):
        self._procs: dict[str, ProcHandle] = {}
        self._log_dir = log_dir

    def launch(self, name: str, wenv: WorkerEnv,
               extra_env: Optional[dict[str, str]] = None) -> ProcHandle:
        if name in self._procs and self._procs[name].poll() is None:
            raise RuntimeError(f"worker {name} already running")
        env = platform_env(wenv.platform)
        env.update(wenv.to_env())
        if extra_env:
            env.update(extra_env)
        log_path = None
        stdout = stderr = subprocess.DEVNULL
        if self._log_dir:
            os.makedirs(self._log_dir, exist_ok=True)
            log_path = os.path.join(self._log_dir, f"{name}.log")
            logf = open(log_path, "ab")
            stdout = stderr = logf
        popen = subprocess.Popen(
            [sys.executable, "-m", "kubeflow_tpu.runtime.worker_main"],
            env=env, stdout=stdout, stderr=stderr,
            start_new_session=True,  # isolate signals from the control plane
        )
        h = ProcHandle(name=name, popen=popen,
                       heartbeat_file=wenv.heartbeat_file, log_path=log_path)
        self._procs[name] = h
        return h

    def get(self, name: str) -> Optional[ProcHandle]:
        return self._procs.get(name)

    def poll(self, name: str) -> Optional[int]:
        h = self._procs.get(name)
        return None if h is None else h.poll()

    def signal(self, name: str, sig: int = signal.SIGTERM) -> bool:
        h = self._procs.get(name)
        if h is None or h.poll() is not None:
            return False
        try:
            os.killpg(os.getpgid(h.pid), sig)
            return True
        except (ProcessLookupError, PermissionError):
            return False

    def kill(self, name: str, grace_seconds: float = 5.0) -> Optional[int]:
        """SIGTERM, wait up to grace, then SIGKILL. Returns the exit code."""
        h = self._procs.get(name)
        if h is None:
            return None
        if h.poll() is None:
            self.signal(name, signal.SIGTERM)
            try:
                h.popen.wait(timeout=grace_seconds)
            except subprocess.TimeoutExpired:
                self.signal(name, signal.SIGKILL)
                h.popen.wait()  # blocking-ok: final reap after SIGKILL — the kernel guarantees exit
        return h.poll()

    def reap(self, name: str) -> None:
        h = self._procs.pop(name, None)
        if h is not None and h.poll() is None:
            self._procs[name] = h
            raise RuntimeError(f"worker {name} still running; kill first")

    def alive(self) -> list[str]:
        return [n for n, h in self._procs.items() if h.poll() is None]

    def shutdown(self) -> None:
        for n in list(self._procs):
            self.kill(n, grace_seconds=2.0)
