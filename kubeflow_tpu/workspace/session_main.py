"""Notebook kernel process: ``python -m kubeflow_tpu.workspace.session_main``.

The jupyter-server analog for the Notebook controller (SURVEY.md §2.1#1):
a long-lived JAX-ready Python session listening on a unix socket, speaking
JSON-lines: ``{"code": "..."} → {"ok": bool, "output": str, "error": str}``.
Every request touches the activity file — the controller's idle culler reads
its mtime exactly like the reference culler polls ``/api/kernels``.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import socket
import socketserver
import sys
import traceback


def touch(path: str) -> None:
    with open(path, "a"):
        os.utime(path, None)


@contextlib.contextmanager
def unix_address(path: str):
    """An address for the unix socket at ``path`` that ``bind`` and
    ``connect`` take whatever the path's length: an AF_UNIX address holds
    107 bytes, and a notebook's directory under a deep ``base_dir`` is
    longer (the session then died at ``bind`` and the controller restarted
    it for ever). A longer path is reached through its directory's open
    descriptor."""
    if len(os.fsencode(path)) <= 107:
        yield path
        return
    fd = os.open(os.path.dirname(path), os.O_RDONLY | os.O_DIRECTORY)
    try:
        yield f"/proc/self/fd/{fd}/{os.path.basename(path)}"
    finally:
        os.close(fd)


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        for line in self.rfile:
            line = line.strip()
            if not line:
                continue
            try:
                req = json.loads(line)
            except ValueError:
                self._reply({"ok": False, "error": "bad json"})
                continue
            touch(self.server.activity_file)
            if req.get("op") == "ping":
                self._reply({"ok": True, "output": "pong"})
                continue
            self._reply(self._exec(req.get("code", "")))

    def _exec(self, code: str) -> dict:
        buf = io.StringIO()
        try:
            with contextlib.redirect_stdout(buf), \
                    contextlib.redirect_stderr(buf):
                try:
                    # Expression? Show its repr, REPL-style.
                    result = eval(code, self.server.user_globals)
                    if result is not None:
                        print(repr(result))
                except SyntaxError:
                    exec(code, self.server.user_globals)
            return {"ok": True, "output": buf.getvalue()}
        except Exception:
            return {"ok": False, "output": buf.getvalue(),
                    "error": traceback.format_exc(limit=10)}

    def _reply(self, obj: dict) -> None:
        self.wfile.write((json.dumps(obj) + "\n").encode())
        self.wfile.flush()


class _Server(socketserver.ThreadingUnixStreamServer):
    daemon_threads = True
    allow_reuse_address = True


def main() -> int:
    sock_path = os.environ["KFTPU_NB_SOCKET"]
    activity = os.environ["KFTPU_NB_ACTIVITY"]
    workdir = os.environ.get("KFTPU_NB_WORKDIR")
    if workdir:
        os.makedirs(workdir, exist_ok=True)
        os.chdir(workdir)
    if os.path.exists(sock_path):
        os.unlink(sock_path)
    os.makedirs(os.path.dirname(sock_path), exist_ok=True)
    touch(activity)
    with unix_address(sock_path) as address:
        srv = _Server(address, _Handler)
    srv.activity_file = activity
    srv.user_globals = {"__name__": "__kftpu_notebook__"}
    # Kernel-profile preimports (the image family's preinstalled stack —
    # core/workspace_specs.py::KERNEL_PROFILES): the controller passes the
    # profile's module list; the legacy KFTPU_NB_PREIMPORT=1 flag keeps
    # meaning "jax" for sessions launched without a controller.
    pre = os.environ.get("KFTPU_NB_PREIMPORTS")
    if pre is None:
        # contract: legacy user-facing flag for controllerless sessions
        pre = "jax" if os.environ.get("KFTPU_NB_PREIMPORT", "1") == "1" else ""
    import importlib

    for mod in filter(None, pre.split(",")):
        try:
            srv.user_globals[mod] = importlib.import_module(mod)
        except ImportError:
            pass
    if os.environ.get("KFTPU_NB_PROFILER") == "1":
        # jax-full profile: expose the profiler server so tensorboard can
        # attach to live kernels (port 0 = ephemeral is not supported by
        # start_server; pick one from the OS first).
        try:
            import socket as _socket

            import jax as _jax

            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            _jax.profiler.start_server(port)
            srv.user_globals["_kftpu_profiler_port"] = port
        except Exception as e:  # noqa: BLE001 — profiler is best-effort
            # Best-effort, but never silent: the bind→close→start_server
            # dance can lose the port to another process (TOCTOU), and a
            # jax-full profile without its profiler should be diagnosable
            # from the session log.
            print(f"kftpu-session: profiler server failed to start: {e!r}",
                  file=sys.stderr)
    touch(activity)
    try:
        srv.serve_forever(poll_interval=0.2)
    except KeyboardInterrupt:
        pass
    return 0


def exec_code(sock_path: str, code: str, timeout: float = 60.0) -> dict:
    """Client helper: run one cell in a session (used by the CLI and tests)."""
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        with unix_address(sock_path) as address:
            s.connect(address)
        s.sendall((json.dumps({"code": code}) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf.decode())


if __name__ == "__main__":
    sys.exit(main())
