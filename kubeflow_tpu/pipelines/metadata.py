"""Metadata store bindings — lineage for pipelines (ML-Metadata analog).

The reference's only C++ service is ml-metadata ((U) google/ml-metadata;
SURVEY.md §2.5#41): typed Artifacts/Executions/Contexts + an Event lineage
graph, on SQLite/MySQL. The rebuild keeps that native-parity component:
``native/metadata_store/metadata_store.cc`` (C++ on the system SQLite,
flat C ABI) consumed here via ctypes — pybind11 isn't in the image.

``MetadataStore(path)`` prefers the native library (built on first use, and
again whenever the committed source differs from what built the library on
disk) and falls back to a pure-Python sqlite3 implementation with identical
semantics only on hosts with no toolchain; where there is one, a failed build
is an error. ``backend="native"`` forces (and asserts) the C++ path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import sqlite3 as _pysqlite
import subprocess
import threading
from typing import Any, Optional, Union

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libmetadata_store.so")
_SRC_DIR = os.path.join(_REPO_ROOT, "native", "metadata_store")

# Node kinds (the C ABI's `kind` arg).
ARTIFACT, EXECUTION, CONTEXT = 0, 1, 2
# Execution states.
EXEC_NEW, EXEC_RUNNING, EXEC_COMPLETE, EXEC_FAILED, EXEC_CACHED, EXEC_CANCELED = range(6)
# Artifact states.
ART_UNKNOWN, ART_PENDING, ART_LIVE, ART_DELETED = range(4)
# Event types.
EVENT_INPUT, EVENT_OUTPUT = 0, 1

_build_lock = threading.Lock()
# The library is a build product (git-ignored), so one found on disk may
# predate the committed source. It is tied to the source by a stamp: the
# sha256 of the files the Makefile's rule depends on, written beside the
# library by the build that produced it.
_STAMP_PATH = _LIB_PATH + ".src-sha256"
_SRC_FILES = ("metadata_store.cc", "sqlite3_api.h", "Makefile")


class NativeBuildError(Exception):
    """The host has a toolchain and the committed source did not build."""


def _source_digest() -> str:
    h = hashlib.sha256()
    for name in _SRC_FILES:
        with open(os.path.join(_SRC_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _stamp() -> Optional[str]:
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip()
    except FileNotFoundError:
        return None


def _try_build_native() -> bool:
    """True when ``_LIB_PATH`` holds a library built from the committed
    source, building it (again) when the source differs from what built
    it. False only where there is nothing to build with: no source tree,
    or no ``make``/compiler on the host — the pure-Python backend's case.
    With a toolchain present a FAILED build raises: silently taking the
    other backend would hide a broken source file behind passing runs."""
    if not os.path.isdir(_SRC_DIR):
        return os.path.exists(_LIB_PATH)     # installed without the source
    digest = _source_digest()
    with _build_lock:
        if os.path.exists(_LIB_PATH) and _stamp() == digest:
            return True
        cxx = os.environ.get("CXX", "g++")
        if shutil.which("make") is None or shutil.which(cxx) is None:
            return False
        # Build beside the target and rename: another process may be
        # loading the library that is there now.
        tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
        try:
            subprocess.run(["make", "-B", f"OUT={tmp}"], cwd=_SRC_DIR,
                           check=True, capture_output=True, timeout=120)
        except subprocess.CalledProcessError as exc:
            raise NativeBuildError(
                "building the native metadata store failed:\n"
                + exc.stderr.decode(errors="replace")[-4000:]) from exc
        os.replace(tmp, _LIB_PATH)
        with open(tmp, "w") as f:     # same scratch name, now for the stamp
            f.write(digest)
        os.replace(tmp, _STAMP_PATH)
    return True


def _load_native() -> Optional[ctypes.CDLL]:
    if not _try_build_native():
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    c = ctypes
    lib.ms_open.restype = c.c_void_p
    lib.ms_open.argtypes = [c.c_char_p, c.c_char_p, c.c_int]
    lib.ms_close.argtypes = [c.c_void_p]
    lib.ms_put_type.restype = c.c_int64
    lib.ms_put_type.argtypes = [c.c_void_p, c.c_int, c.c_char_p]
    lib.ms_get_type.restype = c.c_int64
    lib.ms_get_type.argtypes = [c.c_void_p, c.c_int, c.c_char_p]
    lib.ms_create_artifact.restype = c.c_int64
    lib.ms_create_artifact.argtypes = [c.c_void_p, c.c_int64, c.c_char_p, c.c_int]
    lib.ms_update_artifact.argtypes = [c.c_void_p, c.c_int64, c.c_char_p, c.c_int]
    lib.ms_get_artifact.argtypes = [c.c_void_p, c.c_int64, c.c_char_p, c.c_int,
                                    c.POINTER(c.c_int), c.POINTER(c.c_int64)]
    lib.ms_create_execution.restype = c.c_int64
    lib.ms_create_execution.argtypes = [c.c_void_p, c.c_int64, c.c_int]
    lib.ms_update_execution_state.argtypes = [c.c_void_p, c.c_int64, c.c_int]
    lib.ms_get_execution.argtypes = [c.c_void_p, c.c_int64,
                                     c.POINTER(c.c_int), c.POINTER(c.c_int64)]
    lib.ms_create_context.restype = c.c_int64
    lib.ms_create_context.argtypes = [c.c_void_p, c.c_int64, c.c_char_p]
    lib.ms_list_by_type.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                    c.POINTER(c.c_int64), c.c_int]
    lib.ms_put_property.argtypes = [c.c_void_p, c.c_int, c.c_int64, c.c_char_p,
                                    c.c_int, c.c_int64, c.c_double, c.c_char_p]
    lib.ms_get_property.argtypes = [c.c_void_p, c.c_int, c.c_int64, c.c_char_p,
                                    c.POINTER(c.c_int), c.POINTER(c.c_int64),
                                    c.POINTER(c.c_double), c.c_char_p, c.c_int]
    lib.ms_list_property_keys.argtypes = [c.c_void_p, c.c_int, c.c_int64,
                                          c.c_char_p, c.c_int]
    lib.ms_find_executions_by_property.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.POINTER(c.c_int64), c.c_int]
    lib.ms_put_event.argtypes = [c.c_void_p, c.c_int64, c.c_int64, c.c_int,
                                 c.c_char_p]
    lib.ms_events_by_execution.argtypes = [
        c.c_void_p, c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int),
        c.c_char_p, c.c_int, c.c_int]
    lib.ms_events_by_artifact.argtypes = [
        c.c_void_p, c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int), c.c_int]
    lib.ms_add_association.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.ms_add_attribution.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.ms_list_context_executions.argtypes = [c.c_void_p, c.c_int64,
                                               c.POINTER(c.c_int64), c.c_int]
    lib.ms_list_context_artifacts.argtypes = [c.c_void_p, c.c_int64,
                                              c.POINTER(c.c_int64), c.c_int]
    lib.ms_report_observations.argtypes = [
        c.c_void_p, c.c_int64, c.c_char_p, c.POINTER(c.c_int64),
        c.POINTER(c.c_double), c.c_int]
    lib.ms_get_observations.argtypes = [
        c.c_void_p, c.c_int64, c.c_char_p, c.POINTER(c.c_int64),
        c.POINTER(c.c_double), c.c_int]
    lib.ms_observation_metrics.argtypes = [c.c_void_p, c.c_int64,
                                           c.c_char_p, c.c_int]
    return lib


_native_lib: Optional[ctypes.CDLL] = None
_native_tried = False


def native_library() -> Optional[ctypes.CDLL]:
    global _native_lib, _native_tried
    if not _native_tried:
        _native_lib = _load_native()
        _native_tried = True
    return _native_lib


PropertyValue = Union[int, float, str]


class _NativeBackend:
    def __init__(self, path: str):
        lib = native_library()
        if lib is None:
            raise RuntimeError("native metadata store library unavailable")
        self._lib = lib
        self._path = path
        err = ctypes.create_string_buffer(256)
        self._h = lib.ms_open(path.encode(), err, len(err))
        if not self._h:
            raise RuntimeError(f"ms_open failed: {err.value.decode()}")

    def list_artifact_ids(self) -> list[int]:
        """Every artifact id, ascending. The C ABI has no list-all call and
        the library is frozen, but the native store is the system SQLite
        underneath — enumerate through a read-only side connection (GC
        depends on a FULL scan: probing ids until the first gap silently
        unroots everything past a gap)."""
        db = _pysqlite.connect(f"file:{self._path}?mode=ro", uri=True)
        try:
            return [r[0] for r in
                    db.execute("SELECT id FROM artifacts ORDER BY id")]
        finally:
            db.close()

    def close(self) -> None:
        if self._h:
            self._lib.ms_close(self._h)
            self._h = None

    # thin 1:1 shims -----------------------------------------------------------

    def put_type(self, kind: int, name: str) -> int:
        return self._check_id(self._lib.ms_put_type(self._h, kind, name.encode()))

    def get_type(self, kind: int, name: str) -> Optional[int]:
        tid = self._lib.ms_get_type(self._h, kind, name.encode())
        return None if tid < 0 else tid

    def create_artifact(self, type_id: int, uri: str, state: int) -> int:
        return self._check_id(
            self._lib.ms_create_artifact(self._h, type_id, uri.encode(), state))

    def update_artifact(self, aid: int, uri: Optional[str], state: int) -> None:
        rc = self._lib.ms_update_artifact(
            self._h, aid, uri.encode() if uri is not None else None, state)
        self._check_rc(rc)

    def get_artifact(self, aid: int) -> Optional[tuple[str, int, int]]:
        uri = ctypes.create_string_buffer(4096)
        state = ctypes.c_int()
        tid = ctypes.c_int64()
        rc = self._lib.ms_get_artifact(self._h, aid, uri, len(uri),
                                       ctypes.byref(state), ctypes.byref(tid))
        if rc != 0:
            return None
        return uri.value.decode(), state.value, tid.value

    def create_execution(self, type_id: int, state: int) -> int:
        return self._check_id(
            self._lib.ms_create_execution(self._h, type_id, state))

    def update_execution_state(self, eid: int, state: int) -> None:
        self._check_rc(self._lib.ms_update_execution_state(self._h, eid, state))

    def get_execution(self, eid: int) -> Optional[tuple[int, int]]:
        state = ctypes.c_int()
        tid = ctypes.c_int64()
        rc = self._lib.ms_get_execution(self._h, eid, ctypes.byref(state),
                                        ctypes.byref(tid))
        return None if rc != 0 else (state.value, tid.value)

    def create_context(self, type_id: int, name: str) -> int:
        return self._check_id(
            self._lib.ms_create_context(self._h, type_id, name.encode()))

    def list_by_type(self, kind: int, type_id: int) -> list[int]:
        return self._ids(lambda buf, cap: self._lib.ms_list_by_type(
            self._h, kind, type_id, buf, cap))

    def put_property(self, kind: int, owner: int, key: str, tag: int,
                     ival: int, dval: float, sval: str) -> None:
        self._check_rc(self._lib.ms_put_property(
            self._h, kind, owner, key.encode(), tag, ival, dval, sval.encode()))

    def get_property(self, kind: int, owner: int, key: str
                     ) -> Optional[tuple[int, int, float, str]]:
        tag = ctypes.c_int()
        ival = ctypes.c_int64()
        dval = ctypes.c_double()
        sbuf = ctypes.create_string_buffer(65536)
        rc = self._lib.ms_get_property(
            self._h, kind, owner, key.encode(), ctypes.byref(tag),
            ctypes.byref(ival), ctypes.byref(dval), sbuf, len(sbuf))
        if rc != 0:
            return None
        return tag.value, ival.value, dval.value, sbuf.value.decode()

    def list_property_keys(self, kind: int, owner: int) -> list[str]:
        buf = ctypes.create_string_buffer(65536)
        n = self._lib.ms_list_property_keys(self._h, kind, owner, buf, len(buf))
        if n <= 0:
            return []
        return buf.value.decode().split("\n")

    def find_executions_by_property(self, key: str, sval: str) -> list[int]:
        return self._ids(lambda buf, cap: self._lib.ms_find_executions_by_property(
            self._h, key.encode(), sval.encode(), buf, cap))

    def put_event(self, eid: int, aid: int, etype: int, path: str) -> None:
        self._check_rc(self._lib.ms_put_event(self._h, eid, aid, etype,
                                              path.encode()))

    def events_by_execution(self, eid: int) -> list[tuple[int, int, str]]:
        cap = 256
        while True:
            arts = (ctypes.c_int64 * cap)()
            types = (ctypes.c_int * cap)()
            pbuf = ctypes.create_string_buffer(cap * 256)
            n = self._lib.ms_events_by_execution(self._h, eid, arts, types,
                                                 pbuf, len(pbuf), cap)
            if n < 0:
                raise RuntimeError("events_by_execution failed")
            if n <= cap:
                paths = pbuf.value.decode().split("\n") if n else []
                paths += [""] * (n - len(paths))
                return [(arts[i], types[i], paths[i]) for i in range(n)]
            cap = n

    def events_by_artifact(self, aid: int) -> list[tuple[int, int]]:
        cap = 256
        while True:
            execs = (ctypes.c_int64 * cap)()
            types = (ctypes.c_int * cap)()
            n = self._lib.ms_events_by_artifact(self._h, aid, execs, types, cap)
            if n < 0:
                raise RuntimeError("events_by_artifact failed")
            if n <= cap:
                return [(execs[i], types[i]) for i in range(n)]
            cap = n

    def add_association(self, ctx: int, eid: int) -> None:
        self._check_rc(self._lib.ms_add_association(self._h, ctx, eid))

    def add_attribution(self, ctx: int, aid: int) -> None:
        self._check_rc(self._lib.ms_add_attribution(self._h, ctx, aid))

    def list_context_executions(self, ctx: int) -> list[int]:
        return self._ids(lambda buf, cap: self._lib.ms_list_context_executions(
            self._h, ctx, buf, cap))

    def list_context_artifacts(self, ctx: int) -> list[int]:
        return self._ids(lambda buf, cap: self._lib.ms_list_context_artifacts(
            self._h, ctx, buf, cap))

    def report_observations(self, trial: int, metric: str,
                            points: list[tuple[int, float]]) -> None:
        n = len(points)
        if not n:
            return
        steps = (ctypes.c_int64 * n)(*[int(s) for s, _ in points])
        values = (ctypes.c_double * n)(*[float(v) for _, v in points])
        self._check_rc(self._lib.ms_report_observations(
            self._h, trial, metric.encode(), steps, values, n))

    def get_observations(self, trial: int,
                         metric: str) -> list[tuple[int, float]]:
        cap = 1024
        while True:
            steps = (ctypes.c_int64 * cap)()
            values = (ctypes.c_double * cap)()
            n = self._lib.ms_get_observations(
                self._h, trial, metric.encode(), steps, values, cap)
            if n < 0:
                raise RuntimeError("get_observations failed")
            if n <= cap:
                return [(steps[i], values[i]) for i in range(n)]
            cap = n

    def observation_metrics(self, trial: int) -> list[str]:
        cap = 65536
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.ms_observation_metrics(self._h, trial, buf, cap)
            if n < 0:
                raise RuntimeError("observation_metrics failed")
            if n < cap:           # joined length fits (snprintf truncates)
                return buf.value.decode().split("\n") if n else []
            cap = n + 1

    # helpers ------------------------------------------------------------------

    @staticmethod
    def _ids_call(fn, cap):
        buf = (ctypes.c_int64 * cap)()
        n = fn(buf, cap)
        return n, buf

    def _ids(self, fn) -> list[int]:
        cap = 256
        while True:
            n, buf = self._ids_call(fn, cap)
            if n < 0:
                raise RuntimeError("metadata store query failed")
            if n <= cap:
                return [buf[i] for i in range(n)]
            cap = n  # truncated: retry with the exact size

    @staticmethod
    def _check_id(v: int) -> int:
        if v < 0:
            raise RuntimeError("metadata store write failed")
        return v

    @staticmethod
    def _check_rc(rc: int) -> None:
        if rc != 0:
            raise RuntimeError("metadata store operation failed")


class _PythonBackend:
    """Same schema/semantics on the stdlib sqlite3 module (fallback when the
    native library can't be built/loaded)."""

    _SCHEMA = """
    CREATE TABLE IF NOT EXISTS types(
      id INTEGER PRIMARY KEY AUTOINCREMENT, kind INTEGER NOT NULL,
      name TEXT NOT NULL, UNIQUE(kind, name));
    CREATE TABLE IF NOT EXISTS artifacts(
      id INTEGER PRIMARY KEY AUTOINCREMENT, type_id INTEGER NOT NULL,
      uri TEXT NOT NULL DEFAULT '', state INTEGER NOT NULL DEFAULT 0,
      create_ts INTEGER NOT NULL DEFAULT (strftime('%s','now')));
    CREATE TABLE IF NOT EXISTS executions(
      id INTEGER PRIMARY KEY AUTOINCREMENT, type_id INTEGER NOT NULL,
      state INTEGER NOT NULL DEFAULT 0,
      create_ts INTEGER NOT NULL DEFAULT (strftime('%s','now')));
    CREATE TABLE IF NOT EXISTS contexts(
      id INTEGER PRIMARY KEY AUTOINCREMENT, type_id INTEGER NOT NULL,
      name TEXT NOT NULL, UNIQUE(type_id, name));
    CREATE TABLE IF NOT EXISTS properties(
      kind INTEGER NOT NULL, owner_id INTEGER NOT NULL, key TEXT NOT NULL,
      tag INTEGER NOT NULL, ival INTEGER, dval REAL, sval TEXT,
      PRIMARY KEY(kind, owner_id, key));
    CREATE INDEX IF NOT EXISTS properties_by_value ON properties(kind, key, sval);
    CREATE TABLE IF NOT EXISTS events(
      id INTEGER PRIMARY KEY AUTOINCREMENT, execution_id INTEGER NOT NULL,
      artifact_id INTEGER NOT NULL, type INTEGER NOT NULL,
      path TEXT NOT NULL DEFAULT '',
      ts INTEGER NOT NULL DEFAULT (strftime('%s','now')));
    CREATE INDEX IF NOT EXISTS events_by_execution ON events(execution_id);
    CREATE INDEX IF NOT EXISTS events_by_artifact ON events(artifact_id);
    CREATE TABLE IF NOT EXISTS associations(
      context_id INTEGER NOT NULL, execution_id INTEGER NOT NULL,
      PRIMARY KEY(context_id, execution_id));
    CREATE TABLE IF NOT EXISTS attributions(
      context_id INTEGER NOT NULL, artifact_id INTEGER NOT NULL,
      PRIMARY KEY(context_id, artifact_id));
    CREATE TABLE IF NOT EXISTS observations(
      trial_id INTEGER NOT NULL, metric TEXT NOT NULL, step INTEGER NOT NULL,
      value REAL NOT NULL,
      ts INTEGER NOT NULL DEFAULT (strftime('%s','now')),
      PRIMARY KEY(trial_id, metric, step));
    """

    def __init__(self, path: str):
        self._db = _pysqlite.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        with self._lock:
            self._db.executescript(self._SCHEMA)
            self._db.commit()

    def close(self) -> None:
        self._db.close()

    def _one(self, sql, args=()):
        with self._lock:
            cur = self._db.execute(sql, args)
            return cur.fetchone()

    def _all(self, sql, args=()):
        with self._lock:
            return self._db.execute(sql, args).fetchall()

    def _write(self, sql, args=()):
        with self._lock:
            cur = self._db.execute(sql, args)
            self._db.commit()
            return cur.lastrowid

    def put_type(self, kind, name):
        self._write("INSERT OR IGNORE INTO types(kind,name) VALUES(?,?)",
                    (kind, name))
        return self._one("SELECT id FROM types WHERE kind=? AND name=?",
                         (kind, name))[0]

    def get_type(self, kind, name):
        row = self._one("SELECT id FROM types WHERE kind=? AND name=?",
                        (kind, name))
        return row[0] if row else None

    def create_artifact(self, type_id, uri, state):
        return self._write(
            "INSERT INTO artifacts(type_id,uri,state) VALUES(?,?,?)",
            (type_id, uri, state))

    def update_artifact(self, aid, uri, state):
        if uri is not None:
            self._write("UPDATE artifacts SET uri=?, state=? WHERE id=?",
                        (uri, state, aid))
        else:
            self._write("UPDATE artifacts SET state=? WHERE id=?", (state, aid))

    def get_artifact(self, aid):
        row = self._one("SELECT uri,state,type_id FROM artifacts WHERE id=?",
                        (aid,))
        return tuple(row) if row else None

    def list_artifact_ids(self):
        return [r[0] for r in
                self._all("SELECT id FROM artifacts ORDER BY id")]

    def create_execution(self, type_id, state):
        return self._write("INSERT INTO executions(type_id,state) VALUES(?,?)",
                           (type_id, state))

    def update_execution_state(self, eid, state):
        self._write("UPDATE executions SET state=? WHERE id=?", (state, eid))

    def get_execution(self, eid):
        row = self._one("SELECT state,type_id FROM executions WHERE id=?",
                        (eid,))
        return tuple(row) if row else None

    def create_context(self, type_id, name):
        self._write("INSERT OR IGNORE INTO contexts(type_id,name) VALUES(?,?)",
                    (type_id, name))
        return self._one("SELECT id FROM contexts WHERE type_id=? AND name=?",
                         (type_id, name))[0]

    def list_by_type(self, kind, type_id):
        table = {ARTIFACT: "artifacts", EXECUTION: "executions",
                 CONTEXT: "contexts"}[kind]
        return [r[0] for r in self._all(
            f"SELECT id FROM {table} WHERE type_id=? ORDER BY id", (type_id,))]

    def put_property(self, kind, owner, key, tag, ival, dval, sval):
        self._write(
            "INSERT OR REPLACE INTO properties(kind,owner_id,key,tag,ival,dval,sval)"
            " VALUES(?,?,?,?,?,?,?)", (kind, owner, key, tag, ival, dval, sval))

    def get_property(self, kind, owner, key):
        row = self._one(
            "SELECT tag,ival,dval,sval FROM properties"
            " WHERE kind=? AND owner_id=? AND key=?", (kind, owner, key))
        return tuple(row) if row else None

    def list_property_keys(self, kind, owner):
        return [r[0] for r in self._all(
            "SELECT key FROM properties WHERE kind=? AND owner_id=? ORDER BY key",
            (kind, owner))]

    def find_executions_by_property(self, key, sval):
        return [r[0] for r in self._all(
            "SELECT owner_id FROM properties"
            " WHERE kind=1 AND key=? AND sval=? ORDER BY owner_id",
            (key, sval))]

    def report_observations(self, trial, metric, points):
        if not points:
            return
        with self._lock:
            try:
                self._db.executemany(
                    "INSERT INTO observations(trial_id,metric,step,value)"
                    " VALUES(?,?,?,?) ON CONFLICT(trial_id,metric,step)"
                    " DO UPDATE SET value=excluded.value,"
                    " ts=strftime('%s','now')",
                    [(trial, metric, int(s), float(v)) for s, v in points])
                self._db.commit()
            except _pysqlite.Error:
                # Batch atomicity matches the native backend: a mid-batch
                # failure must not leave half the rows in the implicit open
                # transaction for the next unrelated commit to persist.
                self._db.rollback()
                raise

    def get_observations(self, trial, metric):
        return [(r[0], r[1]) for r in self._all(
            "SELECT step,value FROM observations"
            " WHERE trial_id=? AND metric=? ORDER BY step", (trial, metric))]

    def observation_metrics(self, trial):
        return [r[0] for r in self._all(
            "SELECT DISTINCT metric FROM observations WHERE trial_id=?"
            " ORDER BY metric", (trial,))]

    def put_event(self, eid, aid, etype, path):
        self._write(
            "INSERT INTO events(execution_id,artifact_id,type,path)"
            " VALUES(?,?,?,?)", (eid, aid, etype, path))

    def events_by_execution(self, eid):
        return [tuple(r) for r in self._all(
            "SELECT artifact_id,type,path FROM events"
            " WHERE execution_id=? ORDER BY id", (eid,))]

    def events_by_artifact(self, aid):
        return [tuple(r) for r in self._all(
            "SELECT execution_id,type FROM events"
            " WHERE artifact_id=? ORDER BY id", (aid,))]

    def add_association(self, ctx, eid):
        self._write(
            "INSERT OR IGNORE INTO associations(context_id,execution_id)"
            " VALUES(?,?)", (ctx, eid))

    def add_attribution(self, ctx, aid):
        self._write(
            "INSERT OR IGNORE INTO attributions(context_id,artifact_id)"
            " VALUES(?,?)", (ctx, aid))

    def list_context_executions(self, ctx):
        return [r[0] for r in self._all(
            "SELECT execution_id FROM associations WHERE context_id=?"
            " ORDER BY execution_id", (ctx,))]

    def list_context_artifacts(self, ctx):
        return [r[0] for r in self._all(
            "SELECT artifact_id FROM attributions WHERE context_id=?"
            " ORDER BY artifact_id", (ctx,))]


class MetadataStore:
    """High-level store: typed nodes + properties + lineage queries.

    Property values are int/float/str (the MLMD value union)."""

    def __init__(self, path: str, backend: str = "auto"):
        self.path = path
        if backend == "python":
            self._b = _PythonBackend(path)
            self.backend = "python"
        elif backend == "native":
            self._b = _NativeBackend(path)
            self.backend = "native"
        elif native_library() is not None:    # raises on a failed build
            self._b = _NativeBackend(path)
            self.backend = "native"
        else:
            self._b = _PythonBackend(path)
            self.backend = "python"

    def close(self) -> None:
        self._b.close()

    def __enter__(self) -> "MetadataStore":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- types -----------------------------------------------------------------

    def put_artifact_type(self, name: str) -> int:
        return self._b.put_type(ARTIFACT, name)

    def put_execution_type(self, name: str) -> int:
        return self._b.put_type(EXECUTION, name)

    def put_context_type(self, name: str) -> int:
        return self._b.put_type(CONTEXT, name)

    # -- properties ------------------------------------------------------------

    def _set_props(self, kind: int, owner: int,
                   props: Optional[dict[str, PropertyValue]]) -> None:
        for k, v in (props or {}).items():
            if isinstance(v, bool):
                v = int(v)
            if isinstance(v, int):
                self._b.put_property(kind, owner, k, 0, v, 0.0, "")
            elif isinstance(v, float):
                self._b.put_property(kind, owner, k, 1, 0, v, "")
            else:
                self._b.put_property(kind, owner, k, 2, 0, 0.0, str(v))

    def _get_props(self, kind: int, owner: int) -> dict[str, PropertyValue]:
        out: dict[str, PropertyValue] = {}
        for k in self._b.list_property_keys(kind, owner):
            row = self._b.get_property(kind, owner, k)
            if row is None:
                continue
            tag, ival, dval, sval = row
            out[k] = ival if tag == 0 else dval if tag == 1 else sval
        return out

    # -- artifacts -------------------------------------------------------------

    def create_artifact(self, type_name: str, uri: str = "",
                        state: int = ART_PENDING,
                        properties: Optional[dict[str, PropertyValue]] = None,
                        ) -> int:
        tid = self._b.put_type(ARTIFACT, type_name)
        aid = self._b.create_artifact(tid, uri, state)
        self._set_props(ARTIFACT, aid, properties)
        return aid

    def update_artifact(self, aid: int, *, uri: Optional[str] = None,
                        state: int = ART_LIVE,
                        properties: Optional[dict[str, PropertyValue]] = None,
                        ) -> None:
        self._b.update_artifact(aid, uri, state)
        self._set_props(ARTIFACT, aid, properties)

    def get_artifact(self, aid: int) -> Optional[dict[str, Any]]:
        row = self._b.get_artifact(aid)
        if row is None:
            return None
        uri, state, tid = row
        return {"id": aid, "uri": uri, "state": state, "type_id": tid,
                "properties": self._get_props(ARTIFACT, aid)}

    def artifacts_of_type(self, type_name: str) -> list[int]:
        tid = self._b.get_type(ARTIFACT, type_name)
        return [] if tid is None else self._b.list_by_type(ARTIFACT, tid)

    def list_artifact_ids(self) -> list[int]:
        """Every artifact id regardless of type, ascending — the full-scan
        enumeration destructive consumers (pipelines/gc.py root discovery)
        must use instead of probing ids until the first gap."""
        return self._b.list_artifact_ids()

    # -- executions ------------------------------------------------------------

    def create_execution(self, type_name: str, state: int = EXEC_RUNNING,
                         properties: Optional[dict[str, PropertyValue]] = None,
                         ) -> int:
        tid = self._b.put_type(EXECUTION, type_name)
        eid = self._b.create_execution(tid, state)
        self._set_props(EXECUTION, eid, properties)
        return eid

    def update_execution(self, eid: int, state: int,
                         properties: Optional[dict[str, PropertyValue]] = None,
                         ) -> None:
        self._b.update_execution_state(eid, state)
        self._set_props(EXECUTION, eid, properties)

    def get_execution(self, eid: int) -> Optional[dict[str, Any]]:
        row = self._b.get_execution(eid)
        if row is None:
            return None
        state, tid = row
        return {"id": eid, "state": state, "type_id": tid,
                "properties": self._get_props(EXECUTION, eid)}

    def executions_of_type(self, type_name: str) -> list[int]:
        tid = self._b.get_type(EXECUTION, type_name)
        return [] if tid is None else self._b.list_by_type(EXECUTION, tid)

    def find_executions_by_property(self, key: str, value: str) -> list[int]:
        return self._b.find_executions_by_property(key, value)

    # -- contexts --------------------------------------------------------------

    def create_context(self, type_name: str, name: str,
                       properties: Optional[dict[str, PropertyValue]] = None,
                       ) -> int:
        tid = self._b.put_type(CONTEXT, type_name)
        cid = self._b.create_context(tid, name)
        self._set_props(CONTEXT, cid, properties)
        return cid

    def add_association(self, context_id: int, execution_id: int) -> None:
        self._b.add_association(context_id, execution_id)

    def add_attribution(self, context_id: int, artifact_id: int) -> None:
        self._b.add_attribution(context_id, artifact_id)

    def context_executions(self, context_id: int) -> list[int]:
        return self._b.list_context_executions(context_id)

    def context_artifacts(self, context_id: int) -> list[int]:
        return self._b.list_context_artifacts(context_id)

    # -- lineage ---------------------------------------------------------------

    def put_event(self, execution_id: int, artifact_id: int, event_type: int,
                  path: str = "") -> None:
        self._b.put_event(execution_id, artifact_id, event_type, path)

    def events_by_execution(self, execution_id: int) -> list[tuple[int, int, str]]:
        """[(artifact_id, event_type, path)] in event order."""
        return self._b.events_by_execution(execution_id)

    def events_by_artifact(self, artifact_id: int) -> list[tuple[int, int]]:
        """[(execution_id, event_type)] in event order."""
        return self._b.events_by_artifact(artifact_id)

    # -- observations (katib observation_logs analog — SURVEY.md §2.4#33) -----

    def report_observations(self, trial_execution_id: int, metric: str,
                            points: list[tuple[int, float]]) -> None:
        """Batch-upsert (step, value) points for one (trial, metric) into
        the dedicated observations table — one transaction, no string-keyed
        property rows (the 1e5-point-log fast path)."""
        self._b.report_observations(trial_execution_id, metric, points)

    def get_observations(self, trial_execution_id: int,
                         metric: str) -> list[tuple[int, float]]:
        return self._b.get_observations(trial_execution_id, metric)

    def observation_metrics(self, trial_execution_id: int) -> list[str]:
        return self._b.observation_metrics(trial_execution_id)

    def lineage(self, artifact_id: int, max_hops: int = 20) -> dict[str, Any]:
        """Upstream provenance: which executions/artifacts produced this one.

        Walks OUTPUT events backwards (producer execution → its INPUT
        artifacts → their producers …), the MLMD lineage-graph query."""
        seen_a: set[int] = set()
        seen_e: set[int] = set()
        frontier = [artifact_id]
        for _ in range(max_hops):
            next_frontier: list[int] = []
            for aid in frontier:
                if aid in seen_a:
                    continue
                seen_a.add(aid)
                for eid, etype in self._b.events_by_artifact(aid):
                    if etype != EVENT_OUTPUT or eid in seen_e:
                        continue  # producer executions only
                    seen_e.add(eid)
                    for in_aid, in_type, _ in self._b.events_by_execution(eid):
                        if in_type == EVENT_INPUT:
                            next_frontier.append(in_aid)
            if not next_frontier:
                break
            frontier = next_frontier
        return {"artifacts": sorted(seen_a), "executions": sorted(seen_e)}
