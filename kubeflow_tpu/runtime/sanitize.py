"""Runtime sanitizers — the dynamic half of ``kftpu lint`` (ISSUE 7).

``KFTPU_SANITIZE`` is a comma-separated list of modes:

- ``transfer`` (also the legacy ``1``): the engine runs every decode pass
  under ``jax.transfer_guard("disallow")`` (serve/engine.py) — implicit
  host<->device transfers raise instead of silently stalling the hot
  loop. Cross-checks the D1xx device-hygiene rules.
- ``refcount``: the ``PageAllocator`` stamps every page alloc/incref with
  an owner + call site, and ``assert_quiescent`` reports leaks PER OWNER
  (which request/path forgot its free). Cross-checks R501/R502.
- ``lockorder``: a process-wide lock-acquisition watchdog
  (``install_lockorder_watchdog``) wraps ``threading.Lock``/``RLock``
  creation, records the runtime acquisition-order graph keyed by lock
  CREATION SITE, and raises ``LockOrderError`` the moment an acquisition
  closes a cycle — the dynamic half of R503. Installed automatically at
  ``import kubeflow_tpu`` when the mode is on.
- ``recompile``: a compilation watchdog (``install_recompile_watchdog``)
  hooks JAX's compilation-cache-miss logging (the ``Compiling <fn>``
  records ``jax._src.interpreters.pxla`` emits once per actual compile)
  and attributes EVERY retrace to the first non-library stack frame —
  the call site that dispatched it. After ``mark_compile_warm()`` any
  further compile is a steady-state recompile: ``recompile_report()``
  is the audit payload (the ``leak_report_by_owner()`` of the compile
  cache) and ``assert_no_steady_recompiles()`` raises
  ``RecompileError`` naming each offending site. The dynamic half of
  the F6xx compilation-stability rules.
- ``contract``: a name-contract auditor (``install_contract_auditor``)
  records every metric series actually rendered to an exposition
  endpoint, every series the autoscaler probe actually matched, and
  every ``X-Kftpu-*`` header actually read or stamped on a hop —
  ``contract_report()`` is the audit payload and ``contract_diff()``
  checks it against the statically-extracted contract table
  (``kftpu lint --contracts-json``). The dynamic half of the X7xx
  cross-component contract rules: a series name the AST extractor
  cannot see (built dynamically) shows up here as *undeclared*.
- ``threads``: a thread-lifecycle sanitizer (``install_thread_sanitizer``)
  wraps ``threading.Thread`` so every thread APPLICATION code creates is
  stamped with its creation site and an owner (the refcount sanitizer's
  owner idiom: an explicit ``thread_owner(...)`` scope, else the bound
  target's class, else inherited from the creating thread).
  ``thread_report()`` lists the live tracked threads,
  ``thread_leak_report_by_owner()`` groups them, and
  ``assert_threads_quiescent()`` — asserted at engine/server/router
  stop — raises ``ThreadLeakError`` naming each leaked thread's name,
  owner, and creation site. Library-internal threads (jax pools,
  executor workers, socketserver handlers) are deliberately untracked:
  quiescence is asserted over the threads THIS codebase starts. The
  dynamic half of the T8xx liveness rules.
- ``all``: everything above.

This module is stdlib-only (no jax): the watchdogs must be installable
before any engine/router constructs its locks — or jax even imports —
including under a bare ``import kubeflow_tpu``. The recompile hook works
without touching jax because jax logs every compile at DEBUG even when
``jax_log_compiles`` is off; raising the LOGGER's level to DEBUG and
attaching a recording handler is enough, and the records never reach a
console handler (root stays at WARNING).
"""

from __future__ import annotations

import _thread
import contextlib
import logging
import os
import re
import sys
import threading
import time
import weakref
from typing import Iterable, Optional

_KNOWN_MODES = frozenset({"transfer", "refcount", "lockorder",
                          "recompile", "contract", "threads"})


def sanitize_modes() -> frozenset:
    """The active sanitizer modes from ``KFTPU_SANITIZE``. Legacy truthy
    values (``1``/``on``/anything unrecognized) mean ``transfer`` — the
    PR-5 behavior those settings already had."""
    raw = os.environ.get("KFTPU_SANITIZE", "")
    if raw.strip() in ("", "0"):
        return frozenset()
    out: set[str] = set()
    for tok in raw.split(","):
        t = tok.strip().lower()
        if not t:
            continue
        if t == "all":
            out |= _KNOWN_MODES
        elif t in _KNOWN_MODES:
            out.add(t)
        else:
            out.add("transfer")
    return frozenset(out)


def enabled(mode: str) -> bool:
    return mode in sanitize_modes()


def call_site(skip_files: tuple = ()) -> str:
    """``file:line`` of the nearest caller frame outside this module and
    ``skip_files`` — the owner stamp for refcount mode and the lock
    identity for lockorder mode."""
    skip = (__file__,) + tuple(skip_files)
    frame = sys._getframe(1)
    for _ in range(32):
        if frame is None:
            break
        fname = frame.f_code.co_filename
        if fname not in skip and "threading" not in os.path.basename(fname):
            return f"{os.path.basename(fname)}:{frame.f_lineno}"
        frame = frame.f_back
    return "<unknown>"


# -- lockorder watchdog --------------------------------------------------------


class LockOrderError(AssertionError):
    """An acquisition closed a cycle in the runtime lock-order graph."""


class _LockOrderWatchdog:
    """Process-wide acquisition-order recorder.

    Lock identity is the CREATION call site (``router.py:101``), so every
    Router's ``_lock`` is one node — the graph describes the code, not
    one process's object population. Edges A->B mean "B acquired while A
    held". Same-site edges are skipped (reentrant RLocks and ordered
    traversal over same-class instances are both legitimate). Cycle check
    runs on each NEW edge only."""

    def __init__(self):
        self.graph: dict[str, set[str]] = {}
        self.edge_threads: dict[tuple, str] = {}
        self._meta = _thread.allocate_lock()   # raw: never itself watched
        self._tls = threading.local()

    # -- per-thread held stack --------------------------------------------

    def _held(self) -> list:
        return getattr(self._tls, "held", [])

    def note_acquire(self, site: str, obj_id: int) -> None:
        held = self._held()
        new_edges = []
        for h_site, _ in held:
            if h_site != site:
                new_edges.append((h_site, site))
        cycle = None
        if new_edges:
            with self._meta:
                for a, b in new_edges:
                    peers = self.graph.setdefault(a, set())
                    if b in peers:
                        continue
                    peers.add(b)
                    self.edge_threads[(a, b)] = \
                        threading.current_thread().name
                    cycle = cycle or self._find_cycle(b, a)
        if cycle is not None:
            # Do NOT record the acquisition: the caller releases the
            # underlying lock and re-raises.
            raise LockOrderError(
                "lock-order inversion at runtime: "
                + " -> ".join(cycle + [cycle[0]])
                + f" (closing edge acquired on thread "
                f"'{threading.current_thread().name}'); "
                "the static analyzer's R503 models this cycle")
        self._tls.held = held + [(site, obj_id)]

    def note_release(self, site: str, obj_id: int) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] == (site, obj_id):
                self._tls.held = held[:i] + held[i + 1:]
                return

    def _find_cycle(self, start: str, target: str) -> Optional[list]:
        """Path start ->* target in the graph (meta lock held), i.e. the
        cycle target -> start ->* target. Returns node list from target."""
        stack = [(start, [start])]
        seen = {start}
        while stack:
            cur, path = stack.pop()
            for nxt in self.graph.get(cur, ()):
                if nxt == target:
                    return [target] + path
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append((nxt, path + [nxt]))
        return None

    def report(self) -> dict:
        with self._meta:
            return {a: sorted(bs) for a, bs in sorted(self.graph.items())}


class _WatchedLock:
    """Wraps one real lock; forwards everything, reporting acquire/release
    to the watchdog. Works as a Condition's backing lock through the
    stdlib's acquire/release fallbacks."""

    __slots__ = ("_lk", "_site", "_wd")

    def __init__(self, lk, site: str, wd: _LockOrderWatchdog):
        self._lk = lk
        self._site = site
        self._wd = wd

    def acquire(self, *args, **kwargs):
        got = self._lk.acquire(*args, **kwargs)
        if got:
            try:
                self._wd.note_acquire(self._site, id(self))
            except LockOrderError:
                self._lk.release()
                raise
        return got

    def release(self):
        self._wd.note_release(self._site, id(self))
        self._lk.release()

    def locked(self):
        return self._lk.locked()

    def __getattr__(self, name):
        # stdlib internals poke at real-lock attributes we don't model
        # (_at_fork_reinit in concurrent.futures, acquire_lock aliases) —
        # forward them; the bookkeeping only needs acquire/release.
        return getattr(self._lk, name)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"<WatchedLock {self._site} of {self._lk!r}>"


_watchdog: Optional[_LockOrderWatchdog] = None
_originals: Optional[tuple] = None


def install_lockorder_watchdog() -> _LockOrderWatchdog:
    """Patch ``threading.Lock``/``RLock`` so every lock created AFTER this
    call is watched. Idempotent; returns the active watchdog."""
    global _watchdog, _originals
    if _watchdog is not None:
        return _watchdog
    wd = _LockOrderWatchdog()
    orig_lock, orig_rlock = threading.Lock, threading.RLock

    def make_lock():
        return _WatchedLock(orig_lock(), call_site(), wd)

    def make_rlock():
        return _WatchedLock(orig_rlock(), call_site(), wd)

    threading.Lock = make_lock           # type: ignore[assignment]
    threading.RLock = make_rlock         # type: ignore[assignment]
    _originals = (orig_lock, orig_rlock)
    _watchdog = wd
    return wd


def uninstall_lockorder_watchdog() -> None:
    """Restore the real factories. Locks created while installed keep
    working (they wrap real locks); they go on reporting to the detached
    watchdog object, which nothing consults anymore."""
    global _watchdog, _originals
    if _originals is not None:
        threading.Lock, threading.RLock = _originals
        _originals = None
    _watchdog = None


def lockorder_watchdog() -> Optional[_LockOrderWatchdog]:
    return _watchdog


# -- recompile watchdog --------------------------------------------------------


class RecompileError(AssertionError):
    """A jit compile happened after ``mark_compile_warm()`` — the steady
    state recompiled. The message attributes every retrace to its
    dispatch call site."""


#: Loggers that announce one record per ACTUAL compile (cache miss).
#: ``pxla`` covers jit/pjit ("Compiling <fn> with global shapes...") and
#: pmap ("Compiling <fn> (<id>) for <n> devices..."); both spellings
#: start with "Compiling ".
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla",)
_COMPILE_PREFIX = "Compiling "
_PROGRAM_NAME = re.compile(r"\w+\((.+)\)")


def _app_call_site() -> str:
    """``file:line`` of the nearest stack frame outside installed
    libraries, the logging machinery, and this module — the application
    code whose dispatch triggered the compile."""
    frame = sys._getframe(1)
    for _ in range(128):
        if frame is None:
            break
        fname = frame.f_code.co_filename
        base = os.path.basename(os.path.dirname(fname))
        if "site-packages" not in fname and "dist-packages" not in fname \
                and base != "logging" and fname != __file__ \
                and not fname.startswith("<frozen"):
            return f"{os.path.basename(fname)}:{frame.f_lineno}"
        frame = frame.f_back
    return "<unknown>"


class _RecompileWatchdog(logging.Handler):
    """Counts and attributes every jit compile in the process.

    Compiles before ``mark_warm()`` are the expected warmup set; each is
    still attributed (the report shows where every trace came from).
    Compiles after are steady-state recompiles — the exact defect class
    the F6xx rules model statically — and fail
    ``assert_no_steady_recompiles()`` with the full attribution."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self._meta = _thread.allocate_lock()
        self._warm = False
        # phase -> {(fn, site): count}; insertion order = compile order
        self.compiles: dict[str, dict] = {"warmup": {}, "steady": {}}

    # -- logging.Handler ---------------------------------------------------

    def emit(self, record: logging.LogRecord) -> None:
        # Installation raises the hooked logger to DEBUG and cuts its
        # propagation (jax parks a stderr StreamHandler on the "jax"
        # logger that would otherwise splat every DEBUG compile record
        # to the console). Anything a user would normally see — WARNING
        # and up — is forwarded to the parent chain by hand.
        if record.levelno >= logging.WARNING:
            logging.getLogger("jax").handle(record)
        try:
            msg = record.getMessage()
        except (TypeError, ValueError):
            # A malformed record (bad %-args) must never break jax's
            # dispatch path; it also can't be a compile announcement.
            return
        if not msg.startswith(_COMPILE_PREFIX):
            return
        fn = str(record.args[0]) if record.args else \
            msg[len(_COMPILE_PREFIX):].split(" ", 1)[0]
        # This JAX (0.9) announces the PROGRAM's name, "jit(<fn>)" or
        # "pmap(<fn>)"; the report names the function.
        wrapped = _PROGRAM_NAME.fullmatch(fn)
        if wrapped:
            fn = wrapped.group(1)
        site = _app_call_site()
        with self._meta:
            phase = "steady" if self._warm else "warmup"
            key = (fn, site)
            self.compiles[phase][key] = \
                self.compiles[phase].get(key, 0) + 1

    # -- audit surface -----------------------------------------------------

    def mark_warm(self) -> None:
        """Everything the workload needed is compiled; from here on any
        compile is a steady-state recompile."""
        with self._meta:
            self._warm = True

    def reset(self, warm: bool = False) -> None:
        with self._meta:
            self._warm = warm
            self.compiles = {"warmup": {}, "steady": {}}

    def steady_count(self) -> int:
        with self._meta:
            return sum(self.compiles["steady"].values())

    def report(self) -> dict:
        """``{"warm": bool, "warmup": [...], "steady": [...],
        "steady_count": int}`` with one ``{fn, site, count}`` entry per
        distinct (compiled function, dispatch site) pair, in first-
        compile order — who traced, from where, how often."""
        with self._meta:
            out = {"warm": self._warm,
                   "steady_count": sum(self.compiles["steady"].values())}
            for phase in ("warmup", "steady"):
                out[phase] = [
                    {"fn": fn, "site": site, "count": count}
                    for (fn, site), count in self.compiles[phase].items()]
            return out

    def assert_no_steady_recompiles(self) -> None:
        rep = self.report()
        if rep["steady_count"]:
            lines = [f"  {e['fn']} x{e['count']} dispatched at "
                     f"{e['site']}" for e in rep["steady"]]
            raise RecompileError(
                f"{rep['steady_count']} steady-state recompile(s) after "
                "mark_compile_warm() — the dispatch signature drifted "
                "(shape/dtype/weak-type/static-arg/pytree; the static "
                "F6xx rules model exactly this):\n" + "\n".join(lines))


_recompile_wd: Optional[_RecompileWatchdog] = None
_logger_prior: dict[str, tuple[int, bool]] = {}


def install_recompile_watchdog() -> _RecompileWatchdog:
    """Attach the compile recorder to jax's compile-announcing loggers.
    Idempotent; works before jax is imported (loggers are created on
    demand by name) and never flips ``jax_log_compiles`` — the records
    exist at DEBUG regardless, they just need a handler that listens."""
    global _recompile_wd
    if _recompile_wd is not None:
        return _recompile_wd
    wd = _RecompileWatchdog()
    for name in _COMPILE_LOGGERS:
        lg = logging.getLogger(name)
        _logger_prior[name] = (lg.level, lg.propagate)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False        # see _RecompileWatchdog.emit
        lg.addHandler(wd)
    _recompile_wd = wd
    return wd


def uninstall_recompile_watchdog() -> None:
    global _recompile_wd
    if _recompile_wd is None:
        return
    for name in _COMPILE_LOGGERS:
        lg = logging.getLogger(name)
        lg.removeHandler(_recompile_wd)
        level, prop = _logger_prior.pop(name, (logging.NOTSET, True))
        lg.setLevel(level)
        lg.propagate = prop
    _recompile_wd = None


def recompile_watchdog() -> Optional[_RecompileWatchdog]:
    return _recompile_wd


def mark_compile_warm() -> None:
    """Module-level convenience mirroring the watchdog method: call at
    the end of warmup; a no-op when the mode is off."""
    if _recompile_wd is not None:
        _recompile_wd.mark_warm()


def recompile_report() -> dict:
    """The audit payload, shaped like ``leak_report_by_owner()``: empty
    dict when the watchdog is not installed."""
    if _recompile_wd is None:
        return {}
    return _recompile_wd.report()


def assert_no_steady_recompiles() -> None:
    if _recompile_wd is not None:
        _recompile_wd.assert_no_steady_recompiles()


# -- contract auditor ----------------------------------------------------------


#: Suffixes a histogram family fans out into at render time; the static
#: contract table records the FAMILY name, so runtime/consumed series are
#: normalized back through these before matching.
HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def series_base(name: str) -> str:
    """``kftpu_x_seconds_bucket`` → ``kftpu_x_seconds`` (histogram fan-out
    stripped); non-suffixed names pass through."""
    for suffix in HIST_SUFFIXES:
        if name.endswith(suffix):
            return name[:-len(suffix)]
    return name


class _ContractAuditor:
    """Records the name exchanges a run ACTUALLY performed.

    Four sets, all of plain strings: metric series rendered to an
    exposition endpoint / matched by a scraper, and ``X-Kftpu-*`` headers
    stamped onto a forwarded hop / read off a request. Everything is
    process-local and bounded by the name population (a few dozen), so
    recording is a set-add under one raw lock — cheap enough to leave in
    scrape paths."""

    def __init__(self):
        self._meta = _thread.allocate_lock()   # raw: never itself watched
        self.series: dict[str, set] = {"produced": set(), "consumed": set()}
        self.headers: dict[str, set] = {"set": set(), "read": set()}

    def note_series(self, name: str, direction: str) -> None:
        with self._meta:
            self.series[direction].add(str(name))

    def note_header(self, name: str, direction: str) -> None:
        with self._meta:
            self.headers[direction].add(str(name))

    def report(self) -> dict:
        with self._meta:
            return {
                "series_produced": sorted(self.series["produced"]),
                "series_consumed": sorted(self.series["consumed"]),
                "headers_set": sorted(self.headers["set"]),
                "headers_read": sorted(self.headers["read"]),
            }

    def reset(self) -> None:
        with self._meta:
            for d in (self.series, self.headers):
                for s in d.values():
                    s.clear()


_contract_auditor: Optional[_ContractAuditor] = None


def install_contract_auditor() -> _ContractAuditor:
    """Idempotent; returns the active auditor. Pure bookkeeping — nothing
    is patched, the instrumented sites simply start finding an auditor."""
    global _contract_auditor
    if _contract_auditor is None:
        _contract_auditor = _ContractAuditor()
    return _contract_auditor


def uninstall_contract_auditor() -> None:
    global _contract_auditor
    _contract_auditor = None


def contract_auditor() -> Optional[_ContractAuditor]:
    return _contract_auditor


def contract_report() -> dict:
    """The audit payload (empty dict when the mode is off) — the
    ``leak_report_by_owner()`` of the name-contract surface."""
    if _contract_auditor is None:
        return {}
    return _contract_auditor.report()


def contract_diff(report: dict, static_doc: dict) -> dict:
    """Diff a runtime ``contract_report()`` against a static contract
    table (the ``kftpu lint --contracts-json`` document). Returns the
    UNDECLARED exchanges — names the run actually used that the static
    extractor never saw. Empty lists == the static table is an honest
    superset of runtime behavior.

    Series match by exact name, histogram-suffix family, or a declared
    dynamic prefix (f-string heads the extractor could not expand);
    headers match case-insensitively."""
    series = static_doc.get("series", {})
    declared = set(series.get("produced", ())) \
        | set(series.get("consumed", ()))
    prefixes = tuple(series.get("produced_prefixes", ()))
    headers = static_doc.get("headers", {})
    declared_headers = {h.lower() for h in headers.get("set", ())} \
        | {h.lower() for h in headers.get("read", ())}

    def series_ok(name: str) -> bool:
        if name in declared or series_base(name) in declared:
            return True
        return bool(prefixes) and name.startswith(prefixes)

    out = {"undeclared_series": [], "undeclared_headers": []}
    for key in ("series_produced", "series_consumed"):
        for name in report.get(key, ()):
            if not series_ok(name):
                out["undeclared_series"].append(name)
    for key in ("headers_set", "headers_read"):
        for name in report.get(key, ()):
            if name.lower() not in declared_headers:
                out["undeclared_headers"].append(name)
    out["undeclared_series"] = sorted(set(out["undeclared_series"]))
    out["undeclared_headers"] = sorted(set(out["undeclared_headers"]))
    return out


# -- thread-lifecycle sanitizer ------------------------------------------------


class ThreadLeakError(AssertionError):
    """Tracked threads survived a quiescence point; each is named with
    its creation site and owner — the T803/T804 leak, caught live."""


_STDLIB_DIR = os.path.dirname(os.__file__)


def _is_app_file(fname: str) -> bool:
    """Application code: not stdlib, not an installed library, not a
    synthesized frame. Threads libraries start (executor workers, jax
    pools, socketserver handlers) are their business to reap."""
    return ("site-packages" not in fname
            and "dist-packages" not in fname
            and not fname.startswith(("<", _STDLIB_DIR)))


def _creator_site() -> tuple[str, bool]:
    """(``file:line``, is_app_code) of the nearest frame outside this
    module and the threading machinery — who constructed the thread."""
    frame = sys._getframe(1)
    for _ in range(32):
        if frame is None:
            break
        fname = frame.f_code.co_filename
        if fname != __file__ \
                and "threading" not in os.path.basename(fname):
            return (f"{os.path.basename(fname)}:{frame.f_lineno}",
                    _is_app_file(fname))
        frame = frame.f_back
    return "<unknown>", False


class _ThreadSanitizer:
    """State for the ``threads`` mode: the per-creating-thread owner
    label (``thread_owner`` scopes) and the tracked-thread view. There
    is no registry — ``threading.enumerate()`` already holds every live
    thread, and dead threads need no bookkeeping to forget."""

    def __init__(self):
        self._tls = threading.local()

    def current_owner(self) -> Optional[str]:
        return getattr(self._tls, "owner", None)

    @contextlib.contextmanager
    def owner_scope(self, owner: str):
        prev = getattr(self._tls, "owner", None)
        self._tls.owner = owner
        try:
            yield
        finally:
            self._tls.owner = prev

    @staticmethod
    def tracked() -> list:
        me = threading.current_thread()
        return [t for t in threading.enumerate()
                if t is not me and t.is_alive()
                and getattr(t, "_kftpu_site", None) is not None]

    def stamp(self, t) -> None:
        site, app = _creator_site()
        if not app:
            return              # library-internal thread: untracked
        target = getattr(t, "_target", None)
        owner_obj = getattr(target, "__self__", None) \
            if target is not None else None
        owner = self.current_owner()
        if owner is None and owner_obj is not None:
            owner = type(owner_obj).__name__
        if owner is None:
            owner = getattr(threading.current_thread(),
                            "_kftpu_owner", None)     # inherit
        if owner is None:
            owner = site.split(":")[0]
        t._kftpu_site = site
        t._kftpu_owner = owner
        t._kftpu_created = time.monotonic()
        if owner_obj is not None:
            try:
                t._kftpu_owner_ref = weakref.ref(owner_obj)
            except TypeError:
                t._kftpu_owner_ref = None
        else:
            t._kftpu_owner_ref = None


_thread_san: Optional[_ThreadSanitizer] = None
_thread_orig: Optional[type] = None


def install_thread_sanitizer() -> _ThreadSanitizer:
    """Patch ``threading.Thread`` so every thread created AFTER this call
    is stamped at construction. Idempotent; returns the active
    sanitizer. (``threading.Timer`` subclassed ``Thread`` at interpreter
    start, so Timers bypass the stamp — they carry their own interval
    bound.)"""
    global _thread_san, _thread_orig
    if _thread_san is not None:
        return _thread_san
    san = _ThreadSanitizer()
    orig = threading.Thread

    class _StampedThread(orig):        # type: ignore[valid-type, misc]
        def __init__(self, *args, **kwargs):
            # NOT super(): stdlib subclasses fixed at interpreter start
            # (threading.Timer) call the module-global ``Thread.__init__
            # (self)`` — their self is an ``orig`` instance, not ours.
            orig.__init__(self, *args, **kwargs)
            if _thread_san is not None and isinstance(self, _StampedThread):
                _thread_san.stamp(self)

    _StampedThread.__name__ = "Thread"
    _StampedThread.__qualname__ = "Thread"
    threading.Thread = _StampedThread      # type: ignore[misc]
    _thread_orig = orig
    _thread_san = san
    return san


def uninstall_thread_sanitizer() -> None:
    """Restore the real Thread class. Threads created while installed
    keep their stamps (harmless attributes on dead-soon objects)."""
    global _thread_san, _thread_orig
    if _thread_orig is not None:
        threading.Thread = _thread_orig    # type: ignore[misc]
        _thread_orig = None
    _thread_san = None


def thread_sanitizer() -> Optional[_ThreadSanitizer]:
    return _thread_san


def thread_owner(owner: str):
    """Context manager labelling every thread the CURRENT thread creates
    inside the scope — the refcount sanitizer's owner idiom applied to
    thread creation. No-op context when the mode is off."""
    if _thread_san is None:
        return contextlib.nullcontext()
    return _thread_san.owner_scope(owner)


def thread_report() -> list:
    """Live tracked threads: ``[{name, owner, site, daemon, age_s}]``.
    Empty when the sanitizer is not installed."""
    if _thread_san is None:
        return []
    now = time.monotonic()
    return [{"name": t.name,
             "owner": getattr(t, "_kftpu_owner", "<unknown>"),
             "site": getattr(t, "_kftpu_site", "<unknown>"),
             "daemon": t.daemon,
             "age_s": round(now - getattr(t, "_kftpu_created", now), 3)}
            for t in _ThreadSanitizer.tracked()]


def thread_leak_report_by_owner() -> dict:
    """``thread_report()`` grouped by owner — which component forgot to
    join what."""
    out: dict[str, list] = {}
    for entry in thread_report():
        out.setdefault(entry["owner"], []).append(entry)
    return out


def _quiescence_pool(owner, threads: Optional[Iterable]) -> list:
    me = threading.current_thread()
    pool = [t for t in (threads if threads is not None
                        else _ThreadSanitizer.tracked()) if t is not None]
    out = []
    for t in pool:
        if t is me or not t.is_alive():
            continue
        if owner is None:
            out.append(t)
        elif isinstance(owner, str):
            if getattr(t, "_kftpu_owner", None) == owner:
                out.append(t)
        else:
            ref = getattr(t, "_kftpu_owner_ref", None)
            if ref is not None and ref() is owner:
                out.append(t)
    return out


def assert_threads_quiescent(owner=None, *, grace_s: float = 5.0,
                             threads: Optional[Iterable] = None) -> None:
    """Raise ``ThreadLeakError`` if tracked threads are still alive after
    ``grace_s``. ``owner=None`` audits every tracked thread; a string
    matches the stamped owner label; any other object matches threads
    whose bound target method belongs to that instance (identity).
    ``threads=`` audits an explicit iterable instead of the tracked set
    (stamped or not). No-op when the sanitizer is not installed —
    stop paths call this unconditionally."""
    if _thread_san is None:
        return
    deadline = time.monotonic() + max(grace_s, 0.0)
    leaked = _quiescence_pool(owner, threads)
    while leaked:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        # Join rather than spin: the leaker exiting wakes us immediately.
        leaked[0].join(timeout=min(0.2, remaining))
        leaked = _quiescence_pool(owner, threads)
    if not leaked:
        return
    lines = [
        f"  '{t.name}' (owner={getattr(t, '_kftpu_owner', '<unstamped>')}, "
        f"created at {getattr(t, '_kftpu_site', '<unstamped>')}, "
        f"daemon={t.daemon})" for t in leaked]
    raise ThreadLeakError(
        f"{len(leaked)} thread(s) still alive after {grace_s:.1f}s "
        "quiescence grace — each names its creation site (the static "
        "T803/T804 rules model exactly this):\n" + "\n".join(lines))


def maybe_install() -> None:
    """Called from ``kubeflow_tpu/__init__`` so ``KFTPU_SANITIZE=
    lockorder`` / ``=recompile`` / ``=contract`` / ``=threads`` cover
    every lock the platform creates, every compile it dispatches, every
    name exchange it performs, and every thread it starts, whatever the
    entry point."""
    modes = sanitize_modes()
    if "lockorder" in modes:
        install_lockorder_watchdog()
    if "recompile" in modes:
        install_recompile_watchdog()
    if "contract" in modes:
        install_contract_auditor()
    if "threads" in modes:
        install_thread_sanitizer()
