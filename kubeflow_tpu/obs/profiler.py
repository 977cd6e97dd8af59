"""The one trace control of the process that holds the chip.

Every ``jax.profiler`` trace in this process starts and stops here
(``start`` / ``stop`` / ``active``), and the hot loops write their phases
into that trace through ``hot_span``: host spans in the same
``.xplane.pb`` as the device's ops, on the profiler's clock, on the thread
that did the work. Callers: ``Trainer`` (``profile_start_step`` and
``request_profile``), the model server's ``/debug/profile`` endpoint, and
the benchmark's ``--trace`` modes. Nothing else in the repo calls
``jax.profiler.start_trace`` / ``stop_trace``.

Who pays what:

- off (``active()`` false): ``hot_span`` reads one module flag and returns
  one shared no-op object; no span is allocated and no clock is read (a
  call that passes attributes still builds its keyword dict).
- on: a span is a ``jax.profiler.TraceAnnotation`` (one TraceMe, a
  microsecond or two); the profiler itself costs what it costs
  (PERF.md, Findings, PR 25).
- always (``PhaseClock``, the engine scheduler's): a phase boundary is one
  clock read and one add into the phase's running sum, capture or none, and
  the ``hot_span`` of the same name between the same two boundaries: a
  microsecond a phase, no object allocated while no capture is active
  (PERF.md, Findings, PR 37).

- a start (``ENGINE_START_PHASES`` / ``TRAIN_START_PHASES``): the engine's
  constructor and the trainer's build, resume and first step go through a
  ``PhaseClock`` of their own, so what a start cost is always-on sums
  (``start_<phase>_sum_s`` of ``counters()``, constants once the start is
  over) and, where a capture is active while an engine or a trainer is
  built, ``engine.start.*`` / ``train.start.*`` spans: about ten boundaries
  a process, none inside a loop.

The spans of one thread nest, so the innermost span that covers an instant
says what that thread was doing; keyword arguments become the event's
stats and tie spans together (``round=`` on a decode dispatch and on the
fetch that consumed it, ``step=`` in the trainer; ``slots=`` and ``rows=``
on ``engine.sync_state``: what the sync sent; ``tokens=`` and ``streams=``
on ``engine.emit``: what the round handed on).

A span exists only inside a capture. What a loop's time went to over ANY
stretch is ``PhaseClock``'s: the scheduler's eleven phases as running sums
of EXCLUSIVE seconds (a phase's own time, its children's taken out: the
rule ``benchmark/hostspans.py::innermost_segments`` cuts a capture's spans
by, so a window's sums and a tail's segments mean the same thing), and the
loop's time under no phase beside them. ``LLMEngine.counters()`` carries
them as ``sched_<phase>_sum_s`` and ``sched_other_sum_s``.

On ``start`` one anchor annotation (``ANCHOR``) carries ``time.time_ns()``
and ``time.monotonic_ns()`` of the instant it was written: a reader lays
``Tracer``'s wall-clock request spans and ``EngineMetrics``' monotonic
stamps on the trace's timeline through it (the profiler's own origin is
the start of the session on the CPU, and whatever the runtime chose on
the chip).

This module imports nothing but the standard library; JAX is imported
inside ``start``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any

ANCHOR = "kftpu.trace_anchor"

# Span names, in one place (README.md "Observability" lists them).
ENGINE_REAP = "engine.reap"
ENGINE_ADMIT = "engine.admit"
ENGINE_PREFILL_DISPATCH = "engine.prefill_dispatch"
ENGINE_SAMPLE_FIRST = "engine.sample_first"
ENGINE_KVTIER_TICK = "engine.kvtier_tick"
ENGINE_ENSURE_PAGES = "engine.ensure_pages"
ENGINE_SYNC_STATE = "engine.sync_state"
ENGINE_DECODE_DISPATCH = "engine.decode_dispatch"
ENGINE_FETCH = "engine.fetch"            # every blocking device_get
ENGINE_EMIT = "engine.emit"
ENGINE_IDLE = "engine.idle"
ENGINE_PHASES = (
    ENGINE_REAP, ENGINE_ADMIT, ENGINE_PREFILL_DISPATCH, ENGINE_SAMPLE_FIRST,
    ENGINE_KVTIER_TICK, ENGINE_ENSURE_PAGES, ENGINE_SYNC_STATE,
    ENGINE_DECODE_DISPATCH, ENGINE_FETCH, ENGINE_EMIT, ENGINE_IDLE)
# The constructor's phases, on a PhaseClock of their own (``begin`` at its
# first line, ``end`` at its last): the load path, the pool and the decode
# state, the relaid weights, every program the constructor runs once.
ENGINE_START_PLACE = "engine.start.place"
ENGINE_START_POOL = "engine.start.pool"
ENGINE_START_RELAY = "engine.start.relay"
ENGINE_START_WARM = "engine.start.warm"    # one span a program: ``program=``
ENGINE_START_PHASES = (ENGINE_START_PLACE, ENGINE_START_POOL,
                       ENGINE_START_RELAY, ENGINE_START_WARM)
TRAIN_STEP = "train"
TRAIN_STAGE_WAIT = "train.stage_wait"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
TRAIN_LOG = "train.log"
TRAIN_CHECKPOINT = "train.checkpoint"
# A trainer's start: ``Trainer.__init__``, ``try_resume``, and the run's
# first step alone, from its lowering to its outputs ready (compile or
# load, first execution; a one-off ``train.sync`` whatever ``log_every``).
TRAIN_START_BUILD = "train.start.build"
TRAIN_START_RESUME = "train.start.resume"
TRAIN_START_FIRST_STEP = "train.start.first_step"
TRAIN_START_PHASES = (TRAIN_START_BUILD, TRAIN_START_RESUME,
                      TRAIN_START_FIRST_STEP)


class _NoSpan:
    """What ``hot_span`` hands out while no trace is being taken."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set_metadata(self, **attrs: Any) -> None:
        """What a ``TraceAnnotation`` takes once its phase knows it."""


NO_SPAN = _NoSpan()

_lock = threading.Lock()
_active = False                 # guarded_by: _lock (read lock-free)
_trace_dir = ""                 # guarded_by: _lock
_annotation: Any = None         # jax.profiler.TraceAnnotation once started
_step_annotation: Any = None    # jax.profiler.StepTraceAnnotation


def active() -> bool:
    return _active


def start(trace_dir: str, *, python_tracer: bool = False) -> None:
    """Start a profiler trace into ``trace_dir``. The Python tracer stamps
    every Python call of every thread, which slows the host threads and
    makes the trace large, so it is off unless asked for; device events,
    the runtime's own host events and ``hot_span``'s stay. A second
    ``start`` while a trace is being taken raises."""
    global _active, _trace_dir, _annotation, _step_annotation
    import jax

    with _lock:
        if _active:
            raise RuntimeError(
                f"a profiler trace into {_trace_dir!r} is already active")
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        _annotation = jax.profiler.TraceAnnotation
        _step_annotation = jax.profiler.StepTraceAnnotation
        _trace_dir = trace_dir
        _active = True
    with _annotation(ANCHOR, wall_ns=time.time_ns(),
                     mono_ns=time.monotonic_ns()):
        pass


def stop() -> str:
    """Stop the trace and return its directory; without a ``start`` it does
    nothing and returns ``""``."""
    global _active, _trace_dir
    with _lock:
        if not _active:
            return ""
        import jax

        _active = False
        trace_dir, _trace_dir = _trace_dir, ""
        jax.profiler.stop_trace()
    return trace_dir


def hot_span(name: str, **attrs: Any):
    """A context manager around one phase of a hot loop (see the module's
    docstring for what it costs on and off)."""
    if not _active:
        return NO_SPAN
    return _annotation(name, **attrs)


def hot_step(name: str, step: int):
    """``hot_span`` for one iteration of a training loop: a
    ``StepTraceAnnotation``, which the profiler's tools group by step."""
    if not _active:
        return NO_SPAN
    return _step_annotation(name, step_num=step)


class _Phase:
    """One phase of a ``PhaseClock``, entered and left with ``with``. One
    object a phase for the clock's lifetime: what an entry has to remember
    lies on the clock's stacks, so a phase may nest in itself."""

    __slots__ = ("_clock", "_index", "_name")

    def __init__(self, clock: "PhaseClock", index: int, name: str):
        self._clock, self._index, self._name = clock, index, name

    def __enter__(self):
        c = self._clock
        c.tick()
        c._enclosing.append(c._current)
        c._current = self._index
        attrs, c._attrs = c._attrs, None
        span = hot_span(self._name, **attrs) if attrs \
            else hot_span(self._name)
        c._spans.append(span)
        return span.__enter__()

    def __exit__(self, *exc: Any) -> bool:
        c = self._clock
        c._spans.pop().__exit__(*exc)
        c.tick()
        c._current = c._enclosing.pop()
        return False


class PhaseClock:
    """One loop thread's time by phase, always on: one boundary, two sinks.

    ``with clock.phase(name, attrs):`` adds the seconds between its two
    boundaries to ``sums[name]`` LESS what phases entered inside it took
    (each boundary is one clock read: the time since the last boundary goes
    to the innermost phase open, or to ``OTHER`` under none), and opens
    ``hot_span(name, **attrs)`` between the same boundaries, which is a span
    while a capture is active and nothing otherwise. ``attrs`` is a dict or
    anything false: a caller that has attributes builds them only under
    ``active()``. ``with`` hands back the span (``NO_SPAN`` off capture),
    for ``set_metadata`` of what the phase learns by its end. ``phase`` is
    for a ``with`` at once: it hands out ONE object a phase, and the
    attributes wait on the clock for the entry that follows.

    ``begin`` / ``end`` bracket the loop: between them every second lands
    in exactly one sum, so the sums of a stretch add up to its wall time.
    Outside them a phase still counts, and the time between phases does
    not. Confined to the loop's thread; another thread reads ``sums``
    (plain floats: a snapshot lacks at most the phase under way)."""

    OTHER = "other"

    def __init__(self, names, clock=time.monotonic):
        self.names = tuple(names) + (self.OTHER,)
        self.sums = [0.0] * len(self.names)
        self._phases = {name: _Phase(self, i, name)
                        for i, name in enumerate(names)}
        self._read = clock
        self._running = False
        self._mark = 0.0
        self._current = len(names)          # OTHER
        self._enclosing: list[int] = []     # the phases open around it
        self._spans: list[Any] = []         # their spans, innermost last
        self._attrs: Any = None

    def phase(self, name: str, attrs: Any = None) -> _Phase:
        self._attrs = attrs
        return self._phases[name]

    def begin(self) -> bool:
        """Start attributing every second; False where the loop already
        runs (``end`` is then its starter's to call)."""
        if self._running:
            return False
        self._mark = self._read()
        self._running = True
        return True

    def end(self) -> None:
        self.tick()
        self._running = False

    def tick(self) -> float:
        """A boundary that changes no phase: the clock, read once, with the
        time up to it attributed."""
        now = self._read()
        if self._running or self._enclosing:
            self.sums[self._current] += now - self._mark
        self._mark = now
        return now

    def total(self, name: str) -> float:
        return self.sums[self._phases[name]._index]

    def snapshot(self) -> dict[str, float]:
        return dict(zip(self.names, self.sums))
