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

The spans of one thread nest, so the innermost span that covers an instant
says what that thread was doing; keyword arguments become the event's
stats and tie spans together (``round=`` on a decode dispatch and on the
fetch that consumed it, ``step=`` in the trainer).

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
TRAIN_STEP = "train"
TRAIN_STAGE_WAIT = "train.stage_wait"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
TRAIN_LOG = "train.log"
TRAIN_CHECKPOINT = "train.checkpoint"


class _NoSpan:
    """What ``hot_span`` hands out while no trace is being taken."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


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
