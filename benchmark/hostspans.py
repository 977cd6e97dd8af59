"""The program's own spans in a profiler trace, and what is reduced from
them: which host phase an idle stretch of the device fell under, and how
much of a traced window a loop's thread spent on work of its own.

The program writes its phases into the profiler's trace as host
annotations (``kubeflow_tpu/obs/profiler.py::hot_span``). They land in the
plane ``/host:CPU``, one line per thread, every line named after the
process and not the thread, among the runtime's own events; the keyword
arguments are the event's stats. ``from_profile`` keeps the program's spans
and brings them to a plain form, so that a test can hand-build one:

    [[[name, start_s, duration_s, attrs], ...],    # one list per thread
     ...]

on the origin of the device events beside them (``benchmark/tracing.py``).
A thread is found by the spans on it (the scheduler's is the one that holds
``engine.decode_dispatch`` or ``engine.idle``), never by a name. A trace of
a program that has no such control holds no anchor annotation, and its
plain form is None: the readers then have nothing to read.
"""

from __future__ import annotations

import bisect
import re

from benchmark.tracing import measure, subtract, union

HOST_PLANE = "/host:CPU"
ANCHOR = "kftpu.trace_anchor"
# A span of the program: lower-case dotted words, each starting with a
# letter (the runtime's own events are ``PjitFunction(...)``, ``Foo::Bar``
# and the like; the CPU backend's ops, which run on the calling thread, end
# in a number: ``copy.24``), or a step annotation, which carries
# ``step_num``.
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
STEP_STAT = "step_num"

ENGINE_THREAD = ("engine.decode_dispatch", "engine.idle", "engine.admit")
ENGINE_BLOCKED = ("engine.fetch", "engine.idle")
TRAINER_THREAD = ("train.dispatch",)
TRAINER_BLOCKED = ("train.sync", "train.stage_wait")
UNTRACED = "host:untraced"


def _plain(value):
    return value if isinstance(value, (int, float, str, bool)) \
        else str(value)


def from_profile(data, origin_ns: float):
    """The program's spans of every host thread in plain form, or None
    where the trace holds no anchor (a program without the control).
    ``data`` is a ``jax.profiler.ProfileData``."""
    threads, anchored = [], False
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                step = not PROGRAM_SPAN.match(name)
                if step and not (name.isidentifier() and name.islower()):
                    continue
                attrs = {k: _plain(v) for k, v in ev.stats}
                if step and STEP_STAT not in attrs:
                    continue
                anchored = anchored or name == ANCHOR
                spans.append([name, (ev.start_ns - origin_ns) / 1e9,
                              ev.duration_ns / 1e9, attrs])
            if spans:
                spans.sort(key=lambda s: (s[1], -s[2]))
                threads.append(spans)
    return threads if anchored else None


def anchor(host_spans) -> dict | None:
    """The anchor's stats (``wall_ns``, ``mono_ns``) and its instant on the
    trace's timeline (``trace_s``): what lays wall-clock and monotonic
    stamps on the trace."""
    for spans in host_spans or []:
        for name, start, _, attrs in spans:
            if name == ANCHOR:
                return {**attrs, "trace_s": start}
    return None


def thread_with(host_spans, names) -> list | None:
    """The thread that holds a span named in ``names`` (the one with most
    of them, should two threads hold one)."""
    best, most = None, 0
    for spans in host_spans or []:
        n = sum(1 for s in spans if s[0] in names)
        if n > most:
            best, most = spans, n
    return best


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """One thread's timeline cut into (start, end, name) pieces, each named
    after the innermost span that covers it. Spans of one thread nest; a
    child's piece is taken out of its parent's."""
    out: list[tuple[float, float, str]] = []
    stack: list[list] = []          # [name, end, cursor]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur, _ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[2], start, top[0]))
            top[2] = max(top[2], start)
        stack.append([name, start + dur, start])
    close(float("inf"))
    return sorted(out)


def device_idle(trace: dict, device: int = 0) -> list[tuple[float, float]]:
    """The stretches between the first and the last op of one device in
    which no op ran."""
    if not trace["devices"]:
        return []
    busy = union((s, s + d) for _, s, d in trace["devices"][device]["ops"])
    return [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]


def idle_by_host_phase(trace: dict, thread, n: int = 10,
                       device: int = 0) -> list:
    """Idle time on one device by what the host's loop thread was doing:
    each idle stretch is split over the innermost span of ``thread`` that
    covers it, summed by name (``host:engine.admit``, ...), the rest as
    ``host:untraced``; largest first: [[name, seconds], ...]."""
    idle = device_idle(trace, device)       # sorted and disjoint
    starts = [a for a, _ in idle]
    total: dict[str, float] = {}
    covered = 0.0
    for s0, s1, name in innermost_segments(thread or []):
        if name == ANCHOR:
            continue
        part = 0.0
        i = max(bisect.bisect_right(starts, s0) - 1, 0)
        while i < len(idle) and idle[i][0] < s1:
            part += max(0.0, min(idle[i][1], s1) - max(idle[i][0], s0))
            i += 1
        if part > 0.0:
            total["host:" + name] = total.get("host:" + name, 0.0) + part
            covered += part
    rest = measure(idle) - covered
    if rest > 1e-9:
        total[UNTRACED] = rest
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def busy_share(thread, blocked_names) -> float:
    """Percent of one loop thread's traced window that it did not spend
    inside a span named in ``blocked_names`` (waiting for the device, for
    input, or for work): everything the host does itself, spanned or not.
    The window runs from the thread's first span to the end of its last.
    0.0 for a thread without a span."""
    spans = [s for s in thread or [] if s[0] != ANCHOR]
    if not spans:
        return 0.0
    t0 = min(s[1] for s in spans)
    t1 = max(s[1] + s[2] for s in spans)
    if t1 <= t0:
        return 0.0
    own = subtract([(t0, t1)], [(s[1], s[1] + s[2]) for s in spans
                                if s[0] in blocked_names])
    return 100.0 * measure(own) / (t1 - t0)


def loop_thread(host_spans):
    """The scheduler's or the trainer's thread, whichever the trace holds."""
    return thread_with(host_spans, ENGINE_THREAD) \
        or thread_with(host_spans, TRAINER_THREAD)
