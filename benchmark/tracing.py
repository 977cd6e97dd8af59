"""From a profiler trace to numbers: the benchmark's own reduction of the
``.xplane.pb`` that ``jax.profiler`` writes, read with nothing but JAX
(``jax.profiler.ProfileData``). The profiler itself is started and stopped
through the program's one control, ``kubeflow_tpu/obs/profiler.py``.

The trace is first brought to a small plain form (``load_xplane``), lists of
``[name, start_s, duration_s]`` per device: its XLA modules (one event per
execution of a jitted program) and its XLA ops (one per executed operation).
Every reduction below works on that form, so a test can hand-build one, and
a trimmed recording of a real one is kept with the tests. The program's own
host spans ride along under ``host_spans`` (``benchmark/hostspans.py`` has
their form and their reductions).

Rules:
- busy: the union of the op intervals of a device; a run's ``busy_s`` is the
  mean over its devices. Idle is the rest of the traced window.
- a module's device time: the duration of its event on the modules line.
- exposed collective time: the part of the union of collective ops'
  intervals that no other op's interval covers, per device.
"""

from __future__ import annotations

import glob
import os
import re
import time

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)


# -- recording and loading -----------------------------------------------------

def start(trace_dir: str) -> None:
    """Start the profiler through the program's one control
    (``kubeflow_tpu/obs/profiler.py``), with the Python tracer off: it
    stamps every Python call of every thread, which slows the host threads
    the run is measuring and makes the trace large. Device events, the
    runtime's own host events and the program's spans stay."""
    from kubeflow_tpu.obs import profiler

    profiler.start(trace_dir)


def abort() -> None:
    """Stop a trace that a failing run left open; nothing is read."""
    from kubeflow_tpu.obs import profiler

    profiler.stop()


def warm(trace_dir: str) -> None:
    """Start and stop the profiler once and throw that trace away: the
    first start of a process costs seconds, which then fall into no
    traced window (``--trace 2``)."""
    import shutil

    start(trace_dir)
    abort()
    shutil.rmtree(trace_dir, ignore_errors=True)


def stop(trace_dir: str, window_s: float) -> dict:
    """Stop the profiler and bring the newest trace to the plain form; a
    summary a person can read is left beside the trace directory."""
    import json

    abort()
    trace = load_newest(trace_dir, window_s)
    with open(os.path.join(os.path.dirname(trace_dir),
                           "trace_summary.json"), "w") as f:
        json.dump(summary(trace), f, indent=1)
    return trace


def record(trace_dir: str, seconds: float) -> dict:
    """Trace ``seconds`` of whatever the process is doing now; returns the
    plain form. The traced window is the host's clock around it."""
    start(trace_dir)
    t_on = time.monotonic()
    time.sleep(seconds)
    return stop(trace_dir, time.monotonic() - t_on)


def load_newest(trace_dir: str, window_s: float) -> dict:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_xplane(files[-1], window_s)


def load_xplane(path: str, window_s: float) -> dict:
    from jax.profiler import ProfileData

    from benchmark import hostspans

    data = ProfileData.from_file(path)
    devices, others = [], []
    origin = None
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            others.append(plane.name)
            continue
        dev = {"name": plane.name, "modules": [], "ops": [], "lines": {}}
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.duration_ns)
                      for ev in line.events]
            dev["lines"][line.name] = len(events)
            if line.name == MODULE_LINE:
                dev["modules"] = events
            elif line.name == OP_LINE:
                dev["ops"] = events
            for _, s, _ in events[:1]:
                origin = s if origin is None else min(origin, s)
        devices.append(dev)
    origin = origin or 0
    for dev in devices:
        for key in ("modules", "ops"):
            dev[key] = [[n, (s - origin) / 1e9, d / 1e9]
                        for n, s, d in dev[key]]
    devices.sort(key=lambda d: d["name"])
    return {"window_s": float(window_s), "devices": devices,
            "other_planes": others, "source": os.path.basename(path),
            "host_spans": hostspans.from_profile(data, origin)}


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint cover of ``intervals`` ((start, end) pairs)."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events]


# -- reductions ----------------------------------------------------------------

def busy_s(trace: dict) -> float:
    """Seconds in which an op ran, mean over the traced devices."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(measure(_spans(d["ops"])) for d in devs) / len(devs)


def traced_window_s(trace: dict) -> float:
    """The host-clock window, or the device events' own span where that is
    longer (the profiler lets events in until it has stopped)."""
    span = 0.0
    for d in trace["devices"]:
        ev = d["ops"] + d["modules"]
        if ev:
            span = max(span, max(s + dur for _, s, dur in ev)
                       - min(s for _, s, _ in ev))
    return max(trace["window_s"], span)


def module_events(trace: dict, pattern: str, device: int = 0) -> list:
    """[name, start_s, duration_s] of the module executions on one device
    whose name matches ``pattern`` (a regular expression, searched)."""
    if not trace["devices"]:
        return []
    rx = re.compile(pattern)
    return [e for e in trace["devices"][device]["modules"]
            if rx.search(e[0])]


def module_time_s(trace: dict, pattern: str, device: int = 0) -> float:
    return sum(d for _, _, d in module_events(trace, pattern, device))


def ops_within(trace: dict, start: float, end: float, pattern: str,
               device: int = 0) -> list:
    rx = re.compile(pattern)
    return [e for e in trace["devices"][device]["ops"]
            if start <= e[1] < end and rx.search(e[0])]


def exposed_collective_s(trace: dict) -> float:
    """Mean over devices of the time in collective ops during which no other
    op ran on that device; 0.0 for a trace that holds no collective."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        coll = [e for e in d["ops"] if COLLECTIVE.search(e[0])]
        rest = [e for e in d["ops"] if not COLLECTIVE.search(e[0])]
        total += sum(e - s for s, e in subtract(_spans(coll), _spans(rest)))
    return total / len(devs)


CONTAINER = re.compile(r"^(while|conditional|call)\b")


def short_name(op_name: str) -> str:
    """The trace names an op by its whole HLO instruction; its name is the
    part before `` = `` (``%copy.72 = bf16[...] copy(...)`` -> ``copy.72``)."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def _module_at(modules_sorted: list, starts: list, t: float) -> str:
    import bisect

    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules_sorted[i][1] + modules_sorted[i][2]:
        return re.sub(r"\(\d+\)$", "", modules_sorted[i][0])
    return "?"


def top_ops(trace: dict, n: int = 10, device: int = 0) -> list:
    """The ops that took most device time on one device:
    [["<program>/<op>", seconds], ...]. An op is named by its HLO name and
    the program (XLA module) it ran in; loops and calls, whose events span
    the ops inside them, are left out so that nothing is counted twice."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][device]
    mods = sorted(dev["modules"], key=lambda e: e[1])
    starts = [e[1] for e in mods]
    total: dict[str, float] = {}
    for name, start, dur in dev["ops"]:
        op = short_name(name)
        if CONTAINER.match(op):
            continue
        key = f"{_module_at(mods, starts, start)}/{op}"
        total[key] = total.get(key, 0.0) + dur
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10, device: int = 0) -> list:
    """Idle time on one device, by the program the device was waiting for
    (the module that ran next), largest first: [[name, seconds], ...]. The
    host's own spans are not on the profiler's clock yet, so what the HOST
    was doing in a gap is not named (PERF.md, Open questions)."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][device]
    busy = union(_spans(dev["ops"]))
    mods = sorted(dev["modules"], key=lambda e: e[1])
    total: dict[str, float] = {}
    j = 0
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        while j < len(mods) and mods[j][1] + mods[j][2] <= s1:
            j += 1
        nxt = mods[j][0] if j < len(mods) else "?"
        key = "before " + re.sub(r"\(\d+\)$", "", nxt)
        total[key] = total.get(key, 0.0) + (s1 - e0)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summary(trace: dict, n: int = 40) -> dict:
    """What a person reads first: per device the lines and their sizes, the
    distinct module names, and the heaviest ops."""
    out = {"window_s": trace["window_s"], "busy_s": busy_s(trace),
           "other_planes": trace["other_planes"], "devices": []}
    for i, d in enumerate(trace["devices"]):
        mods: dict[str, list] = {}
        for name, _, dur in d["modules"]:
            key = re.sub(r"\(\d+\)$", "", name)
            m = mods.setdefault(key, [0, 0.0])
            m[0] += 1
            m[1] += dur
        out["devices"].append({
            "name": d["name"], "lines": d["lines"], "modules": mods,
            "top_ops": top_ops(trace, n, i)})
    return out

