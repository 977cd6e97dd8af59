"""Platform-wide request tracing: one trace id from router to decode step.

Dapper-style spans over the whole platform (SURVEY.md §5: the reference's
observability stops at controller-runtime metrics and never sees the data
plane). One process-wide ``Tracer`` holds a bounded ring of recent traces;
every layer annotates it:

- the serving router opens (or joins) a trace per proxied request and
  propagates it downstream in the ``X-Kftpu-Trace`` header;
- the model server joins the header and spans the protocol request plus the
  detokenize hop;
- the engine scheduler spans each request's queued → prefill → decode
  lifecycle (decode rounds land as span events — a span per round would
  cost more than the dispatch it measures);
- controllers span each reconcile, the pipeline executor spans each task,
  the trainer spans each logged step window.

Surfaces: ``/debug/traces`` (JSON, ``?slowest=N``) on the model server, the
platform API server, and the router (``/-/router/debug/traces``); a
slow-request log (root spans longer than ``slow_threshold_s`` log their
span tree at WARNING); Chrome ``about:tracing`` / Perfetto JSON export; and
``python -m kubeflow_tpu.cli trace <file>`` to pretty-print a dump.

Cost model: a span is a dict-sized Python object and a couple of lock-free
contextvar ops (cross-thread spans take one lock on end); a traced request
creates ~6 spans total and up to ``MAX_EVENTS`` ``decode_round`` events.
Engine-side instrumentation only runs for requests that carry a trace
parent — but the tracer is ON by default and the model server roots a span
for every POST, so every request through ``ModelServer`` is a traced
request and pays all of that; only a direct ``engine.submit`` without a
``trace_parent``, or ``get_tracer().enabled = False``, pays nothing. What
it costs a benchmark request was measured once (PERF.md, Findings, PR 25).

These spans are on the wall clock (``time.time()``) and are an operator's
tool. The hot loops' phases on the PROFILER's clock, beside the device's
ops, are ``obs/profiler.py``'s (``hot_span``); its anchor annotation lays
the two on one timeline.

Cross-thread propagation: contextvars do not flow into the engine scheduler
thread, so the server attaches the request span's ``SpanContext`` to the
engine-side ``Request`` and the scheduler opens children against that
explicit parent.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Iterator, Optional

# Trace-context propagation header (``<trace_id>-<parent_span_id>``),
# re-exported from the one module that owns every X-Kftpu-* name.
from kubeflow_tpu.core.headers import TRACE_HEADER  # noqa: F401

#: Span-event cap: decode annotates one event per round, and a 4k-token
#: generation must not grow an unbounded list.
MAX_EVENTS = 32

logger = logging.getLogger("kubeflow_tpu.obs")
slow_logger = logging.getLogger("kubeflow_tpu.obs.slow")


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


@dataclass(frozen=True)
class SpanContext:
    """The propagatable identity of a span (what rides in the header)."""

    trace_id: str
    span_id: str

    def header_value(self) -> str:
        return f"{self.trace_id}-{self.span_id}"


def parse_trace_header(value: Optional[str]) -> Optional[SpanContext]:
    """``<trace_id>-<span_id>`` → SpanContext, or None on absent/garbage
    (a malformed header must start a fresh trace, never 500 a request)."""
    if not value:
        return None
    trace_id, sep, span_id = value.strip().partition("-")
    if not sep or not trace_id or not span_id:
        return None
    if not all(c in "0123456789abcdef" for c in trace_id + span_id):
        return None
    return SpanContext(trace_id=trace_id, span_id=span_id)


class Span:
    """One timed operation. Created via ``Tracer.span``/``start_span``;
    mutation (attrs/events) is single-writer by convention — the layer that
    opened the span owns it until ``end()``."""

    __slots__ = ("_tracer", "trace_id", "span_id", "parent_id", "name",
                 "start", "end_time", "attrs", "events", "status")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: Optional[str], attrs: dict,
                 start: Optional[float] = None):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.name = name
        self.start = time.time() if start is None else start
        self.end_time: Optional[float] = None
        self.attrs = attrs
        self.events: list[dict] = []
        self.status = "ok"

    @property
    def context(self) -> SpanContext:
        return SpanContext(trace_id=self.trace_id, span_id=self.span_id)

    @property
    def duration(self) -> Optional[float]:
        if self.end_time is None:
            return None
        return self.end_time - self.start

    def set_attrs(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, **attrs: Any) -> None:
        if len(self.events) >= MAX_EVENTS:
            return
        self.events.append({"name": name, "ts": time.time(), **attrs})

    def end(self, status: Optional[str] = None) -> None:
        """Idempotent close; the first call wins (a request failing twice —
        e.g. reap then caller timeout — keeps the first verdict)."""
        if self.end_time is not None:
            return
        if status is not None:
            self.status = status
        self.end_time = time.time()
        self._tracer._on_end(self)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "start": self.start, "end": self.end_time,
            "duration_ms": (None if self.duration is None
                            else self.duration * 1e3),
            "status": self.status, "attrs": dict(self.attrs),
            "events": list(self.events),
        }


class _NoopSpan:
    """Returned while tracing is disabled: absorbs the API at near-zero
    cost and never reaches the ring buffer."""

    __slots__ = ()
    trace_id = span_id = ""
    parent_id = None
    status = "ok"
    context = None

    def set_attrs(self, **attrs: Any) -> "_NoopSpan":
        return self

    def add_event(self, name: str, **attrs: Any) -> None:
        pass

    def end(self, status: Optional[str] = None) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Thread-safe span tracer with an in-memory ring of recent traces.

    ``span()`` is the contextvar path (nesting within a thread is
    automatic); ``start_span(parent=...)`` is the cross-thread path (the
    engine scheduler annotating a request submitted from a handler
    thread). Completed spans land in a per-trace record; the ring holds
    the ``max_traces`` most recently *started* traces and evicts oldest.
    """

    def __init__(self, max_traces: int = 256,
                 slow_threshold_s: Optional[float] = 5.0):
        self.enabled = True
        self.slow_threshold_s = slow_threshold_s
        self._max_traces = max_traces
        self._lock = threading.Lock()
        # trace_id -> {"spans": [dict], "root": Optional[dict]}
        self._traces: "OrderedDict[str, dict]" = OrderedDict()
        self._open = 0
        self._current: contextvars.ContextVar[Optional[Span]] = \
            contextvars.ContextVar("kftpu_current_span", default=None)

    # -- span creation ---------------------------------------------------------

    def start_span(self, name: str,
                   parent: Optional[SpanContext | Span] = None,
                   start: Optional[float] = None, **attrs: Any):
        """Open a span WITHOUT touching the contextvar — the cross-thread
        primitive. ``parent`` may be a Span, a SpanContext (joined from a
        header or another thread), or None for a new root."""
        if not self.enabled:
            return NOOP_SPAN
        if isinstance(parent, _NoopSpan):
            parent = None
        if parent is None:
            trace_id, parent_id = _new_id(16), None
        else:
            trace_id, parent_id = parent.trace_id, parent.span_id
        span = Span(self, name, trace_id, parent_id, attrs, start=start)
        with self._lock:
            self._open += 1
            rec = self._traces.get(trace_id)
            if rec is None:
                self._traces[trace_id] = {"spans": [], "root": None}
                while len(self._traces) > self._max_traces:
                    self._traces.popitem(last=False)
        return span

    @contextlib.contextmanager
    def span(self, name: str,
             parent: Optional[SpanContext | Span] = None,
             **attrs: Any) -> Iterator[Span]:
        """Contextvar-propagated span: children opened inside the block
        (same thread/context) nest automatically. An escaping exception
        closes the span with ``error`` status and its type attached."""
        sp = self.start_span(name, parent=parent or self._current.get(),
                             **attrs)
        token = self._current.set(sp if isinstance(sp, Span) else None)
        try:
            yield sp
        except BaseException as exc:
            sp.set_attrs(error=f"{type(exc).__name__}: {exc}")
            sp.end("error")
            raise
        finally:
            self._current.reset(token)
            sp.end()

    def current(self) -> Optional[Span]:
        """The innermost open contextvar span on this thread, or None."""
        return self._current.get()

    # -- propagation -----------------------------------------------------------

    def inject(self, span: Optional[Span]) -> Optional[str]:
        """Header value carrying ``span``'s context (None when untraced)."""
        if span is None or isinstance(span, _NoopSpan):
            return None
        return span.context.header_value()

    def extract(self, header_value: Optional[str]) -> Optional[SpanContext]:
        return parse_trace_header(header_value)

    # -- completion / ring buffer ----------------------------------------------

    def _on_end(self, span: Span) -> None:
        d = span.to_dict()
        with self._lock:
            self._open -= 1
            rec = self._traces.get(span.trace_id)
            if rec is not None:        # may have been evicted while open
                rec["spans"].append(d)
                if span.parent_id is None:
                    rec["root"] = d
        if (span.parent_id is None and self.slow_threshold_s is not None
                and span.duration is not None
                and span.duration > self.slow_threshold_s):
            tree = self._tree_locked_free(span.trace_id, d)
            slow_logger.warning(
                "slow request: trace %s root %s took %.1f ms\n%s",
                span.trace_id, span.name, span.duration * 1e3, tree)

    def _tree_locked_free(self, trace_id: str, root: dict) -> str:
        with self._lock:
            rec = self._traces.get(trace_id)
            spans = list(rec["spans"]) if rec else [root]
        return format_trace_tree(spans)

    def open_spans(self) -> int:
        """Started-but-not-ended spans. The quiescence invariant the
        lifecycle tests assert: an idle stack holds zero open spans."""
        with self._lock:
            return self._open

    def reset(self) -> None:
        """Drop every recorded trace and zero the open-span count (test
        isolation between cases sharing the process-wide tracer)."""
        with self._lock:
            self._traces.clear()
            self._open = 0

    # -- read surfaces ---------------------------------------------------------

    def traces(self, slowest: Optional[int] = None,
               limit: int = 64) -> list[dict]:
        """Recent traces, newest first (or the N slowest by root duration
        when ``slowest`` is given). Each entry: trace_id, root name/status/
        duration, and the full span list."""
        with self._lock:
            items = [
                {"trace_id": tid,
                 "root": rec["root"],
                 "spans": list(rec["spans"])}
                for tid, rec in self._traces.items()
            ]
        items.reverse()
        if slowest is not None:
            items = [t for t in items if t["root"] is not None]
            items.sort(key=lambda t: t["root"]["duration_ms"] or 0.0,
                       reverse=True)
            items = items[:max(slowest, 0)]
        else:
            items = items[:limit]
        return items

    def trace(self, trace_id: str) -> Optional[dict]:
        with self._lock:
            rec = self._traces.get(trace_id)
            if rec is None:
                return None
            return {"trace_id": trace_id, "root": rec["root"],
                    "spans": list(rec["spans"])}

    def export_chrome(self, trace_id: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (``about:tracing`` / Perfetto): complete
        "X" events, microsecond timestamps, one pid per process and the
        span id folded into tid so sibling spans stack visibly."""
        selected = ([self.trace(trace_id)] if trace_id is not None
                    else self.traces())
        events = []
        for t in selected:
            if not t:
                continue
            for s in t["spans"]:
                if s["end"] is None:
                    continue
                events.append({
                    "name": s["name"], "cat": "kftpu", "ph": "X",
                    "ts": s["start"] * 1e6,
                    "dur": (s["end"] - s["start"]) * 1e6,
                    "pid": os.getpid(),
                    "tid": int(s["span_id"][:6], 16),
                    "args": {**s["attrs"], "trace_id": s["trace_id"],
                             "status": s["status"]},
                })
                # Span events as thread-scoped instants on the same lane
                # (e.g. per-round "decode_round" markers with their
                # host_gap_ms) — Perfetto shows them as ticks inside the
                # span's slice.
                for ev in s.get("events", []):
                    events.append({
                        "name": ev["name"], "cat": "kftpu", "ph": "i",
                        "ts": ev["ts"] * 1e6, "s": "t",
                        "pid": os.getpid(),
                        "tid": int(s["span_id"][:6], 16),
                        "args": {k: v for k, v in ev.items()
                                 if k not in ("name", "ts")},
                    })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


#: The engine's per-request lifecycle phases, in span-name order
#: (``engine.queued`` → ``engine.prefill`` [→ ``engine.handoff``] →
#: ``engine.decode``). ``handoff`` appears only on disaggregated
#: requests: the prefill model server opens it around KV export + POST
#: + ack, and the adopting engine's queued/decode spans continue the
#: SAME trace on the decode side. ``adapter_load`` appears when an
#: admission had to hot-load its LoRA adapter into the packed buffers
#: (serve/lora.py) — the phase a multi-tenant churn regression shows
#: up under.
ENGINE_PHASES = ("queued", "adapter_load", "kv_migrate", "prefill",
                 "handoff", "decode")


def phase_durations(spans: list[dict]) -> dict:
    """Total engine time per lifecycle phase in a span list, in ms:
    ``{"queued_ms": ..., "prefill_ms": ..., "decode_ms": ...}``.

    Sums every closed ``engine.<phase>`` span — a preempted request
    contributes two queued (and prefill) spans, and the sum is the real
    time it spent in that phase. Phases with no closed span are absent;
    a trace with no engine spans returns {}. This is the per-request
    breakdown the serving loadgen's attribution reports aggregate, and
    the rollup ``/debug/traces`` and ``kftpu trace`` print per trace."""
    out: dict = {}
    for s in spans:
        name = s.get("name", "")
        if not name.startswith("engine."):
            continue
        phase = name.split(".", 1)[1]
        if phase not in ENGINE_PHASES or s.get("duration_ms") is None:
            continue
        key = f"{phase}_ms"
        out[key] = round(out.get(key, 0.0) + s["duration_ms"], 3)
    return out


def debug_traces_payload(path: str,
                         tracer: Optional[Tracer] = None) -> dict:
    """The shared ``/debug/traces`` response body: recent traces as JSON,
    ``?slowest=N`` for the N slowest by root duration, ``?chrome=1`` for a
    Chrome trace-event export. Every HTTP surface (model server, router,
    platform API server) serves this one payload. Traces touching the
    engine carry a ``phases`` rollup (queued/prefill/decode ms) so the
    slowest-request view says which phase ate the time without reading
    the span tree."""
    from urllib.parse import parse_qs, urlparse

    t = tracer or get_tracer()
    q = parse_qs(urlparse(path).query)
    if q.get("chrome", ["0"])[0] not in ("0", "", "false"):
        return t.export_chrome()
    slowest_raw = q.get("slowest", [None])[0]
    try:
        slowest = int(slowest_raw) if slowest_raw is not None else None
    except ValueError:
        slowest = None
    traces = t.traces(slowest=slowest)
    for tr in traces:
        phases = phase_durations(tr["spans"])
        if phases:
            tr["phases"] = phases
    return {"traces": traces}


def format_trace_tree(spans: list[dict]) -> str:
    """Render a span list as an indented tree with durations — the shape
    the slow-request log and the CLI dump both print."""
    by_parent: dict[Optional[str], list[dict]] = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        # Orphans (parent ended after eviction, or lives in another
        # process) print at top level rather than vanish.
        parent = s["parent_id"] if s["parent_id"] in ids else None
        by_parent.setdefault(parent, []).append(s)
    for children in by_parent.values():
        children.sort(key=lambda s: s["start"])
    lines: list[str] = []

    def walk(parent: Optional[str], depth: int) -> None:
        for s in by_parent.get(parent, []):
            dur = ("%.1fms" % s["duration_ms"]
                   if s.get("duration_ms") is not None else "open")
            mark = "" if s["status"] == "ok" else f" [{s['status']}]"
            attrs = " ".join(f"{k}={v}" for k, v in sorted(s["attrs"].items())
                             if k != "error")
            lines.append("  " * depth
                         + f"{s['name']} {dur}{mark}"
                         + (f" ({attrs})" if attrs else ""))
            # Span events (e.g. per-round decode_round markers with
            # host_gap_ms) print as bullet children so `kftpu trace` shows
            # the hot-loop health without a Perfetto round-trip.
            for ev in s.get("events", []):
                ev_attrs = " ".join(
                    f"{k}={v}" for k, v in sorted(ev.items())
                    if k not in ("name", "ts"))
                lines.append("  " * (depth + 1)
                             + f"· {ev['name']}"
                             + (f" ({ev_attrs})" if ev_attrs else ""))
            walk(s["span_id"], depth + 1)

    walk(None, 0)
    return "\n".join(lines)


def format_dump(doc: dict) -> str:
    """Pretty-print a trace dump file: either a ``/debug/traces`` JSON
    body ({"traces": [...]}) or a Chrome export ({"traceEvents": [...]}).
    Flight-recorder dumps (obs/fleet.py) are ``{"traces": [...]}``
    documents with a ``flight_recorder`` sidecar — they render like any
    trace dump, prefixed with the snapshot's reason/window header."""
    if "traces" in doc:
        out = []
        fr = doc.get("flight_recorder")
        if fr:
            out.append(
                f"flight recorder: reason={fr.get('reason')} "
                f"window={fr.get('window_s')}s "
                f"history_series={len(fr.get('history') or [])} "
                f"written_unix={fr.get('written_unix')}")
        for t in doc["traces"]:
            root = t.get("root") or {}
            dur = root.get("duration_ms")
            head = f"trace {t['trace_id']}"
            if dur is not None:
                head += f" ({dur:.1f} ms, {root.get('name')})"
            # Engine-phase rollup (from the payload when present, else
            # recomputed — old dump files still get the line).
            phases = t.get("phases") or phase_durations(t.get("spans", []))
            if phases:
                head += "  [" + " ".join(
                    f"{p}={phases[f'{p}_ms']:.1f}ms" for p in ENGINE_PHASES
                    if f"{p}_ms" in phases) + "]"
            out.append(head)
            out.append(format_trace_tree(t["spans"]))
        return "\n".join(out)
    if "traceEvents" in doc:
        spans = []
        for ev in doc["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            args = ev.get("args", {})
            spans.append({
                "span_id": format(ev.get("tid", 0), "x"),
                "parent_id": None,
                "name": ev.get("name", "?"),
                "start": ev.get("ts", 0) / 1e6,
                "duration_ms": ev.get("dur", 0) / 1e3,
                "status": args.get("status", "ok"),
                "attrs": {k: v for k, v in args.items()
                          if k not in ("status",)},
            })
        by_trace: dict[str, list[dict]] = {}
        for s in spans:
            by_trace.setdefault(s["attrs"].get("trace_id", "?"),
                                []).append(s)
        out = []
        for tid, ss in by_trace.items():
            out.append(f"trace {tid}")
            out.append(format_trace_tree(ss))
        return "\n".join(out)
    raise ValueError("not a trace dump: expected 'traces' or 'traceEvents'")


def load_dump(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


#: The process-wide tracer every layer shares (one trace id across
#: router → server → engine requires one tracer instance per process).
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER
