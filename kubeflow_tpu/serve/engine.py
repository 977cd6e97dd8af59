"""Continuous-batching LLM decode engine — the TPU-native counterpart of the
reference's vLLM-backed HuggingFace runtime ((U) kserve
python/huggingfaceserver; SURVEY.md §3.2 "engine step loop").

Design, driven by XLA's compilation model rather than CUDA streams:

- **Recompile-free shapes.** A fixed set of compiled programs serves all
  traffic: the decode step at a fixed slot count [B, 1] and the chunk
  programs (serve/chunk_programs.py). Admission changes data (slot contents,
  page-table rows), never shapes — XLA traces once.
- **One KV cache: the page pool** (serve/paged.py). [L, P, page, ...] per
  plane with a [B, mpp] page table and per-slot lengths. A slot is the
  unit of admission (continuous batching: new sequences join between
  decode steps, finished ones free their slot and pages immediately);
  pages are the unit of memory, shared between requests by the prefix
  index. Buffers are donated so the pool updates in place in HBM.
- **Every prompt prefills in chunks** (``chunked_prefill_tokens``), decode
  rounds interleaving between them; a chunk's K/V scatters per token into
  the pages its table row names.
- **Scheduler in plain Python** between device steps: reap → admit →
  prefill → decode → emit. The hot loop holds no Python per-token state
  beyond the slot table; everything tensor-shaped lives on device.
- **Device-resident decode state + pipelined dispatch** (the hot-loop
  host-overhead elimination): the per-slot scheduler arrays
  (tokens/lengths/live/sampling params/budgets) and the paged page table
  are persistent device arrays (serve/device_state.py) — admissions,
  reaps, preemptions and page-table growth apply as per-slot DELTAS, a
  round's worth in one upload and one donated program, and steady-state
  rounds upload nothing. With
  ``BatchingSpec.pipelined_decode`` (default on) the scheduler dispatches
  round N+1 before consuming round N's tokens, so detokenization, stream
  callbacks, reaping and admission overlap device compute. The staleness
  contract is one round deep and bounded: a cancellation or admission
  decided while a round is in flight takes effect the NEXT round, and a
  cancelled slot's in-flight results are masked before emission — output
  streams never contain post-cancel tokens. Greedy outputs are
  token-identical with pipelining on and off (regression-tested).
- **Request lifecycle** (deadlines, cancellation, load shedding): every
  request may carry a monotonic ``deadline`` and can be ``cancel()``ed from
  any thread; the scheduler reaps dead requests each step wherever they
  live (backlog, chunked prefill, live slot), freeing the slot and paged-KV
  pages refcount-balanced. Admission is bounded (``BatchingSpec.max_queue``
  → ``EngineOverloaded``, the HTTP-429 signal) and queue time is budgeted
  (``queue_delay_budget`` → finish_reason="shed").
- **Tensor-parallel mesh mode** ((U) kserve huggingfaceserver → vLLM
  ``tensor_parallel_size``; SURVEY.md §2.3#27): pass a ``mesh`` and the
  engine shards weights by the same logical rules training uses
  (parallel/sharding.py — Megatron head/mlp/vocab splits over ``model``)
  and the KV cache on the kv-head dim. Dispatches stay the SAME jitted
  functions — GSPMD partitions them and inserts the per-layer psums over
  ICI. This is what serves models bigger than one chip's HBM (the 8B-on-
  v5e-8 north star: 16 GB of bf16 params cannot fit one 16 GB chip).
  The scheduler is unchanged: one engine = one process = N chips.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.core.serving import (
    BatchingSpec, QOS_DEFAULT, QOS_PRIORITY,
)
from kubeflow_tpu.serve.chunk_programs import (
    ChunkPrograms, plan_chunks, program_key,
)
from kubeflow_tpu.serve.device_state import DEAD_SLOT, DecodeState
from kubeflow_tpu.serve.pacing import RoundPacer, decode_ladder
from kubeflow_tpu.serve.paged import (
    MOE_ROWS, SEQUENCE_PLANES, PageAllocator, PagePoolExhausted,
    engine_pool_shapes, first_page_ids, own_first_pages, paged_chunk_prefill,
    paged_decode_multi, pool_bytes_per_token, pool_shapes, ring_pages,
)
from kubeflow_tpu.serve.weight_layout import (
    relaid_bytes, relay, weight_formats,
)
from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import (
    Params, init_decoder_params, plane_kind,
)
from kubeflow_tpu.obs import profiler as prof
from kubeflow_tpu.obs.stats import quantile as _quantile
from kubeflow_tpu.obs.trace import get_tracer
from kubeflow_tpu.runtime.bootstrap import compile_counters, watch_compiles

logger = logging.getLogger("kubeflow_tpu.serve.engine")


class EngineOverloaded(Exception):
    """The admission queue is at ``BatchingSpec.max_queue``: shed at the
    door, in microseconds, instead of queueing into a guaranteed timeout.
    The protocol layer maps this to HTTP 429 + ``Retry-After``."""

    def __init__(self, message: str, retry_after: float = 1.0,
                 qos: str = QOS_DEFAULT):
        super().__init__(message)
        self.retry_after = retry_after
        self.qos = qos


# -- sampling ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SamplingParams:
    max_new_tokens: int = 64
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = off
    top_p: float = 1.0                # >= 1 = off (nucleus sampling)
    stop_token: Optional[int] = None  # eos


def _keys_selected(pos: int, real: int, topk: int) -> int:
    """Keys an indexer of ``topk`` selects for the ``real`` queries of a
    chunk at ``pos``: ``min(topk, t + 1)`` for the query at position
    ``t``."""
    whole = min(max(topk - pos, 0), real)   # queries that still see <= topk
    return whole * pos + whole * (whole + 1) // 2 + (real - whole) * topk


def _mode_for(params_list) -> str:
    """Static sampling mode for a dispatch (cheapest program that is exact
    for every slot in it)."""
    if all(p.temperature <= 0.0 for p in params_list):
        return "greedy"
    if all(p.top_k <= 0 and p.top_p >= 1.0 for p in params_list):
        return "plain"
    return "full"


def _sample_batch(logits: jax.Array, key: jax.Array, temps: jax.Array,  # traced
                  top_k: jax.Array, top_p: jax.Array,
                  mode: str = "full") -> jax.Array:
    """[B, V] logits -> [B] token ids with PER-SLOT sampling params.

    ``temps``/``top_k``/``top_p`` are traced [B] arrays, so one compiled
    program serves every mix of greedy / top-k / nucleus requests sharing a
    decode batch (a slot asking top_k=0 full-categorical must never inherit a
    neighbor's truncation). One descending sort per step provides both the
    k-th-value threshold (any k, no static cap) and the nucleus cumsum.

    ``mode`` is a static fast-path hint the host computes per dispatch:
    "greedy" (every slot temperature=0) skips sampling entirely; "plain"
    (no slot requests truncation) skips the sort pipeline and draws from the
    scaled logits directly; "full" runs top-k/top-p filtering."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    if mode == "greedy":
        return greedy
    if mode == "plain":
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(temps > 0, sampled, greedy)
    order = jnp.argsort(-logits, axis=-1)                       # [B,V] desc
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)
    keep_k = jnp.where((top_k > 0)[:, None], col < top_k[:, None], True)
    scaled = jnp.where(keep_k, sorted_logits, -1e30) \
        / jnp.maximum(temps, 1e-6)[:, None]
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs                    # exclusive
    # Exclusive cumsum keeps the first token whenever top_p > 0; the col==0
    # clause guards degenerate top_p <= 0 from an all-masked row.
    keep_p = (cum < top_p[:, None]) | (col == 0)
    final = jnp.where(keep_p, scaled, -1e30)
    draw = jax.random.categorical(key, final, axis=-1)          # [B]
    sampled = jnp.take_along_axis(order, draw[:, None], axis=-1)[:, 0]
    return jnp.where(temps > 0, sampled, greedy)


# -- requests ------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    prompt_tokens: list[int]
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    id: str = ""
    arrival: float = dataclasses.field(default_factory=time.monotonic)
    # Request lifecycle: ``deadline`` is a monotonic timestamp (None = no
    # deadline) stamped by the caller — the model server derives it from the
    # client timeout / router deadline header. The scheduler reaps expired
    # and cancelled requests wherever they live (backlog, chunked prefill,
    # live slot), freeing the slot and its KV pages instead of decoding
    # dead work.
    deadline: Optional[float] = None
    # Multi-tenant QoS class (core/serving.QOS_CLASSES): drives admission
    # quotas, strict-priority dequeue, shed order under overload, and
    # cross-class preemption. Rides end-to-end on the X-Kftpu-Qos header.
    qos: str = QOS_DEFAULT
    # Multi-tenant LoRA (serve/lora.py): the registered adapter this
    # request decodes through (None = base model). Rides the request's
    # model id end-to-end ("model" body field / X-Kftpu-Model header);
    # admission acquires a packed-buffer slot (hot-loading on miss) and
    # every release path returns the reference.
    adapter: Optional[str] = None
    # Recompute-preemption bookkeeping: output tokens already
    # folded back into prompt_tokens when the slot was preempted.
    resumed_from: int = 0
    # Disaggregated serving (serve/handoff.py). ``handoff_requested``:
    # this prefill-side request stops at the first token and exports its
    # KV instead of decoding (finish_reason="handoff", payload in
    # ``handoff``). ``adopt``: this decode-side request was born from a
    # handoff payload — admission uploads its KV instead of prefilling.
    handoff_requested: bool = False
    handoff: Optional[Any] = None
    adopt: Optional[Any] = None
    # results
    output_tokens: list[int] = dataclasses.field(default_factory=list)
    # Monotonic instant of the request's FIRST admission (None while it
    # waits): admission to first token is the prefill phase
    # (``LLMEngine.counters``).
    admitted_time: Optional[float] = None
    first_token_time: Optional[float] = None
    # The scheduler's clock when the tokens last put on ``stream`` lay
    # ready on the host: the fetch of their round, or of their prompt's
    # first token, had returned. Set once a round a stream, before its
    # puts; the server's wake time runs from it (``ModelServer.counters``).
    tokens_ready_time: Optional[float] = None
    finish_time: Optional[float] = None
    finish_reason: Optional[str] = None
    stream: "queue.Queue[Optional[int]]" = dataclasses.field(
        default_factory=queue.Queue)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    _cancelled: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # Observability (obs/trace.py): ``trace_parent`` is the submitter's span
    # context (the model server's request span — contextvars don't cross
    # into the scheduler thread, so it rides on the request); ``span`` is
    # the currently-open engine child span (queued → prefill → decode),
    # owned exclusively by the scheduler. None on both = untraced request,
    # and every tracing hook is a no-op.
    trace_parent: Optional[Any] = None
    span: Optional[Any] = None

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival

    def cancel(self) -> None:
        """Client abandonment: flag the request for the scheduler, which
        reaps it at its next step. Safe from any thread, idempotent, and a
        no-op on an already-finished request."""
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def abandon_reason(self, now: Optional[float] = None) -> Optional[str]:
        """Why the scheduler should drop this request, or None to keep it.
        Cancellation wins over expiry (it is the more explicit signal)."""
        if self._cancelled.is_set():
            return "cancelled"
        if self.deadline is not None and \
                (time.monotonic() if now is None else now) > self.deadline:
            return "deadline"
        return None

    def result(self, timeout: Optional[float] = None) -> list[int]:
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} not finished")
        return self.output_tokens


def _span_close(req: Request, status: str = "ok", **attrs: Any) -> None:
    """End the request's open engine span (no-op for untraced requests)."""
    if req.span is not None:
        if attrs:
            req.span.set_attrs(**attrs)
        req.span.end(status)
        req.span = None


def _span_open(req: Request, name: str, **attrs: Any) -> None:
    if req.trace_parent is not None:
        req.span = get_tracer().start_span(name, parent=req.trace_parent,
                                           request=req.id, **attrs)


@dataclasses.dataclass
class _Slot:
    request: Request
    length: int           # position of the NEXT token to be written
    last_token: int
    generated: int = 0
    admit_seq: int = 0    # admission order (preemption picks the youngest)


@dataclasses.dataclass
class _Chunking:
    """An in-flight chunked prefill (several may run concurrently — no
    head-of-line blocking between long prompts)."""
    request: Request
    slot: int
    pos: int              # next prompt position to prefill
    stalls: int = 0       # consecutive page-starved attempts
    turn: int = -1        # the admit pass of its last turn (_advance_chunked)


@dataclasses.dataclass
class _InflightRound:
    """A dispatched-but-unconsumed decode round. Pipelined dispatch keeps
    at most one in flight while the host detokenizes/streams/reaps/admits;
    ``active`` snapshots the dispatch-time slot occupants so consumption
    can mask slots that were reaped, preempted, or re-admitted while the
    round ran (the one-round staleness contract)."""
    out: jax.Array                      # [B, k_steps] device token buffer
    active: list[tuple[int, "_Slot"]]
    k_steps: int
    gap_ms: Optional[float]             # host gap preceding this dispatch
    round_id: int = 0                   # ties the dispatch span to its fetch
    # the iteration that dispatched it sent no prefill program before it:
    # the device runs nothing but this round between the last one's end and
    # its own
    alone: bool = False
    # the expert rows' running sums as they stood when the round ended
    # (int32 [2], a buffer of its own: the pool's is donated onward), or None
    rows: Optional[jax.Array] = None


def _pin2(out, pin):
    """Apply the cache-sharding pin to a dispatch's returned cache (always
    the second tuple element) — keeps donated in/out layouts identical so
    GSPMD never re-lays the KV cache between steps in mesh mode."""
    return (out[0], pin(out[1])) + tuple(out[2:])


def _committed(tree):
    """``tree`` with every array committed where it lies (no copy, no
    program)."""
    return jax.device_put(tree, jax.tree.map(lambda x: x.sharding, tree))


# -- the engine ----------------------------------------------------------------

#: Queue-delay histogram bucket upper bounds (seconds). Chosen to resolve
#: both the healthy regime (sub-dispatch waits) and the overload knee.
QUEUE_DELAY_BUCKETS = (0.005, 0.02, 0.05, 0.1, 0.25, 1.0, 5.0, 30.0)

#: Host-gap histogram bucket upper bounds (seconds): the per-round wall
#: time between the previous decode round's results landing on host and
#: the next round entering the device queue (0 when the next round was
#: already in flight — the pipelined steady state). Buckets resolve both
#: the pipelined regime (sub-ms) and the unpipelined host-bound tail.
HOST_GAP_BUCKETS = (0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                    0.1, 0.5)


class EngineMetrics:
    """Serving metrics the reference never surfaces from its own code:
    req/s, TTFT and TPOT quantiles, tokens/s (SURVEY.md §5 observability),
    plus speculative-decoding health (acceptance rate, verified tokens per
    dispatch, draft overhead share) when the engine runs spec rounds."""

    def __init__(self, window: int = 2048):
        self._lock = threading.Lock()
        self.requests_completed = 0     # guarded_by: _lock
        self.tokens_generated = 0       # guarded_by: _lock
        self.started = time.monotonic()
        self._ttft: list[float] = []    # guarded_by: _lock
        self._tpot: list[float] = []    # guarded_by: _lock
        self._window = window
        # speculative decoding counters (one "round" = one verify dispatch)
        self.spec_rounds = 0            # guarded_by: _lock
        self.spec_drafted = 0           # guarded_by: _lock
        self.spec_accepted = 0          # guarded_by: _lock
        self.spec_emitted = 0           # guarded_by: _lock
        self.spec_draft_time = 0.0      # guarded_by: _lock
        self.spec_verify_time = 0.0     # guarded_by: _lock
        # request-lifecycle counters (load shedding + reaping)
        self.requests_shed = 0          # guarded_by: _lock
        self.requests_cancelled = 0     # guarded_by: _lock
        self.requests_expired = 0       # guarded_by: _lock
        self.preemptions = 0            # guarded_by: _lock
        # Disaggregated-serving handoff health: exports leaving a prefill
        # engine, adoptions landing on a decode engine, and failed/aborted
        # handoffs (decode side never acked — the recompute path fired).
        self.handoffs_exported = 0      # guarded_by: _lock
        self.handoffs_adopted = 0       # guarded_by: _lock
        self.handoffs_failed = 0        # guarded_by: _lock
        # Cross-host handoff failure budget (ISSUE 17): retried = a POST
        # attempt failed and the relay moved to a DIFFERENT decode
        # replica; fallback = every replica exhausted and the prefill
        # recomputed locally (the terminal degrade — request resolved,
        # never dropped).
        self.handoffs_retried = 0       # guarded_by: _lock
        self.handoffs_fallback = 0      # guarded_by: _lock
        # KV bytes shipped/received over the handoff wire (pages + scale
        # blobs) — with int8 pools these run at ~half the full-dtype
        # rate, the r05 wire-bytes claim's measured series.
        self.handoff_bytes_exported = 0  # guarded_by: _lock
        self.handoff_bytes_adopted = 0   # guarded_by: _lock
        self._qd_counts = [0] * (len(QUEUE_DELAY_BUCKETS) + 1)  # guarded_by: _lock
        self._qd_sum = 0.0              # guarded_by: _lock
        self._qd_n = 0                  # guarded_by: _lock
        self._qd: list[float] = []      # guarded_by: _lock (p95 window)
        # Per-QoS-class health (multi-tenant SLO attainment): shed /
        # preemption / completion counters plus TTFT and queue-delay
        # windows + histogram counts, keyed by class. Lazily created, so
        # a single-class engine carries exactly one entry and the
        # pre-QoS snapshot shape is unchanged.
        self._qos: dict[str, dict] = {}  # guarded_by: _lock
        # decode hot-loop health: host gap per round + dispatch depth
        # (0 = every round waits on the host; 1 = one round in flight
        # while the host works — the pipelined steady state).
        self.dispatch_depth = 0         # guarded_by: _lock
        self._hg: list[float] = []      # guarded_by: _lock
        self._hg_counts = [0] * (len(HOST_GAP_BUCKETS) + 1)  # guarded_by: _lock
        self._hg_sum = 0.0              # guarded_by: _lock
        self._hg_n = 0                  # guarded_by: _lock

    def _qos_entry(self, qos: str) -> dict:  # requires_lock: _lock
        e = self._qos.get(qos)
        if e is None:
            e = self._qos[qos] = {
                "completed": 0, "shed": 0, "preempted": 0,
                "ttft": [], "qd": [],
                "qd_counts": [0] * (len(QUEUE_DELAY_BUCKETS) + 1),
                "qd_sum": 0.0, "qd_n": 0,
            }
        return e

    def observe(self, req: Request) -> None:
        with self._lock:
            self.requests_completed += 1
            self.tokens_generated += len(req.output_tokens)
            e = self._qos_entry(req.qos)
            e["completed"] += 1
            if req.ttft is not None:
                self._ttft.append(req.ttft)
                self._ttft = self._ttft[-self._window:]
                e["ttft"].append(req.ttft)
                e["ttft"] = e["ttft"][-self._window:]
            if (req.finish_time is not None and req.first_token_time is not None
                    and len(req.output_tokens) > 1):
                tpot = ((req.finish_time - req.first_token_time)
                        / (len(req.output_tokens) - 1))
                self._tpot.append(tpot)
                self._tpot = self._tpot[-self._window:]

    def note_shed(self, qos: str = QOS_DEFAULT) -> None:
        with self._lock:
            self.requests_shed += 1
            self._qos_entry(qos)["shed"] += 1

    def note_preempted(self, qos: str = QOS_DEFAULT) -> None:
        """One recompute preemption, labeled by the VICTIM's class —
        the series that shows batch absorbing interactive's bursts."""
        with self._lock:
            self.preemptions += 1
            self._qos_entry(qos)["preempted"] += 1

    def note_handoff(self, event: str, wire_bytes: int = 0) -> None:
        """One handoff lifecycle event: ``exported`` | ``adopted`` |
        ``retried`` | ``fallback`` | ``failed`` — exports/adoptions also
        account their payload's KV wire bytes."""
        with self._lock:
            if event == "exported":
                self.handoffs_exported += 1
                self.handoff_bytes_exported += wire_bytes
            elif event == "adopted":
                self.handoffs_adopted += 1
                self.handoff_bytes_adopted += wire_bytes
            elif event == "retried":
                self.handoffs_retried += 1
            elif event == "fallback":
                self.handoffs_fallback += 1
            else:
                self.handoffs_failed += 1

    def note_abandoned(self, reason: str) -> None:
        with self._lock:
            if reason == "cancelled":
                self.requests_cancelled += 1
            else:
                self.requests_expired += 1

    def observe_queue_delay(self, seconds: float,
                            qos: str = QOS_DEFAULT) -> None:
        with self._lock:
            i = 0
            while i < len(QUEUE_DELAY_BUCKETS) \
                    and seconds > QUEUE_DELAY_BUCKETS[i]:
                i += 1
            self._qd_counts[i] += 1
            self._qd_sum += seconds
            self._qd_n += 1
            self._qd.append(seconds)
            self._qd = self._qd[-self._window:]
            e = self._qos_entry(qos)
            e["qd_counts"][i] += 1
            e["qd_sum"] += seconds
            e["qd_n"] += 1
            e["qd"].append(seconds)
            e["qd"] = e["qd"][-self._window:]

    def queue_delay_histogram(self, qos: Optional[str] = None
                              ) -> tuple[list[float], list[int], float, int]:
        """(bucket upper bounds, per-bucket counts incl. +Inf tail, sum,
        count) — the Prometheus-histogram raw material. ``qos`` selects one
        class's histogram (all-zero for a class never seen)."""
        with self._lock:
            if qos is None:
                return (list(QUEUE_DELAY_BUCKETS), list(self._qd_counts),
                        self._qd_sum, self._qd_n)
            e = self._qos_entry(qos)
            return (list(QUEUE_DELAY_BUCKETS), list(e["qd_counts"]),
                    e["qd_sum"], e["qd_n"])

    def qos_classes(self) -> list[str]:
        """Classes this engine has observed (metrics exposition drives
        one labeled series set per entry)."""
        with self._lock:
            return sorted(self._qos)

    def observe_host_gap(self, seconds: float) -> None:
        with self._lock:
            i = 0
            while i < len(HOST_GAP_BUCKETS) \
                    and seconds > HOST_GAP_BUCKETS[i]:
                i += 1
            self._hg_counts[i] += 1
            self._hg_sum += seconds
            self._hg_n += 1
            self._hg.append(seconds)
            self._hg = self._hg[-self._window:]

    def note_dispatch_depth(self, depth: int) -> None:
        with self._lock:
            self.dispatch_depth = depth

    def host_gap_histogram(self) -> tuple[list[float], list[int],
                                          float, int]:
        """(bucket upper bounds, per-bucket counts incl. +Inf tail, sum,
        count) for ``kftpu_engine_host_gap_seconds``."""
        with self._lock:
            return (list(HOST_GAP_BUCKETS), list(self._hg_counts),
                    self._hg_sum, self._hg_n)

    def observe_spec_round(self, drafted: int, accepted: int, emitted: int,
                           draft_s: float, verify_s: float) -> None:
        with self._lock:
            self.spec_rounds += 1
            self.spec_drafted += drafted
            self.spec_accepted += accepted
            self.spec_emitted += emitted
            self.spec_draft_time += draft_s
            self.spec_verify_time += verify_s

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            elapsed = max(time.monotonic() - self.started, 1e-9)
            out = {
                "requests_completed": self.requests_completed,
                "tokens_generated": self.tokens_generated,
                "requests_per_sec": self.requests_completed / elapsed,
                "tokens_per_sec": self.tokens_generated / elapsed,
                "requests_shed": self.requests_shed,
                "requests_cancelled": self.requests_cancelled,
                "requests_expired": self.requests_expired,
                "preemptions": self.preemptions,
                "handoffs_exported": self.handoffs_exported,
                "handoffs_adopted": self.handoffs_adopted,
                "handoffs_failed": self.handoffs_failed,
                "handoffs_retried": self.handoffs_retried,
                "handoffs_fallback": self.handoffs_fallback,
                "handoff_bytes_exported": self.handoff_bytes_exported,
                "handoff_bytes_adopted": self.handoff_bytes_adopted,
            }
            if self._qd_n:
                out["queue_delay_avg_ms"] = self._qd_sum / self._qd_n * 1e3
            if self._qd:
                out["queue_delay_p95_ms"] = _quantile(self._qd, 0.95) * 1e3
            # Per-class SLO attainment: the series the signal-driven
            # autoscaler and the overload dashboards read.
            qos_out: dict[str, dict[str, Any]] = {}
            for cls, e in self._qos.items():
                c: dict[str, Any] = {"completed": e["completed"],
                                     "shed": e["shed"],
                                     "preempted": e["preempted"]}
                if e["ttft"]:
                    c["ttft_p50_ms"] = _quantile(e["ttft"], 0.5) * 1e3
                    c["ttft_p95_ms"] = _quantile(e["ttft"], 0.95) * 1e3
                if e["qd"]:
                    c["queue_delay_p95_ms"] = _quantile(e["qd"], 0.95) * 1e3
                qos_out[cls] = c
            if qos_out:
                out["qos"] = qos_out
            out["dispatch_depth"] = self.dispatch_depth
            if self._hg_n:
                out["host_gap_seconds"] = self._hg_sum
                out["host_gap_p50_ms"] = _quantile(self._hg, 0.5) * 1e3
                out["host_gap_p99_ms"] = _quantile(self._hg, 0.99) * 1e3
            for name, xs in (("ttft", self._ttft), ("tpot", self._tpot)):
                if xs:
                    srt = sorted(xs)
                    out[f"{name}_p50_ms"] = _quantile(srt, 0.5) * 1e3
                    out[f"{name}_p95_ms"] = _quantile(srt, 0.95) * 1e3
                    out[f"{name}_p99_ms"] = _quantile(srt, 0.99) * 1e3
            if self.spec_rounds:
                out["spec_rounds"] = self.spec_rounds
                out["spec_acceptance_rate"] = (
                    self.spec_accepted / max(self.spec_drafted, 1))
                out["spec_tokens_per_step"] = (
                    self.spec_emitted / self.spec_rounds)
                total = self.spec_draft_time + self.spec_verify_time
                out["spec_draft_overhead"] = (
                    self.spec_draft_time / max(total, 1e-9))
            return out


def serving_configs(cfg: DecoderConfig, b: BatchingSpec):
    """``(cfg_prefill, cfg_decode)``: the config the chunk programs and the
    decode programs of an engine over ``cfg`` are built with. They differ
    only in how a sparse model's experts are reached; both carry the ring a
    sequence keeps in the window layers' planes (``window_ring_pages``)."""
    # Serving MoE must be batch-independent: a request's tokens must not
    # change because co-batched traffic filled an expert's capacity
    # buffer. Two phases, two resolutions (VERDICT r3 #3):
    # - PREFILL: capacity drops are a function of the request alone.
    #   The chunk program may carry several prompts' chunks, and
    #   takes the dispatch path's capacity and claiming order per row
    #   (layers._moe_dispatch, capacity_per_row), so a prompt keeps and
    #   drops what it would alone. The training dispatch path applies
    #   and WINS the on-chip serving A/B (7.0 vs 6.5 req/s, p50 TTFT
    #   -15% at mixtral-0.8b p1024).
    # - DECODE co-batches slots; dispatch is only batch-independent at
    #   zero-drop capacity (C = k*T). The same A/B measured it a tie
    #   within session noise, so dense (simpler, drop-free by
    #   construction) stays the default (bench_serve.py --workload moe).
    # - The chunk program that CARRIES the decode step is built with
    #   ``cfg_prefill`` alone: its decode rows take the prefill path's
    #   drop-free form (dispatch: a capacity group of their own that holds
    #   every token, ``layers._moe_dispatch``'s tail; sorted: no capacity
    #   at all), the same tokens up to rounding. ``moe_decode_impl``
    #   therefore governs the decode-ONLY programs.
    if cfg.layers_of("window"):
        # A sequence's ring in the window layers' planes: every program of
        # the engine reads its length off its config (paged.ring_table).
        cfg = dataclasses.replace(cfg, window_ring_pages=ring_pages(
            cfg, max(0, int(b.chunked_prefill_tokens)) or b.page_size,
            b.page_size, b.max_seq_len // b.page_size))
    cfg_prefill, cfg_decode = cfg, cfg
    if cfg.is_moe:
        pre = b.moe_prefill_impl
        if pre == "auto":
            pre = cfg.moe_impl          # the model's training-time path
        if pre not in ("dispatch", "dense", "sorted"):
            raise ValueError(
                f"unknown moe_prefill_impl {b.moe_prefill_impl!r}")
        cfg_prefill = dataclasses.replace(cfg, moe_impl=pre)
        dec = b.moe_decode_impl
        if dec == "auto":
            # A model whose own path is the drop-free sorted one keeps
            # it (no capacity, so co-batched slots cannot change each
            # other); dense for the capacity-dispatch models.
            dec = "sorted" if cfg.moe_impl == "sorted" else "dense"
        if dec == "zero_drop":
            # cf = E caps capacity at k*T: nothing can ever drop, so
            # outputs are exactly the dense oracle's (tested) while the
            # buffers stay dispatch-shaped for the A/B.
            cfg_decode = dataclasses.replace(
                cfg, moe_impl="dispatch",
                capacity_factor=float(cfg.num_experts))
        elif dec in ("dense", "sorted"):
            cfg_decode = dataclasses.replace(cfg, moe_impl=dec)
        else:
            raise ValueError(
                f"unknown moe_decode_impl {b.moe_decode_impl!r}")
    return cfg_prefill, cfg_decode


class LLMEngine:
    """Slot-based continuous-batching engine over a decoder LLM."""

    def __init__(self, cfg: DecoderConfig, batching: Optional[BatchingSpec] = None,
                 *, params: Optional[Params] = None, seed: int = 0,
                 mesh: Optional[Mesh] = None,
                 draft_params: Optional[Params] = None):
        # The start-up clock (obs/profiler.py): from here to the
        # constructor's last line every second lands in one of the four
        # start phases or in ``other``, always on; under a capture that is
        # active now each phase is an ``engine.start.*`` span as well.
        # ``counters()`` carries the sums, constants once this returns.
        watch_compiles()
        self._start = prof.PhaseClock(prof.ENGINE_START_PHASES)
        self._start.begin()
        start = self._start.phase
        # {program: seconds} of the programs run once below, in order.
        self._start_programs: dict[str, float] = {}
        if mesh is not None and mesh.size > 1:
            # The engine's blocks call the norm/GLU layers with no mesh, so
            # layers.fused_kernels_on would resolve "auto" from the backend
            # alone and put Mosaic kernels over GSPMD-sharded operands —
            # which the TPU compiler refuses ("Mosaic kernels cannot be
            # automatically partitioned"). Same rule as training under a
            # mesh: the XLA ops, which GSPMD partitions.
            cfg = dataclasses.replace(cfg, fused_kernels="off")
        self.cfg = cfg
        self.batching = batching or BatchingSpec()
        b = self.batching
        cfg_prefill, cfg_decode = serving_configs(cfg, b)
        self._cfg_prefill, self._cfg_decode = cfg_prefill, cfg_decode
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self._refuse_unsupported(cfg, b)
        if b.max_seq_len > cfg.max_seq_len:
            raise ValueError("batching.max_seq_len exceeds model max_seq_len")
        self.num_slots = b.max_batch_size
        self.max_len = b.max_seq_len

        with start(prof.ENGINE_START_PLACE):
            key = jax.random.PRNGKey(seed)
            self.params = params if params is not None else init_decoder_params(key, cfg)
            if b.weights_dtype is not None:
                # Inference-only weights: cast once at load instead of per-use.
                # Decode is HBM-bound on the param read, so fp32 checkpoints
                # served as bf16 halve the per-step floor.
                wdt = jnp.dtype(b.weights_dtype)
                self.params = jax.tree.map(
                    lambda x: x.astype(wdt) if jnp.issubdtype(x.dtype, jnp.floating)
                    else x, self.params)
            if b.quantize is not None:
                # Weight-only int8 ((U) vLLM quantization; VERDICT r4 #3): the
                # big matmuls store int8 + per-channel scales and dequantize in
                # the operand read — halves the decode HBM param read vs bf16
                # and halves param residency. Applied after the dtype cast so
                # scales quantize the served (not checkpoint) values.
                if b.quantize != "int8":
                    raise ValueError(
                        f"unknown quantize {b.quantize!r}; supported: int8")
                from kubeflow_tpu.ops.quantization import quantize_params_int8

                self.params = quantize_params_int8(self.params, cfg)
            if b.kv_cache_dtype not in (None, "int8"):
                raise ValueError(
                    f"unknown kv_cache_dtype {b.kv_cache_dtype!r}; "
                    "supported: int8")
            self.kv_quant = b.kv_cache_dtype == "int8"
            self._cache_sh: Optional[NamedSharding] = None
            self._cache_scale_sh: Optional[NamedSharding] = None
            if self.mesh is not None:
                from kubeflow_tpu.models.decoder import decoder_param_specs
                from kubeflow_tpu.parallel.sharding import shard_params

                # Weights: the exact logical rules training uses (heads/mlp/kv/
                # vocab → `model`); non-divisible dims auto-replicate. KV cache:
                # sharded on the kv-head dim — the same split wk/wv produce, so
                # cache writes and decode attention are collective-free; only
                # wo's output psum and the vocab-parallel logits ride ICI.
                self.params = jax.device_put(
                    self.params,
                    shard_params(self.params, decoder_param_specs(cfg),
                                 self.mesh))
                kv_ps = PartitionSpec(None, None, None, "model", None)
                scale_ps = PartitionSpec(None, None, None, "model")
                if cfg.n_kv_heads % self.mesh.shape.get("model", 1):
                    kv_ps = PartitionSpec()      # GQA heads don't divide: replicate
                    scale_ps = PartitionSpec()
                self._cache_sh = NamedSharding(self.mesh, kv_ps)
                self._cache_scale_sh = NamedSharding(self.mesh, scale_ps)
        self._rng = jax.random.PRNGKey(seed + 1)  # lockfree: scheduler-confined

        self.page_size = pg = int(b.page_size)
        self._kvtier = None          # lockfree: scheduler-confined
        if pg <= 0 or self.max_len % pg:
            raise ValueError("page_size must divide max_seq_len")
        # Every admission prefills in chunks, and a chunk writes exactly
        # the pages it fills (no bucket slack), so chunking cannot be off:
        # 0 falls back to one page a chunk.
        self.chunk_size = max(0, int(b.chunked_prefill_tokens)) or pg
        if self.chunk_size % pg:
            raise ValueError(
                "chunked_prefill_tokens must be a multiple of page_size "
                "(chunk boundaries are page boundaries)")
        self._mpp = self.max_len // pg
        self._num_pages = int(b.max_pages or self.num_slots * self._mpp)
        if self._num_pages * pg < self.max_len:
            raise ValueError(
                "page pool smaller than one max-length sequence")
        # Window layers keep a ring of ``_ring`` pages a sequence, over its
        # first pages, in planes of ``_window_pages`` pages: a ring for
        # every slot; linear layers one entry a sequence at its first
        # page's id (a ring of 1 where the stack has no window layer). Those
        # ids are a sequence's first pages and nothing else
        # (``_ensure_pages``). Where a sequence keeps a ring of several
        # pages AND a state (ssm beside window layers), the lowest ids, one
        # a slot, are handed out as a sequence's very first page only: the
        # state's planes hold an entry a slot (``paged.first_page_ids``).
        self._ring = own_first_pages(cfg_decode)
        self._window_pages = min(self._num_pages,
                                 self.num_slots * self._ring)
        self._allocator = PageAllocator(
            self._num_pages, pg,
            enable_prefix_caching=b.enable_prefix_caching,
            ring_pages=self._window_pages,
            first_pages=first_page_ids(cfg_decode, self.num_slots))
        # lockfree: scheduler-confined (host page-table mirror)
        self._table = np.full((self.num_slots, self._mpp), -1, np.int32)
        self._slot_pages: list[list[int]] = [  # lockfree: scheduler-confined
            [] for _ in range(self.num_slots)]
        # The pool, plane by plane as the model describes it (k and v
        # per head, int8 pools with their per-token-per-head scales:
        # +4 bytes per token per kv head against the 2x density win on
        # the Dh-wide vectors; a latent model's one padded row), each
        # over the layers of its kind; the conv layers of a patterned
        # stack hold their state a page, beside the attention layers'
        # rows a token.
        with start(prof.ENGINE_START_POOL):
            shapes = engine_pool_shapes(cfg_decode, self.num_slots,
                                        self._num_pages, pg, self.kv_quant)
            self.cache = {  # lockfree: scheduler-confined (donated KV)
                name: self._zeros(shape, dt, scale=name in ("ks", "vs"))
                for name, (shape, dt) in shapes.items() if name != MOE_ROWS}

        self._kv_bytes_per_token = pool_bytes_per_token(cfg, self.kv_quant)
        self._kv_pool_bytes = int(sum(v.nbytes for v in self.cache.values()))
        by_kind = {kind: int(sum(v.nbytes for n, v in self.cache.items()
                                 if plane_kind(n) == kind))
                   for kind in ("attention", "window", "conv",
                                *SEQUENCE_PLANES)}
        self._state_pool_bytes = by_kind["conv"]
        self._index_pool_bytes = int(
            self.cache["idx"].nbytes) if "idx" in self.cache else 0
        self._kv_window_pool_bytes = by_kind["window"]
        self._kv_global_pool_bytes = by_kind["attention"]
        # The linear and ssm layers' planes, and a parallel layer's own
        # (its K and V are rows of the global planes), hold an entry a
        # SEQUENCE (``slots`` of them, beside the token pages ``max_pages``
        # buys); every other plane holds rows a token or a tail a page.
        self._kv_sequence_pool_bytes = sum(
            by_kind[kind] for kind in SEQUENCE_PLANES)
        self._state_sequences_started = 0       # lockfree: scheduler-confined counter
        # What ONE live row's decode step moves of those planes: its entry
        # in every layer that keeps one, read AND written (the planes' own
        # shapes: bytes an entry, twice).
        self._state_bytes_a_row = 2 * sum(
            int(self.cache[n].nbytes) // self.cache[n].shape[1]
            for kind in SEQUENCE_PLANES for n in SEQUENCE_PLANES[kind]
            if n in self.cache)
        self._state_bytes_stepped = 0           # lockfree: scheduler-confined counter
        # Where a layer holds a share of its experts, the rows its expert
        # layers routed and held ride in the cache pytree as running sums
        # (no pool plane: ``paged._planes_of``); the scheduler reads them in
        # a round's fetch (``_consume_round``): routed, held and, where the
        # router has zero experts, the rows that chose one.
        self._expert_rows = [0, 0, 0]   # lockfree: scheduler-confined
        self._expert_rows_last = (0, 0, 0)  # lockfree: scheduler-confined (what the last fetch added)
        rows_shape, _ = shapes.get(MOE_ROWS, ((2,), None))
        if MOE_ROWS in shapes:
            self.cache[MOE_ROWS] = jnp.zeros(rows_shape, jnp.int32)
        self._expert_rows_seen = np.zeros(rows_shape, np.uint32)  # lockfree: scheduler-confined

        # Compiled programs: donate the cache so it mutates in place in HBM.
        on_tpu = jax.default_backend() == "tpu"
        # Pallas kernel call sites per program AS DISPATCHED (program name +
        # its static arguments -> {kernel: sites}), read from the lowered
        # text at each variant's first dispatch; the server's
        # /debug/device reports it. Stays empty off the TPU.
        self.program_kernels: dict[str, dict[str, int]] = {}  # lockfree: scheduler-confined writes; readers snapshot

        self._chunkings: list[_Chunking] = []   # lockfree: scheduler-confined
        self.max_concurrent_prefills = max(1, int(b.max_concurrent_prefills))
        pattn = b.paged_attn_impl
        if pattn == "auto":
            # Mesh mode: gather (pure XLA ops — GSPMD-partitionable);
            # the direct-page-read kernel would need a shard_map.
            # int8 pools ride the kernel too: it reads int8 pages +
            # scale rows and dequantizes in VMEM.
            pattn = ("pallas" if on_tpu and self.mesh is None
                     else "gather")
        if pattn not in ("gather", "pallas"):
            raise ValueError(
                f"unknown paged_attn_impl {b.paged_attn_impl!r}; "
                "one of auto|gather|pallas")
        self.paged_attn_impl = pattn    # resolved (post-auto) impl
        # The last step of the load path (behind the cast, the quantizer and
        # the mesh's placement: an ``astype`` or a ``tree.map`` after it
        # would drop the layout): the per-head projections lie as both
        # serving programs read them, so neither copies a weight again
        # (serve/weight_layout.py). Logical shapes stay.
        with start(prof.ENGINE_START_RELAY):
            formats = weight_formats(
                self.params, cfg,
                one_chip_pallas=(on_tpu and self.mesh is None
                                 and pattn == "pallas"))
            self.params = relay(self.params, formats)
            self._weights_relaid_bytes = relaid_bytes(self.params, formats)
        if self._weights_relaid_bytes:
            # A relaid leaf is a COMMITTED array (JAX reads a layout off a
            # committed argument only), and what a program returns is
            # committed where any of its arguments is: the pool, the decode
            # state, a chunk's logits. A program called once with a fresh,
            # uncommitted pool and then with a returned one is lowered
            # twice, the second time in the middle of traffic (seen on the
            # chip: the COW copy warmed below, compiled again inside a
            # measured window). So what the engine allocates starts
            # committed too, here and at the decode state, and the programs
            # warmed before traffic are the ones traffic reaches.
            with start(prof.ENGINE_START_POOL):
                self.cache = _committed(self.cache)

        # Which chunk program carries a pass's chunks: ``ChunkPlan.send``
        # (serve/chunk_programs.py). ``_paged_chunk``: the ``[C, V]`` program,
        # for callers OUTSIDE the engine (the benchmark's ``correct``).
        self._plan = plan_chunks(cfg_prefill, self.cache, b, pattn)
        self._programs = ChunkPrograms(
            self, self._plan, cfg_prefill, pattn,
            self._introspected if on_tpu else None)
        self._paged_chunk = self._programs.ask("lone")

        def _paged_decode_fn(p, c, st, tbl, key, n, m, lr=None,
                             _impl=pattn):
            # The device-resident state dict + page table ride in as
            # donated buffers and return advanced — the scheduler never
            # re-uploads them (serve/device_state.py).
            cache_in = {**c, "table": tbl}
            out, cache, tokens, lengths, live, budgets = \
                paged_decode_multi(
                    p, cache_in, st["tokens"], st["lengths"],
                    st["live"], st["temps"], st["top_k"], st["top_p"],
                    st["stops"], st["budgets"], key, cfg_decode, n,
                    sample_mode=m, attn_impl=_impl,
                    lora=lr, adapter_idx=st["adapter"])
            table = cache.pop("table")
            st = {**st, "tokens": tokens, "lengths": lengths,
                  "live": live, "budgets": budgets}
            rows = cache[MOE_ROWS] + 0 if MOE_ROWS in cache else None
            return out, self._pin(cache), st, table, rows

        self._paged_decode_n = jax.jit(
            _paged_decode_fn, static_argnums=(5, 6),
            donate_argnums=(1, 2, 3))
        self._mixed_pass = -1    # lockfree: scheduler-confined (the admit pass that sent a round)
        self._ahead_pass = -1    # lockfree: scheduler-confined (the admit pass that sent the NEXT round too: ``_round_ahead``)
        # Scheduler-confined state (the whole block below): mutated ONLY
        # on the scheduler thread (or by step() when no loop runs — the
        # unthreaded mode never coexists with start()). Cross-thread
        # signals ride `waiting` (a Queue) and the `_stop`/`_wake`
        # Events; everything else is single-owner by construction, which
        # is what the `# lockfree:` contracts below assert for the
        # C301 lock-discipline rule.
        self._preempted: list[Request] = []     # lockfree: scheduler-confined
        self._backlog: list[Request] = []       # lockfree: scheduler-confined
        self._admit_seq = itertools.count()
        # Disaggregated serving (serve/handoff.py). ``role`` comes from
        # BatchingSpec: "prefill" submits default to handoff-at-first-
        # token; "decode" engines adopt payloads via submit_handoff;
        # every role keeps the full engine (unified fallback).
        self.role = b.role
        # Exports awaiting their batched device→host KV fetch (one
        # jax.device_get per admit round, like first-token sampling).
        self._pending_exports: list = []        # lockfree: scheduler-confined
        # Pages backing an exported payload, held until the decode side
        # acks (request id -> (request, pages)). The allocator is
        # scheduler-confined, so server-thread acks marshal through
        # ``_handoff_release`` and free on the next step.
        self._handoff_holds: dict[str, tuple] = {}  # lockfree: scheduler-confined
        self._handoff_release: "queue.Queue[tuple[str, bool]]" = queue.Queue()
        if self.kv_quant:
            def _adopt_paged_fn(c, k, v, ks, vs, pidx):
                # int8 pool: the scale planes scatter alongside their
                # pages — a page without its scales is garbage content.
                npages = c["k"].shape[1]
                pi = jnp.where((pidx >= 0) & (pidx < npages), pidx, npages)
                out = {**c, "k": c["k"].at[:, pi].set(k, mode="drop"),
                       "v": c["v"].at[:, pi].set(v, mode="drop"),
                       "ks": c["ks"].at[:, pi].set(ks, mode="drop"),
                       "vs": c["vs"].at[:, pi].set(vs, mode="drop")}
                return self._pin(out)
        else:
            def _adopt_paged_fn(c, k, v, pidx):
                # OOB page ids (the power-of-two pad) drop their writes —
                # one trace per padded page-count, log-bounded.
                npages = c["k"].shape[1]
                pi = jnp.where((pidx >= 0) & (pidx < npages), pidx, npages)
                out = {**c, "k": c["k"].at[:, pi].set(k, mode="drop"),
                       "v": c["v"].at[:, pi].set(v, mode="drop")}
                return self._pin(out)
        self._adopt_upload = jax.jit(_adopt_paged_fn, donate_argnums=(0,))
        if b.enable_prefix_caching and b.prefix_index == "radix":
            # Tiered KV cache (serve/kvtier.py): token-block radix index
            # with live copy-on-write page sharing + optional host-RAM
            # overflow tier. The index is scheduler-confined like the
            # allocator it extends; device work rides the closures below
            # (all enqueue on the scheduler thread, in program order
            # with the dispatches that read their results).
            from kubeflow_tpu.serve.kvtier import RadixPrefixIndex
            from kubeflow_tpu.serve.paged import copy_pages
            from kubeflow_tpu.serve.storage import kv_fabric_store

            self._kv_copy = jax.jit(
                lambda c, s, d: self._pin(copy_pages(c, s, d)),
                donate_argnums=(0,))
            # Fleet-wide KV fabric third tier: the fabric signature folds
            # every shape/dtype fact a wire blob depends on, so replicas
            # of different models sharing a store root can never adopt
            # each other's pages (the key simply won't match).
            fabric_sig = (f"L{cfg.n_layers}.H{cfg.n_kv_heads}"
                          f".D{cfg.head_dim}.P{self.page_size}"
                          f".{'int8' if self.kv_quant else 'full'}"
                          + (f".latent{cfg.kv_lora_rank}+{cfg.qk_rope_dim}"
                             if cfg.is_latent else ""))
            self._kvtier = RadixPrefixIndex(
                self._allocator, self.page_size,
                host_pages=int(b.host_kv_pages),
                demote_after_s=float(b.kv_demote_after_s),
                migrate_batch_pages=int(b.kv_migrate_batch_pages),
                copy_pages_fn=self._kv_copy_pages,
                upload_pages_fn=self._kv_upload_pages,
                fetch_pages_fn=self._kv_fetch_pages,
                pressure_fn=self._kv_pressure,
                remote_store=kv_fabric_store(b.remote_kv_root),
                remote_after_s=b.kv_remote_after_s,
                remote_deadline_s=b.kv_remote_deadline_s,
                fabric_sig=fabric_sig)
            # Pre-warm the COW-copy trace (a tail copy is always one
            # pow2-padded pair, so this ONE trace covers every live
            # COW): the first mid-traffic divergence must not show up
            # as a steady-state recompile (the F6xx fixed-trace
            # contract the recompile sanitizer audits). The OOB dst
            # drops the write — a no-op dispatch.
            def cow_copy():
                self._kv_copy_pages([0], [-1])
                return self.cache

            self._warm(program_key("kv_copy_pages", 1), cow_copy)
        self._sampler = jax.jit(_sample_batch, static_argnums=(5,))
        # Steps a decode dispatch: sampling happens on-device, and the
        # while_loop exits early when every slot finishes. The two options
        # are CAPS; the length of a round is the scheduler's choice from
        # its own measurements (serve/pacing.py), among the ladder's
        # lengths. num_steps and sample_mode are static: one program a
        # ladder length and sampling mode.
        self.decode_steps = max(1, int(b.decode_steps))
        self.prefill_interleave_steps = max(1, int(b.prefill_interleave_steps))
        self._pacer = RoundPacer(decode_ladder(  # lockfree: scheduler-confined
            self.decode_steps, self.prefill_interleave_steps))

        if on_tpu:
            # (wrapped HERE: the jit constructor above keeps the shape
            # `kftpu lint`'s donation / dispatch-signature rules read)
            self._paged_decode_n = self._introspected(
                "paged_decode", self._paged_decode_n)

        # Speculative decoding (draft + batched verify; serve/spec_decode.py).
        # Greedy rounds draft k tokens per slot and verify all k+1 positions
        # in ONE dispatch — multiple verified tokens per host round-trip at
        # token-identical output. Sampling traffic falls back to the normal
        # decode path (greedy verification is exact for argmax only).
        spec = b.speculative
        self.spec_mode = spec.mode
        self.spec_k = int(spec.k)
        self._spec_ngram_max = int(spec.ngram_max)
        self._spec_ngram_min = int(spec.ngram_min)
        self._draft_cfg: Optional[DecoderConfig] = None
        self._draft_params: Optional[Params] = None
        if self.spec_mode != "off":
            if self.mesh is not None:
                raise ValueError(
                    "speculative decoding is not supported in mesh "
                    "(tensor-parallel) mode yet")
            from kubeflow_tpu.serve.spec_decode import paged_verify_step

            self._verify = jax.jit(
                lambda p, c, t, l, lv: _pin2(
                    paged_verify_step(p, c, t, l, lv, cfg_decode),
                    self._pin),
                donate_argnums=(1,))
        if self.spec_mode == "draft_model":
            from kubeflow_tpu.models.config import preset as _preset
            from kubeflow_tpu.serve.spec_decode import draft_propose

            dconf = dict(spec.draft or {})
            dcfg = _preset(dconf.get("preset", "tiny"),
                           **dconf.get("overrides", {}))
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab {dcfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size} (drafts are token ids — the two "
                    "must share the tokenizer)")
            if dcfg.max_seq_len < self.max_len:
                dcfg = dataclasses.replace(dcfg, max_seq_len=self.max_len)
            self._draft_cfg = dcfg
            self._draft_params = (
                draft_params if draft_params is not None
                else init_decoder_params(jax.random.PRNGKey(seed + 2), dcfg))
            if b.weights_dtype is not None:
                wdt = jnp.dtype(b.weights_dtype)
                self._draft_params = jax.tree.map(
                    lambda x: (x.astype(wdt)
                               if jnp.issubdtype(x.dtype, jnp.floating)
                               else x), self._draft_params)
            # The draft's own KV residency: a page pool of its own planes
            # under the IDENTITY table (slot s owns pages s*mpp ..
            # (s+1)*mpp-1 for good: no allocator, nothing to free). The
            # draft is small, so slots x max_len of its few kv-heads is
            # cheap, and it runs the pool's own decode step and chunk
            # prefill.
            with start(prof.ENGINE_START_POOL):
                self._draft_cache = {  # lockfree: scheduler-confined
                    name: jnp.zeros(shape, dt)
                    for name, (shape, dt) in pool_shapes(
                        dcfg, self.num_slots * self._mpp,
                        self.page_size).items()}
                self._draft_cache["table"] = jnp.arange(
                    self.num_slots * self._mpp, dtype=jnp.int32).reshape(
                        self.num_slots, self._mpp)
            # consumed-context pointer per slot: positions [0, pos) of the
            # TRUE sequence have valid draft KV; reset at (re-)admission
            self._draft_pos = [0] * self.num_slots  # lockfree: scheduler-confined
            self._draft_propose_n = jax.jit(
                lambda p, c, d, dl, dp, lv, n:
                draft_propose(p, c, d, dl, dp, lv, dcfg, n),
                static_argnums=(6,), donate_argnums=(1,))

            def _draft_chunk_fn(p, c, t, s, st, vl):
                # Catch-up: one chunk of slot ``s``'s context into its row
                # of the identity table.
                _, pool = paged_chunk_prefill(
                    p, c, t, c["table"][s][None], st[None], vl[None], dcfg)
                return {**pool, "table": c["table"]}

            self._draft_chunkfn = jax.jit(_draft_chunk_fn,
                                          donate_argnums=(1,))

        # Multi-tenant LoRA adapters (serve/lora.py): the registry owns
        # the packed per-target A/B device buffers and the LRU hot-load/
        # evict slot lifecycle; per-engine-slot assignments below map each
        # running request to its packed slot for the batched dispatch.
        self._lora = None            # lockfree: scheduler-confined (buffers)
        self._slot_aidx = [-1] * self.num_slots    # lockfree: scheduler-confined
        self._slot_aname: list[Optional[str]] = [  # lockfree: scheduler-confined
            None] * self.num_slots
        if b.lora.max_adapters:
            if self.mesh is not None:
                raise ValueError(
                    "lora.max_adapters is not supported in mesh "
                    "(tensor-parallel) mode yet")
            from kubeflow_tpu.serve.lora import AdapterRegistry

            self._lora = AdapterRegistry(
                cfg, max_adapters=int(b.lora.max_adapters),
                rank=int(b.lora.rank), targets=tuple(b.lora.targets))
        self.slots: list[Optional[_Slot]] = [None] * self.num_slots  # lockfree: scheduler-confined
        # Device-resident scheduler state (serve/device_state.py): the
        # decode dispatch's [B] carries and the paged page table live on
        # device for the engine's lifetime; a round's host scheduler
        # events sync together, as one upload and one donated program, so
        # steady-state rounds upload nothing (the stats counters prove it).
        with start(prof.ENGINE_START_POOL):
            self._dstate = DecodeState(self.num_slots, mpp=self._mpp)
            if self._weights_relaid_bytes:  # as the pool: see the load path
                self._dstate.arrays, self._dstate.table = _committed(
                    (self._dstate.arrays, self._dstate.table))
        # Pipelined dispatch (double buffering): dispatch round N+1 before
        # consuming round N, keeping at most ONE unconsumed round in flight
        # while the host detokenizes/streams/reaps/admits. Staleness is one
        # round deep: reaps/admissions decided mid-flight take effect next
        # round, and consumption masks slots whose occupant changed.
        self.pipelined = bool(b.pipelined_decode)
        self._rounds: list[_InflightRound] = []  # lockfree: scheduler-confined
        # First-token sampling batched per admit round: chunked-prefill
        # completions park here and one sampler dispatch + ONE host fetch
        # serves them all (_flush_first_tokens).
        # lockfree: scheduler-confined
        self._pending_first: list[tuple[Request, int, int, jax.Array]] = []
        self._last_ready_t: Optional[float] = None  # lockfree: scheduler-confined
        self.decode_rounds = 0          # lockfree: scheduler-confined counter
        self.first_token_fetches = 0    # lockfree: scheduler-confined counter
        # Running sums behind ``counters()``; every key exists from here on.
        self._prefill_phase_sum_s = 0.0     # lockfree: scheduler-confined counter
        self._prefill_phase_n = 0           # lockfree: scheduler-confined counter
        self._decode_steps_dispatched = 0   # lockfree: scheduler-confined counter
        self._decode_tokens_emitted = 0     # lockfree: scheduler-confined counter
        self._decode_context_tokens = 0     # lockfree: scheduler-confined counter
        self._dsa_keys_visible = 0          # lockfree: scheduler-confined counter
        self._dsa_keys_selected = 0         # lockfree: scheduler-confined counter
        self._round_selected = 0            # lockfree: scheduler-confined
        self._prefill_programs_dispatched = 0   # lockfree: scheduler-confined counter
        self._prefill_chunks_dispatched = 0     # lockfree: scheduler-confined counter
        # Of a several-row program's rows: those that carried a FURTHER
        # chunk of a prompt already in the program, and those that carried
        # nothing.
        self._prefill_rows_ahead = 0            # lockfree: scheduler-confined counter
        self._prefill_rows_dead = 0             # lockfree: scheduler-confined counter
        self._prefill_row_programs_dispatched = 0   # lockfree: scheduler-confined counter
        self._prefill_programs_with_end = 0     # lockfree: scheduler-confined counter
        # Positions at which the chunk programs ran the head: a chunk's
        # ``C`` in a ``[C, V]`` program, one a row in a program over rows
        # where any row's logits are read, none where none's are.
        self._prefill_head_positions = 0        # lockfree: scheduler-confined counter
        self._prefill_tokens_dispatched = 0     # lockfree: scheduler-confined counter
        # Prefill programs that carried a decode step, and the live rows
        # those steps had.
        self._mixed_programs_dispatched = 0     # lockfree: scheduler-confined counter
        self._mixed_decode_rows_sum = 0         # lockfree: scheduler-confined counter
        self._state_tail_writes = 0             # lockfree: scheduler-confined counter
        # Admit passes that sent a prefill program; chunks that were due in
        # a pass and waited for a later one (its budget of programs spent).
        self._prefill_passes = 0                # lockfree: scheduler-confined counter
        self._prefill_chunks_deferred = 0       # lockfree: scheduler-confined counter
        self._admit_pass = 0                    # lockfree: scheduler-confined
        # The scheduler's time by phase, always on (obs/profiler.py): every
        # phase of the loop goes through ``_phase``, which adds its own
        # seconds to a running sum and opens the ``hot_span`` of the same
        # name while a capture is active. Its iterations, the decode rounds
        # whose state sync found anything dirty, the rounds left at their
        # cap.
        self._phases = prof.PhaseClock(prof.ENGINE_PHASES)  # lockfree: scheduler-confined
        self._phase = self._phases.phase
        self._sched_iterations = 0              # lockfree: scheduler-confined counter
        self._state_sync_rounds = 0             # lockfree: scheduler-confined counter
        self._decode_rounds_at_cap = 0          # lockfree: scheduler-confined counter
        # Of the scheduler iteration under way: the prefill programs
        # dispatched when it began, the length of the round it consumed.
        self._programs_at_step = 0              # lockfree: scheduler-confined
        self._consumed_k: Optional[int] = None  # lockfree: scheduler-confined
        self.waiting: "queue.Queue[Request]" = queue.Queue()
        self.metrics = EngineMetrics()
        # Bounded admission + queue-delay budget (load shedding): see
        # BatchingSpec — 0/None keep the pre-hardening unbounded behavior.
        self.max_queue = max(0, int(b.max_queue))
        self.queue_delay_budget = (None if b.queue_delay_budget is None
                                   else float(b.queue_delay_budget))
        # Multi-tenant QoS (BatchingSpec.qos): per-class admission quotas
        # and queue-delay budgets; the priority order itself is fixed
        # (core/serving.QOS_PRIORITY). ``qos_preemption`` enables
        # cross-class recompute preemption on top of the page-pressure
        # preemption that always exists.
        self.qos_policies = dict(b.qos.classes)
        self.qos_preemption = bool(b.qos.preemption)
        self._id_gen = itertools.count()
        # Runtime sanitizer (KFTPU_SANITIZE=transfer, legacy =1): run every
        # scheduler step under ``jax.transfer_guard("disallow")``. The
        # engine's transfer contract is that every host↔device move is
        # EXPLICIT (``jnp.asarray`` at admission/sync sites,
        # ``jax.device_get`` at the designed fetch points) — an implicit
        # transfer anywhere in the step is a regression of exactly the
        # class the static device-hygiene rules (kftpu lint, D1xx) catch,
        # so the two cross-check each other. The refcount/lockorder modes
        # live in runtime/sanitize.py + serve/paged.py.
        from kubeflow_tpu.runtime.sanitize import sanitize_modes

        self.sanitize = "transfer" in sanitize_modes()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        # None until stop() runs; False = the scheduler thread outlived its
        # join timeout and is leaked (it may hold live device buffers).
        self.stopped_clean: Optional[bool] = None
        self._programs.warm(self._warm)
        self._warm_decode_ladder()
        # After the ladder: the state lies where a program left it, as
        # every sync of traffic's will find it.
        self._warm(program_key("state_sync", self.num_slots, self._mpp),
                   self._dstate.warm)
        if self._weights_relaid_bytes:
            self._warm_first_tokens()
        self._start.end()

    def _warm(self, program: str, run) -> None:
        """Compile or load, and run once, now, one program of the engine's
        own set: ``run`` dispatches it and hands back what to wait for.
        One ``engine.start.warm`` phase a program (under a capture a span
        with ``program=``, the program's key in ``program_kernels`` where it
        has one), its seconds kept in ``start_programs()``."""
        clock, warm = self._start, prof.ENGINE_START_WARM
        before = clock.total(warm)
        with clock.phase(warm, prof.active() and {"program": program}):
            jax.block_until_ready(run())
        self._start_programs[program] = clock.total(warm) - before

    def _warm_decode_ladder(self) -> None:
        """Compile and run once, now, the greedy decode program at every
        length of the ladder, over DEAD rows (no live slot: the while_loop
        runs zero steps and writes nothing). Which lengths traffic reaches
        depends on what the scheduler measures, so no warm-up of a caller's
        can be relied on to reach them all; the program set is the engine's
        own, and fixed from here on. The other sampling modes compile at
        their first use. The key is not drawn from: a sampled stream is
        what it was."""
        for k in self._pacer.ladder:
            self._warm(program_key("paged_decode", k, "greedy"),
                       lambda: self._dispatch_decode(k, "greedy", self._rng))

    def _warm_first_tokens(self) -> None:
        """Compile and run once, now, the greedy first-token sampler at
        every width an admit pass can finish prefills in (the powers of two
        up to the slots), over a logits row as a chunk program of COMMITTED
        parameters returns it. Which widths traffic reaches depends on how
        arrivals fall, and a caller that warms them with rows of its own
        making (uncommitted ones) warms other programs than these. The key
        is not drawn from."""
        row = _committed(jnp.zeros((self.cfg.vocab_size,), jnp.float32))
        greedy = SamplingParams(temperature=0.0)
        width = 1
        while width <= self.num_slots:
            self._warm(program_key("sample_first", width, "greedy"),
                       lambda: self._sample_first(
                           [row] * width, [greedy] * width, self._rng))
            width *= 2

    # -- mesh-mode helpers -----------------------------------------------------

    def _zeros(self, shape, dtype, scale: bool = False) -> jax.Array:
        """KV-cache allocation. Mesh mode materializes each shard directly on
        its device (a host-side full array would bound the servable model by
        ONE chip's HBM — the exact limit mesh mode removes)."""
        sh = self._cache_scale_sh if scale else self._cache_sh
        if sh is None:
            return jnp.zeros(shape, dtype)
        return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sh)()

    def _refuse_unsupported(self, cfg: DecoderConfig, b) -> None:
        """Name, when the engine is built, each mechanism that cannot take
        this model yet: a latent page pool (one row a token for all heads)
        has no per-head K and V, nor have K/V heads packed into one row,
        which the int8 pool's scales, the handoff payload, the host tier's
        wire format and the speculative verify step are written over; conv
        layers keep their state a page, and window layers a ring of pages a
        sequence, in planes of their own, which those do not carry; a stack
        of more than one kind or group of layers is not one
        ``params["layers"]``, which those and the weight quantizer, the
        adapter buffers and the mesh's sharding walk; an expert layer that
        holds a share of its experts is one chip's part of a group and has
        no form over a mesh. Prefix reuse is taken over conv layers (it
        resumes at page boundaries only, ``_kv_match``) and REFUSED over
        window layers: a ring that its sequence overwrites cannot be shared
        read-only, and a match would need the ring's pages as they stood at
        the match; and over linear-attention layers, whose state a sequence
        is one matrix a head that every token rewrites: a match would need
        it AS IT STOOD at the match (a snapshot a page: ROADMAP Reach 11),
        and the speculative verify step cannot roll it back; and over
        state-space (ssm) layers for the same two reasons, and over parallel
        layers, whose SSD state a sequence is such a matrix beside the
        layer's own K and V: a matched page's K and V could be shared, the
        state as it stood at the match cannot; and over ssd layers, the same
        mixer as a block's only operator, for the same two reasons (the
        prediction module of such a model stays unbuilt: its drafts'
        verification would have to roll a sequence's state back). A block
        of one sublayer and experts behind a latent projection change the
        tree the quantizer, the adapter buffers and the mesh's sharding
        walk. Gated memory
        units and cross layers keep nothing, but read what a layer in front
        of them computed, which none of the mechanisms above carries, and
        differential attention's paired K/V rows are not the ``[KV, Dh]``
        those are written over. An indexer's key a token is a second plane
        under the latent row's page ids, which none of them carries either;
        prefix reuse is TAKEN over it (both planes are rows a token under
        one page id: a matched page's are both there, and ``copy_pages``
        walks every plane). An expert layer on a shortcut makes a scan unit
        a PAIR of blocks whose expert layer is the pair's ("moe" beside two
        "mlp"), which the quantizer, the adapter buffers, the mesh's
        sharding and the speculative verify's draft walk do not know; zero
        experts are the sorted and the dense expert path's, which a
        capacity-dispatch decode (``moe_decode_impl="zero_drop"``) is not;
        prefix reuse is TAKEN over both (a matched page's latent rows are
        what they would be written as: an expert layer keeps nothing). The
        indexer's kernels take a chunk's queries a
        tile of ``INDEX_QUERY_TILE`` a row of their walk, so a longer chunk
        is whole tiles (in the gathered form too: one spec serves on
        either)."""
        from kubeflow_tpu.ops.paged_attention import INDEX_QUERY_TILE

        chunk = max(0, int(b.chunked_prefill_tokens)) or int(b.page_size)
        what = [name for name, has in (
            ("a latent (ckv) KV pool", cfg.is_latent),
            ("an indexer whose key a token lives in the page pool beside "
             "the latent row (the idx plane)", bool(cfg.index_topk)),
            ("convolution layers whose state lives in the page pool",
             bool(cfg.layers_of("conv"))),
            ("window layers that keep a ring of pages a sequence",
             bool(cfg.layers_of("window"))),
            ("linear-attention layers whose state a sequence lives in the "
             "page pool", bool(cfg.layers_of("linear"))),
            ("state-space (ssm) layers whose state a sequence lives in the "
             "page pool", bool(cfg.layers_of("ssm"))),
            ("parallel layers (attention beside a Mamba-2 mixer) whose SSD "
             "state a sequence lives in the page pool beside the layer's K "
             "and V", bool(cfg.layers_of("parallel"))),
            ("ssd layers (a Mamba-2 mixer alone) whose state a sequence "
             "lives in the page pool", bool(cfg.layers_of("ssd"))),
            ("blocks of one sublayer (no feed-forward part)",
             bool(cfg.ffn_free)),
            (f"experts behind a latent projection of {cfg.moe_latent_dim}",
             bool(cfg.moe_latent_dim)),
            ("gated memory units and cross-attention layers that read "
             "another layer's output and cache", bool(cfg.stateless_tail)),
            ("differential attention over paired K/V heads",
             cfg.diff_attention),
            ("K/V heads packed into one pool row", cfg.kv_heads_packed),
            ("leading dense layers", bool(cfg.leading_dense_layers)),
            (f"expert layers that hold {cfg.experts_held} of "
             f"{cfg.num_experts} experts", bool(cfg.experts_held)),
            ("an expert layer on a shortcut beside the dense MLPs of a "
             "pair of blocks", cfg.moe_shortcut),
            (f"{cfg.zero_experts} zero experts (router outputs that are "
             "the identity)", bool(cfg.zero_experts))) if has]
        if not what:
            return
        refused = {
            "kv_cache_dtype=int8 (int8 KV)": b.kv_cache_dtype is not None,
            f"role={b.role!r} (handoff export/adopt)": b.role != "unified",
            "host_kv_pages / remote_kv_root (the host tier's wire format)":
                bool(b.host_kv_pages) or b.remote_kv_root is not None,
            f"speculative.mode={b.speculative.mode!r} (speculative verify)":
                b.speculative.mode != "off",
            f"lora.targets={list(b.lora.targets)} (LoRA targets that do "
            "not exist in this block)": bool(b.lora.max_adapters),
            "quantize=int8 (weight quantization)": b.quantize is not None,
            "a mesh (tensor-parallel serving)": self.mesh is not None,
            f"chunked_prefill_tokens={chunk} (a chunk of more than "
            f"{INDEX_QUERY_TILE} queries that is no whole number of the "
            f"indexer's tiles of {INDEX_QUERY_TILE})":
                bool(cfg.index_topk) and chunk > INDEX_QUERY_TILE
                and chunk % INDEX_QUERY_TILE != 0,
            f"moe_prefill_impl={b.moe_prefill_impl!r} / moe_decode_impl="
            f"{b.moe_decode_impl!r} (a capacity buffer has no row for an "
            "expert without weights: zero experts are the sorted path's)":
                bool(cfg.zero_experts) and "dispatch" in (
                    self._cfg_prefill.moe_impl, self._cfg_decode.moe_impl),
            "enable_prefix_caching (prefix reuse over window layers: a "
            "ring its sequence overwrites cannot be shared)":
                bool(cfg.layers_of("window")) and b.enable_prefix_caching,
            "enable_prefix_caching (prefix reuse over linear-attention "
            "layers: a match needs the state as it stood at the match)":
                bool(cfg.layers_of("linear")) and b.enable_prefix_caching,
            "enable_prefix_caching (prefix reuse and the radix copy-on-write "
            "tail over ssm layers: a match needs the state as it stood at "
            "the match)":
                bool(cfg.layers_of("ssm")) and b.enable_prefix_caching,
            "enable_prefix_caching (prefix reuse and the radix copy-on-write "
            "tail over parallel layers: a match needs the SSD state as it "
            "stood at the match)":
                bool(cfg.layers_of("parallel")) and b.enable_prefix_caching,
            "enable_prefix_caching (prefix reuse and the radix copy-on-write "
            "tail over ssd layers: a match needs the SSD state as it stood "
            "at the match)":
                bool(cfg.layers_of("ssd")) and b.enable_prefix_caching,
        }
        hit = [name for name, on in refused.items() if on]
        if hit:
            raise ValueError(
                f"this model has {', '.join(what)}; not supported with it "
                "yet: " + "; ".join(hit))

    def _pin(self, cache: dict) -> dict:
        if self._cache_sh is None:
            return cache
        pins = {"k": self._cache_sh, "v": self._cache_sh,
                "ks": self._cache_scale_sh, "vs": self._cache_scale_sh}
        return {k: (jax.lax.with_sharding_constraint(v, pins[k])
                    if k in pins else v)
                for k, v in cache.items()}

    # -- submission ------------------------------------------------------------

    def sched_phase_seconds(self) -> dict[str, float]:
        """The scheduler thread's running seconds by phase, exclusive (a
        phase's own, its children's taken out), keyed by the phase's short
        name (``engine.sync_state`` is ``sync_state``), and ``other``: the
        loop's time under no phase. Together the loop's wall time."""
        return {name.rpartition(".")[2]: seconds
                for name, seconds in self._phases.snapshot().items()}

    def counters(self) -> dict[str, float]:
        """One total snapshot of the engine's running sums and counts: a
        flat dict whose keys all exist from construction on, whatever the
        traffic did, and whose values only ever grow (``slots`` is the
        constant the occupancy is taken against). A reader takes two
        snapshots and works on the differences. The histograms' sums go in
        as ``EngineMetrics`` keeps them."""
        m = self.metrics
        _, _, qd_sum, qd_n = m.queue_delay_histogram()
        _, _, hg_sum, hg_n = m.host_gap_histogram()
        phases = self.sched_phase_seconds()
        return {
            "slots": self.num_slots,
            "queue_delay_sum_s": qd_sum, "queue_delay_n": qd_n,
            "host_gap_sum_s": hg_sum, "host_gap_n": hg_n,
            "preemptions": m.preemptions, "requests_shed": m.requests_shed,
            "requests_completed": m.requests_completed,
            "tokens_generated": m.tokens_generated,
            "decode_rounds": self.decode_rounds,
            "first_token_fetches": self.first_token_fetches,
            # admission to first token, over first admissions
            "prefill_phase_sum_s": self._prefill_phase_sum_s,
            "prefill_phase_n": self._prefill_phase_n,
            # sum of k_steps; tokens the consumed rounds handed to requests
            "decode_steps_dispatched": self._decode_steps_dispatched,
            "decode_tokens_emitted": self._decode_tokens_emitted,
            # rounds dispatched at the cap in force (the length the two
            # options set), not shorter by the scheduler's choice
            "decode_rounds_at_cap": self._decode_rounds_at_cap,
            # the scheduler's time by phase (``sched_phase_seconds``:
            # ``sched_sync_state_sum_s``, ... ``sched_other_sum_s``), and
            # its iterations
            **{f"sched_{phase}_sum_s": seconds
               for phase, seconds in phases.items()},
            "sched_iterations": self._sched_iterations,
            # the loop's wall time less the time blocked fetching from the
            # device and waiting for work: the host's own share, and what a
            # round's length is chosen to hide
            "sched_host_busy_sum_s": sum(phases.values())
            - phases["fetch"] - phases["idle"],
            # what the state syncs sent (``DecodeState.stats``: dirty
            # slots, dirty page-table rows, and the programs that carried
            # them: one a sync, whatever it held) and the decode rounds
            # whose sync found anything dirty
            "state_slot_syncs": self._dstate.stats["slot_syncs"],
            "state_row_syncs": self._dstate.stats["table_row_syncs"],
            "state_sync_dispatches": self._dstate.stats["sync_dispatches"],
            "state_sync_rounds": self._state_sync_rounds,
            # cache rows the dispatched steps attend to, summed over the
            # live slots and the steps of every round
            "decode_context_tokens": self._decode_context_tokens,
            # where an indexer selects the keys attention reads
            # (``cfg.index_topk``; 0 and 0 elsewhere): the keys every
            # dispatched query could see (``t + 1`` for a query at position
            # ``t``, chunk rows and decode rows alike, a layer's worth) and
            # those of them it attends to (``min(index_topk, t + 1)``),
            # summed on the host from the rows' positions
            "dsa_keys_visible": self._dsa_keys_visible,
            "dsa_keys_selected": self._dsa_keys_selected,
            # chunk-prefill programs dispatched, the chunks they carried, a
            # row each (their ratio: how full the programs went) and the
            # real tokens of those chunks, padding excluded
            "prefill_programs_dispatched": self._prefill_programs_dispatched,
            "prefill_chunks_dispatched": self._prefill_chunks_dispatched,
            "prefill_tokens_dispatched": self._prefill_tokens_dispatched,
            # of those chunks, the ones that went AHEAD: a further chunk of
            # a prompt that had a row in the same program already, in a row
            # no due prefill wanted (``_rows_of``); and the rows of the
            # several-row programs that carried nothing (behind a prompt's
            # last chunk, or no page to be had; every spare row where the
            # engine sends no chunk ahead). Together with the chunks: the
            # rows of every program sent
            "prefill_rows_ahead": self._prefill_rows_ahead,
            "prefill_rows_dead": self._prefill_rows_dead,
            # of those programs, the ones that carried a decode step of the
            # live slots (one program an iteration where it would have been
            # two; over ``prefill_programs_dispatched``: how often), and
            # the live rows those steps had
            "mixed_programs_dispatched": self._mixed_programs_dispatched,
            "mixed_decode_rows_sum": self._mixed_decode_rows_sum,
            # of those programs, the ones sent through the program over
            # several prompts' rows (whose head runs at one position a
            # row), and the ones in which some row ended its prompt (the
            # only programs whose logits anybody reads: the program over
            # rows runs its head in no other)
            "prefill_row_programs_dispatched":
                self._prefill_row_programs_dispatched,
            "prefill_programs_with_end": self._prefill_programs_with_end,
            "prefill_head_positions": self._prefill_head_positions,
            # scheduler iterations that sent a prefill program (programs
            # over passes: the programs every live stream waited for at
            # once), and the chunks that were due in a pass and waited for
            # a later one because its budget of programs was spent
            "prefill_passes": self._prefill_passes,
            "prefill_chunks_deferred": self._prefill_chunks_deferred,
            # constants: content bytes a token holds over all layers that
            # keep rows a token, the cache's size on the device (every
            # plane), and of it the planes that hold conv layers' state
            "kv_bytes_per_token": self._kv_bytes_per_token,
            "kv_pool_bytes": self._kv_pool_bytes,
            "state_pool_bytes": self._state_pool_bytes,
            # of the cache's size the planes that hold an indexer's key a
            # token beside the latent row (0 without an indexer)
            "index_pool_bytes": self._index_pool_bytes,
            # of the cache's size the window layers' planes (a ring of
            # ``kv_window_pages_a_sequence`` pages a sequence; 0 and 0 for a
            # stack without window layers) and the global layers' (every
            # page of a sequence)
            "kv_window_pool_bytes": self._kv_window_pool_bytes,
            "kv_global_pool_bytes": self._kv_global_pool_bytes,
            "kv_window_pages_a_sequence":
                self._cfg_decode.window_ring_pages,
            # of the cache's size the planes that hold an entry a SEQUENCE
            # (the linear, ssm and parallel layers' recurrent states and
            # convolution tails, ``slots`` entries; 0 for a stack without
            # such layers) and
            # every other plane (rows a token, tails a page)
            "kv_sequence_pool_bytes": self._kv_sequence_pool_bytes,
            # layers that keep no K/V of their own and attend over ONE
            # layer's planes (the cross layers; 0 for every other stack)
            "kv_layers_sharing": self.cfg.layers_of("cross"),
            "kv_token_pool_bytes":
                self._kv_pool_bytes - self._kv_sequence_pool_bytes,
            # sequences whose state was started from zeros (a chunk at
            # position 0: admissions and a preempted request's second
            # prefill); 0 without linear layers. The entries programs move
            # follow from the counters above: a chunk writes one a linear
            # layer and reads one unless it starts a sequence, a decode step
            # reads and writes one a layer a live stream
            "state_sequences_started": self._state_sequences_started,
            # bytes of sequence entries that decode steps (a program's own
            # and those a chunk program carries) read AND wrote: 2 x a live
            # row's entries over the layers that keep one, a step (beside
            # ``decode_steps_dispatched``); 0 without such layers
            "state_bytes_stepped": self._state_bytes_stepped,
            # (token, choice) rows the expert layers of every program
            # routed, and those of them whose expert is held here and was
            # computed (0 and 0 where every expert is held), as of the last
            # decode round fetched
            "expert_rows_routed": self._expert_rows[0],
            "expert_rows_held": self._expert_rows[1],
            # those of the routed rows that chose a zero expert (the
            # identity: no matrix work; 0 where the router has none)
            "expert_rows_zero": self._expert_rows[2],
            # page-end tails of the conv layers' state that chunk-prefill
            # programs wrote: one for each page a chunk's tokens touched
            "state_tail_writes": self._state_tail_writes,
            # a constant: bytes of the parameters held in another layout
            # than the default, laid out once at load as the programs read
            # them (serve/weight_layout.py)
            "weights_relaid_bytes": self._weights_relaid_bytes,
            # the constructor's seconds by start phase (``engine.start.*``:
            # ``start_place_sum_s``, ... ``start_other_sum_s``; together
            # its wall time): constants once the engine is built
            **{f"start_{phase}_sum_s": seconds
               for phase, seconds in self.start_phase_seconds().items()},
            # what JAX compiled, loaded from its cache, traced and lowered
            # in this PROCESS so far (runtime/bootstrap.py::watch_compiles):
            # in front of a window the start-up's; still over a window that
            # compiles nothing
            **compile_counters(),
        }

    def start_phase_seconds(self) -> dict[str, float]:
        """The constructor's seconds by start phase, exclusive, keyed by
        the phase's short name (``engine.start.place`` is ``place``), and
        ``other``: the constructor's time under none. Together its wall
        time."""
        return {name.rpartition(".")[2]: seconds
                for name, seconds in self._start.snapshot().items()}

    def start_programs(self) -> dict[str, float]:
        """``{program: seconds}`` of the programs the constructor compiled
        or loaded and ran once, in the order it ran them."""
        return dict(self._start_programs)

    def queue_depth(self) -> int:
        """Requests waiting for a slot (admission queue + scheduler-side
        backlog). Approximate under concurrency — good enough for both the
        admission bound and the metrics gauge."""
        return self.waiting.qsize() + len(self._backlog)

    def class_queue_depth(self, qos: str) -> int:
        """Waiting requests of ONE class (admission queue + backlog) — the
        per-class admission quota's input. Approximate under concurrency,
        exactly like ``queue_depth``."""
        return (sum(1 for r in list(self.waiting.queue) if r.qos == qos)
                + sum(1 for r in list(self._backlog) if r.qos == qos))

    def _lower_class_waiting(self, qos: str) -> bool:
        """Any waiting request of a STRICTLY lower class than ``qos``?
        (The shed-lowest-first question: a full queue 429s the arrival
        only when nothing more sheddable is already waiting.)"""
        p = QOS_PRIORITY[qos]
        return any(QOS_PRIORITY.get(r.qos, p) > p
                   for r in list(self.waiting.queue) + list(self._backlog))

    def kv_pages_in_use(self) -> int:
        """RESIDENT-REFERENCED KV pages — pages live requests hold
        references to right now. Cached
        ref-0 prefix content is deliberately excluded: it is freely
        evictable, so it is capacity, not load (the decode router's
        placement signal must not count it). The chaos-suite invariant:
        quiescent engine -> 0 — every reap/finish path freed exactly
        what admission allocated."""
        return self._allocator.in_use()

    def kv_pages_cached(self) -> int:
        """Ref-0 pages still holding reusable prefix content (the
        reclaimable LRU) — the freely-evictable half of the old
        ``resident`` notion, split out so dashboards and the router can
        tell load from cache."""
        return self._allocator.cached()

    def kv_pages_host(self) -> int:
        """Pages resident in the host-RAM overflow tier (0 when the
        tier is off)."""
        return 0 if self._kvtier is None else \
            self._kvtier.host_pages_resident()

    def kv_pages_remote(self) -> int:
        """Pages this replica's radix tree currently indexes in the
        remote store tier (0 when the third tier is off)."""
        return 0 if self._kvtier is None else \
            self._kvtier.remote_pages_resident()

    def kv_tier_pressure(self) -> float:
        """The tier's demotion-urgency ratio (>= 1.0 = urgent) — the
        SAME folded signal the migration scan acts on, exported so the
        split-pool SLO autoscaler sees third-tier pressure (a decode
        pool churning KV through the store needs replicas, not just a
        pool fighting its own TTFT target)."""
        return 0.0 if self._kvtier is None else float(self._kvtier.pressure())

    def drain_kv_to_remote(self, timeout_s: float = 10.0) -> int:
        """Scale-down drain hook: demote + publish every cached prefix
        this engine still holds to the remote tier so conversations
        survive the replica leaving the fleet. Call when idle (the
        ISVC controller drains traffic first). Returns pages published."""
        if self._kvtier is None:
            return 0
        return self._kvtier.spill_all_to_remote(timeout_s)

    def kv_tier_stats(self) -> dict:
        """Radix/tier counters (empty dict under the flat prefix index):
        hits, matched/COW token counts, demotions/promotions, host
        occupancy — the /metrics tier series' source."""
        return {} if self._kvtier is None else self._kvtier.snapshot()

    def kv_pool_density(self) -> dict:
        """Page-pool capacity accounting: token capacity, pool HBM bytes
        (int8 payload + scale rows when quantized), and tokens-per-MiB —
        the density series the int8-KV HBM claim (~1.9x resident tokens at
        equal HBM) is measured from."""
        pool_bytes = self._kv_pool_bytes
        tokens = self._num_pages * self.page_size
        return {
            "quant": int(self.kv_quant),
            "pool_bytes": int(pool_bytes),
            "token_capacity": int(tokens),
            "tokens_per_mib": tokens / (pool_bytes / 2**20),
        }

    def submit(self, prompt_tokens: list[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None, *,
               deadline: Optional[float] = None,
               trace_parent=None, qos: str = QOS_DEFAULT,
               handoff: Optional[bool] = None,
               adapter: Optional[str] = None) -> Request:
        if not prompt_tokens:
            raise ValueError("empty prompt")
        if len(prompt_tokens) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} >= max_seq_len {self.max_len}")
        if qos not in QOS_PRIORITY:
            raise ValueError(
                f"unknown QoS class {qos!r}; known: {sorted(QOS_PRIORITY)}")
        if adapter is not None:
            # Unknown model ids fail HERE, at the door (the protocol
            # layers map KeyError to HTTP 404 / gRPC NOT_FOUND) — the
            # scheduler only ever sees registered adapters. Hot-loading
            # happens at admission, on the scheduler thread.
            if self._lora is None:
                raise KeyError(
                    f"unknown model {adapter!r}: this engine serves no "
                    "adapters (lora.max_adapters=0)")
            if not self._lora.known(adapter):
                raise KeyError(
                    f"unknown model {adapter!r}: adapter not registered")
            if handoff:
                raise ValueError(
                    "adapter requests cannot hand off (adapter KV has "
                    "no cross-engine placement contract)")
        pol = self.qos_policies.get(qos)
        if pol is not None and pol.max_queue \
                and self.class_queue_depth(qos) >= pol.max_queue:
            # Per-class quota: one tenant tier's burst hits its own
            # ceiling without ever crowding the shared queue.
            self.metrics.note_shed(qos)
            raise EngineOverloaded(
                f"{qos} admission quota full "
                f"(max_queue={pol.max_queue})", qos=qos)
        if self.max_queue:
            depth = self.queue_depth()
            if depth >= self.max_queue and not self._lower_class_waiting(qos):
                # Shed-lowest-first: the arrival is itself the most
                # sheddable class present, so IT takes the 429. When a
                # strictly lower class waits, over-admit instead — the
                # scheduler sheds that lower entry at its next step
                # (_enforce_queue_bound), so batch always 429s before
                # interactive ever does.
                self.metrics.note_shed(qos)
                raise EngineOverloaded(
                    f"admission queue full ({depth} >= "
                    f"max_queue={self.max_queue})", qos=qos)
        # Disaggregated default: a prefill-role engine hands off at the
        # first token unless the caller says otherwise (handoff=False is
        # the unified-fallback local decode).
        wants_handoff = (self.role == "prefill" if handoff is None
                         else bool(handoff))
        if wants_handoff and self.cfg.is_latent:
            raise ValueError(
                "handoff export carries per-head K and V; a latent "
                "(ckv) pool is not supported yet")
        req = Request(prompt_tokens=list(prompt_tokens),
                      params=params or SamplingParams(),
                      id=request_id or f"req-{next(self._id_gen)}",
                      deadline=deadline, trace_parent=trace_parent, qos=qos,
                      handoff_requested=wants_handoff, adapter=adapter)
        _span_open(req, "engine.queued", prompt_tokens=len(prompt_tokens),
                   qos=qos)
        self.waiting.put(req)
        self._wake.set()
        return req

    def submit_handoff(self, payload, *, deadline: Optional[float] = None,
                       trace_parent=None) -> Request:
        """Adopt a handed-off request (decode side of serve/handoff.py).

        The request is born mid-lifecycle: its prompt KV arrives in the
        payload, its first token is already emitted client-side by the
        prefill replica. ``prompt_tokens`` carries ``prompt +
        [first_token]`` so the slot invariant (the last token's KV is
        not yet written) and the recompute-preemption fold-back both
        hold exactly as for a locally-prefilled request. Admission
        uploads the KV into this engine's own pool instead of running
        prefill; the emitted stream starts at the SECOND token."""
        payload.validate()
        if self.cfg.is_latent:
            raise ValueError(
                "handoff adopt carries per-head K and V; a latent "
                "(ckv) pool is not supported yet")
        want = "int8" if self.kv_quant else None
        if payload.cache_dtype != want:
            # Mixed-dtype fleets fail loudly at the boundary (the caller
            # recomputes locally) instead of misreading page bytes.
            raise ValueError(
                f"handoff cache-dtype mismatch: payload carries "
                f"{payload.cache_dtype or 'full-dtype'} KV, engine pool is "
                f"{want or 'full-dtype'}")
        plen = payload.kv_len
        if plen + 1 >= self.max_len:
            raise ValueError(
                f"handoff KV length {plen} does not fit max_seq_len "
                f"{self.max_len}")
        expect = (self.cfg.n_layers, plen, self.cfg.n_kv_heads,
                  self.cfg.head_dim)
        if tuple(payload.kv_k.shape) != expect:
            raise ValueError(
                f"handoff KV shape {payload.kv_k.shape} != {expect}")
        if payload.qos not in QOS_PRIORITY:
            raise ValueError(f"unknown QoS class {payload.qos!r}")
        params = SamplingParams(
            max_new_tokens=payload.max_new_tokens,
            temperature=payload.temperature, top_k=payload.top_k,
            top_p=payload.top_p, stop_token=payload.stop_token)
        req = Request(
            prompt_tokens=list(payload.prompt_tokens) + [payload.first_token],
            params=params, id=payload.request_id, deadline=deadline,
            trace_parent=trace_parent, qos=payload.qos, adopt=payload)
        _span_open(req, "engine.queued", prompt_tokens=plen, qos=payload.qos,
                   adopted=True)
        self.waiting.put(req)
        self._wake.set()
        return req

    def complete_handoff(self, request_id: str) -> None:
        """Decode side acked: release the exported pages (marshalled to
        the scheduler thread — safe from any thread)."""
        self._handoff_release.put((request_id, True))
        self._wake.set()

    def fail_handoff(self, request_id: str) -> None:
        """Decode side never acked: release the hold and count the
        failure — the caller recomputes (re-submits locally)."""
        self._handoff_release.put((request_id, False))
        self._wake.set()

    def _introspected(self, name: str, jitted):
        """Wrap ``jitted`` so that the FIRST dispatch of each variant (the
        token block's shape for a prefill, plus the static arguments:
        context pages, steps per dispatch, sample mode) records the Pallas
        kernels in the program's lowered text into ``program_kernels`` —
        what the chip was given, not what the config asked for. The
        lowering shares its trace with the dispatch that follows, and
        happens beside that variant's compile, never in steady state. Only
        applied on the TPU: elsewhere the kernels interpret and a lowered
        program names none."""
        from kubeflow_tpu.runtime.device_report import lowered_kernel_calls

        def dispatch(*args):
            # args[2]: the token block of a prefill, the state of a decode.
            variant = (["x".join(map(str, args[2].shape))]
                       if isinstance(args[2], jax.Array) else [])
            variant += [a for a in args if isinstance(a, (int, str))]
            key = program_key(name, *variant)
            if key not in self.program_kernels:
                self.program_kernels[key] = lowered_kernel_calls(
                    jitted, *args)
            return jitted(*args)

        return dispatch

    # -- scheduler -------------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        reserved = {ch.slot for ch in self._chunkings} \
            | {slot for _, slot, _, _ in self._pending_first}
        for i, s in enumerate(self.slots):
            if s is None and i not in reserved:
                return i
        return None

    def _next_key(self) -> jax.Array:
        self._rng, k = jax.random.split(self._rng)
        return k

    def _sample_first(self, rows: list, sampling: list, key) -> jax.Array:
        """ONE sampler dispatch over the last logits rows of ``len(rows)``
        prefills, stacked and padded to the next power of two so the
        sampler trace set stays log-bounded: their first tokens (and the
        padding's), on the device."""
        n = len(rows)
        width = 1
        while width < n:
            width *= 2
        padded = sampling + [SamplingParams()] * (width - n)
        return self._sampler(
            jnp.stack(rows + [rows[-1]] * (width - n)), key,
            jnp.asarray([p.temperature for p in padded], jnp.float32),
            jnp.asarray([p.top_k for p in padded], jnp.int32),
            jnp.asarray([p.top_p for p in padded], jnp.float32),
            _mode_for(sampling))

    def _flush_first_tokens(self) -> None:
        """ONE sampler dispatch + ONE host fetch for the first tokens of
        every prefill the admit pass finished (``_pending_first``, which
        keeps their slots reserved until here), then admit each request
        into its slot: a ``device_get`` a request would serialize every
        admission behind it."""
        if not self._pending_first:
            return
        items, self._pending_first = self._pending_first, []
        n = len(items)
        with self._phase(prof.ENGINE_SAMPLE_FIRST,
                         prof.active() and {"n": n}):
            firsts = self._sample_first(
                [it[3] for it in items], [it[0].params for it in items],
                self._next_key())
            # The fetch below blocks until the prefill is done, and that
            # queues behind the decode round in flight: the round's tokens
            # are ready first. They go out before the wait, not after it,
            # or every live stream waits a second chunk's time for a token
            # the device has had all along. (Where the pass's program
            # carried a round, the next round goes out first and stays in
            # flight: ``_round_ahead``.)
            self._consume_rounds(keep=int(self._round_ahead()))
            # A wait for the device like the round's own fetch, and named
            # like it.
            with self._phase(prof.ENGINE_FETCH,
                             prof.active() and {"first": n}):
                vals = jax.device_get(firsts)
            self.first_token_fetches += 1
            for j, (req, slot_idx, plen, _) in enumerate(items):
                self._admit_with_token(req, slot_idx, plen, int(vals[j]))

    def _admit_with_token(self, req: Request, slot_idx: int, plen: int,
                          tok: int) -> None:
        if req.trace_parent is not None:
            # prefill → decode: the first token is out. A handoff-bound
            # request opens NO decode span here — its decode phase runs
            # on the adopting engine, and the server's handoff span fills
            # the gap in the same trace.
            _span_close(req, prompt_tokens=plen)
            if not req.handoff_requested:
                _span_open(req, "engine.decode", slot=slot_idx)
        req.tokens_ready_time = time.monotonic()
        if req.first_token_time is None:
            req.first_token_time = req.tokens_ready_time
            if req.admitted_time is not None:
                self._prefill_phase_sum_s += \
                    req.first_token_time - req.admitted_time
                self._prefill_phase_n += 1
        req.output_tokens.append(tok)
        req.stream.put(tok)
        # generated counts ALL emitted tokens — on re-admission after a
        # recompute preemption the budget picks up where it left off.
        self.slots[slot_idx] = _Slot(request=req, length=plen,
                                     last_token=tok,
                                     generated=len(req.output_tokens),
                                     admit_seq=next(self._admit_seq))
        # New occupant: its device-resident decode state and its
        # page-table row sync as deltas at the next dispatch.
        self._dstate.mark_slot(slot_idx)
        self._dstate.mark_row(slot_idx)
        if self._draft_cfg is not None:
            # Fresh occupant: the draft model has consumed none of it yet
            # (the first spec round runs a catch-up prefill).
            self._draft_pos[slot_idx] = 0
        done = self._finish_if_done(slot_idx)
        if not done and req.handoff_requested:
            # Prefill role: the first token is out and decode remains —
            # export the slot's KV instead of decoding locally.
            self._export_handoff(slot_idx)

    def _reserve_chunk_pages(self, ch: "_Chunking") -> bool:
        """Pages for ``ch``'s next chunk. False when page-pool
        pressure defers the chunk to a later step; the other in-flight
        prefills go on without it."""
        req, slot_idx = ch.request, ch.slot
        real = min(self.chunk_size, len(req.prompt_tokens) - ch.pos)
        if self._ensure_pages(slot_idx, ch.pos + real):
            ch.stalls = 0
            return True
        # Pool pressure. A stalled chunking holds pages the decode
        # preemption path can't see (its slot is None), so two growing
        # prefills could deadlock each other: after a few starved attempts,
        # abort this one — release its pages and requeue through the
        # preempted lane, whose admission gate waits for room for the
        # ENTIRE remaining run.
        ch.stalls += 1
        if ch.stalls >= 3:
            self._chunkings.remove(ch)
            # Chunks already written are real prefix KV — index them
            # before the pages release, so the resume's match skips
            # straight back here.
            self._kv_register(req.prompt_tokens, slot_idx, ch.pos)
            self._release_slot_pages(slot_idx)
            self._release_slot_adapter(slot_idx)
            self._preempted.append(req)
            self.metrics.note_preempted(req.qos)
        return False    # otherwise retry next scheduler step

    def _dispatch_chunks(
            self, group: "list[tuple[_Chunking, int]]") -> None:
        """ONE program for the chunks in ``group`` (their pages are
        reserved; which program, how wide: ``ChunkPlan.send``): row ``r``
        carries the chunk of ``group[r]``'s prefill that starts at
        ``group[r]``'s position, the next chunk of a prefill or the one after
        the row in front (``_rows_of``); what is counted and said of a row is
        taken at its own start, and its logits are read only if it ends its
        prompt. Where the program carries the decode step and a slot is live,
        the pass's first program carries the round of this iteration
        (``_ready_round``, as before any round) and says so in both dispatch
        spans; its tokens go the way every round's go (``_rounds``). Whether
        a program of so many chunks carries it is the plan's answer
        (``ChunkPlan.rides``), asked before a round is readied: where it says
        no, the chunks go as they would with no slot live and the iteration's
        step goes out as its own program (``_decode_once``)."""
        C = self.chunk_size
        ride = None
        if self._plan.rides(len(group)) \
                and self._mixed_pass != self._admit_pass:
            ride = self._ready_round(
                [(i, s) for i, s in enumerate(self.slots) if s is not None],
                1)
        sent = self._plan.send(len(group), ride is not None,
                               self._otherwise_idle())
        rows, by_rows = sent.rows, sent.program != "lone"
        # a row: (its prefill, its start, its real tokens)
        group = [(ch, pos, min(C, len(ch.request.prompt_tokens) - pos))
                 for ch, pos in group]
        ends = [pos + real == len(ch.request.prompt_tokens)
                for ch, pos, real in group]
        packed = self._programs.pack(
            [(ch.request.prompt_tokens[pos:pos + real], self._table[ch.slot],
              pos, end) for (ch, pos, real), end in zip(group, ends)], rows)
        sparse = {}
        if self.cfg.index_topk or prof.active():
            # the keys the chunks' queries can see (query ``t``: ``t + 1``)
            sparse["context"] = sum(real * pos + real * (real + 1) // 2
                                    for _, pos, real in group)
        if self.cfg.index_topk:
            # and those the indexer selects (``min(index_topk, t + 1)``)
            sparse["selected"] = sum(
                _keys_selected(pos, real, self.cfg.index_topk)
                for _, pos, real in group)
            self._dsa_keys_visible += sparse["context"]
            self._dsa_keys_selected += sparse["selected"]
        active, mode, gap, context, attrs = ride or (None,) * 5
        with self._phase(prof.ENGINE_PREFILL_DISPATCH, prof.active() and {
                "slot": group[0][0].slot, "pos": group[0][1],
                "chunks": len(group), **sparse, **self._rows_chosen(),
                # of the step the program carries (0 and 0: none rides)
                "live_rows": attrs["live_rows"] if ride else 0,
                "state_bytes": attrs["state_bytes"] if ride else 0}):
            with self._phase(prof.ENGINE_DECODE_DISPATCH,
                             prof.active() and attrs) \
                    if ride else contextlib.nullcontext():
                logits, carried = self._programs.send(
                    sent, packed, mode,
                    [self._slot_aidx[ch.slot] for ch, _, _ in group])
            if ride:
                self._note_round(*carried, active, 1, self._round_cap(),
                                 gap, context, alone=False)
                self._mixed_pass = self._admit_pass
                self._mixed_programs_dispatched += 1
                self._mixed_decode_rows_sum += len(active)
        self._prefill_programs_dispatched += 1
        self._prefill_row_programs_dispatched += rows > 1
        self._prefill_programs_with_end += any(ends)
        # (beside a riding step the one head runs over the chunks' rows too)
        self._prefill_head_positions += (
            rows * (any(ends) or ride is not None) if by_rows else C)
        self._prefill_chunks_dispatched += len(group)
        self._prefill_rows_ahead += sum(
            pos != ch.pos for ch, pos, _ in group)
        self._prefill_rows_dead += rows - len(group)
        self._prefill_tokens_dispatched += sum(
            real for _, _, real in group)
        if self._kv_sequence_pool_bytes:
            self._state_sequences_started += sum(
                pos == 0 for _, pos, _ in group)
        if self._state_pool_bytes:
            pg = self.page_size
            self._state_tail_writes += sum(
                (pos + real - 1) // pg - pos // pg + 1
                for _, pos, real in group)
        for r, (ch, pos, real) in enumerate(group):
            req, plen = ch.request, len(ch.request.prompt_tokens)
            ch.pos = pos + real
            if not ends[r]:
                continue
            self._chunkings.remove(ch)
            # Index the prompt's KV for cross-request reuse — LIVE: the
            # owner keeps decoding while sharers match through these pages
            # (decode writes start at plen, past every claimed position —
            # COW by construction).
            self._kv_register(req.prompt_tokens, ch.slot, plen)
            # The logits of the prompt's true last token: the program over
            # rows returns that position's alone, a row a prompt.
            self._pending_first.append(
                (req, ch.slot, plen,
                 logits[r] if by_rows else logits[real - 1]))

    def _otherwise_idle(self) -> bool:
        """Whether the prefill being sent is all the engine has to do: no
        slot live, no other prefill in flight, nobody waiting (a prompt sent
        alone to an idle engine; a burst after idleness is not)."""
        return (all(s is None for s in self.slots)
                and len(self._chunkings) == 1 and not self._backlog
                and self.waiting.empty())

    def _rows_of(self, group: "list[_Chunking]"
                 ) -> "list[tuple[_Chunking, int]]":
        """The rows of the program that carries the due chunks of ``group``
        (pages reserved): a (prefill, start) each, the prefills' next chunks
        first. Spare rows that the engine may fill (``ChunkPlan.ahead``) go to
        the chunks AFTER those, of the same prefills, the first of ``group``
        first (the oldest of the highest class: it finishes soonest that
        way): ``pos + C``, ``pos + 2C``, ... while the prompt has tokens left
        there and its pages can be had. A further chunk whose pages cannot
        be had leaves its row dead; it is no stall (the chunk that was due
        has its pages) and is due itself in the next pass. A prefill's rows
        stand one behind the other, in the prompt's order: a layer that keeps
        a state a sequence hands it from a row to the row that FOLLOWS it
        (``paged._rows_follow``)."""
        rows = [(ch, ch.pos) for ch in group]
        if not self._plan.ahead:
            return rows
        C = self.chunk_size
        for ch in group:
            plen = len(ch.request.prompt_tokens)
            pos = ch.pos + C
            while len(rows) < self._plan.rows and pos < plen \
                    and self._ensure_pages(ch.slot, min(pos + C, plen)):
                rows.append((ch, pos))
                pos += C
        turn = {id(ch): i for i, ch in enumerate(group)}
        return sorted(rows, key=lambda row: turn[id(row[0])])

    def _advance_chunked(self, due: "Optional[list[_Chunking]]" = None,
                         programs: Optional[int] = None) -> int:
        """The next chunk of the in-flight chunked prefills in ``due`` (all
        of them unless given; decode steps run between calls), in that
        order, as few programs as the engine has rows for: several prompts'
        chunks go to the device together where the plan is several rows
        wide, and every weight is read once for the pass. At most
        ``programs`` programs where given: the prefills past that have no
        turn in this pass. A prefill whose pages cannot be had waits for a
        later pass, fills no row and holds nobody back. Rows left over:
        ``_rows_of``. Returns the chunks dispatched."""
        due = list(self._chunkings) if due is None else due
        width = self._plan.rows
        room = len(due) if programs is None else programs * width
        ready: list[_Chunking] = []
        for ch in due:
            if len(ready) == room:
                break
            ch.turn = self._admit_pass
            if self._reserve_chunk_pages(ch):
                ready.append(ch)
        sent = 0
        for i in range(0, len(ready), width):
            rows = self._rows_of(ready[i:i + width])
            self._dispatch_chunks(rows)
            sent += len(rows)
        return sent

    def _pages_for(self, tokens: int) -> int:
        return -(-min(tokens, self.max_len) // self.page_size)

    def _room_for(self, pages: int) -> bool:
        """Whether the pool can hand a NEW sequence ``pages`` pages, its
        first ``_ring`` of them ring pages."""
        in_ring = min(pages, self._ring)
        return self._allocator.available(ring=True) >= in_ring \
            and self._allocator.available() >= pages - in_ring

    def _drain_waiting(self) -> None:
        while True:
            try:
                self._backlog.append(self.waiting.get_nowait())
            except queue.Empty:
                break

    def _fail_request(self, req: Request, reason: str) -> None:
        """Terminal failure with an explicit reason. The lifecycle
        invariant every robustness path leans on: a submitted request sets
        ``done`` exactly once — no caller ever hangs on a reaped request."""
        if req.done.is_set():
            return
        req.finish_reason = reason
        req.finish_time = time.monotonic()
        # A reaped request's span closes with an explicit failure status —
        # cancelled client, blown deadline, shed, or in-engine error — so
        # the ring buffer never accumulates open spans for dead requests.
        _span_close(req, "cancelled" if reason == "cancelled" else "error",
                    finish_reason=reason, tokens=len(req.output_tokens))
        req.stream.put(None)
        req.done.set()
        if reason == "shed":
            self.metrics.note_shed(req.qos)
        elif reason in ("cancelled", "deadline"):
            self.metrics.note_abandoned(reason)

    def _reap_abandoned(self) -> int:
        """Drop cancelled/expired requests wherever they live — live decode
        slots, in-flight chunked prefills, the preempted lane, and the
        backlog — and shed backlog entries past the queue-delay budget.
        Freed slots and their paged-KV pages return to the pool immediately
        (refcount-balanced) instead of decoding dead work. Runs once per
        scheduler step, so reap latency is one step (or the 50 ms idle
        poll). Returns the number of requests dropped."""
        self._drain_waiting()
        now = time.monotonic()
        n = 0
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            reason = s.request.abandon_reason(now)
            if reason:
                if self._kvtier is not None:
                    # A cancelled conversation's computed KV is still
                    # valid prefix content — index it before release
                    # (the retry/next-turn usually re-sends the same
                    # prefix; cancel-while-shared keeps co-sharers'
                    # references intact either way).
                    self._kv_register(self._context_tokens(s), i, s.length)
                self._release_slot_pages(i)
                self._release_slot_adapter(i)
                self.slots[i] = None
                # Host-only decision (cancel/deadline): the device still
                # thinks the row is live — sync live=False next dispatch;
                # any round already in flight is masked at consume time.
                self._dstate.mark_slot(i)
                self._fail_request(s.request, reason)
                n += 1
        for ch in list(self._chunkings):
            reason = ch.request.abandon_reason(now)
            if reason:
                self._chunkings.remove(ch)
                self._release_slot_pages(ch.slot)
                self._release_slot_adapter(ch.slot)
                self._fail_request(ch.request, reason)
                n += 1
        # Handoff holds: pages backing an exported payload whose request
        # was cancelled or deadlined (e.g. the decode side died and the
        # relay gave up) are released here — a hold can never outlive
        # its request's lifecycle, so a killed server strands nothing.
        for rid, (hreq, pages) in list(self._handoff_holds.items()):
            if hreq.abandon_reason(now):
                del self._handoff_holds[rid]
                self._allocator.free(pages)
                self.metrics.note_handoff("failed")
                n += 1
        for lane in (self._preempted, self._backlog):
            for req in list(lane):
                reason = req.abandon_reason(now)
                if reason is None and lane is self._backlog:
                    # Queue-delay budget: the request's class budget when
                    # one is declared, else the engine-wide budget — an
                    # interactive tier can shed stale work aggressively
                    # while batch waits out long queues.
                    budget = self.queue_delay_budget
                    pol = self.qos_policies.get(req.qos)
                    if pol is not None \
                            and pol.queue_delay_budget is not None:
                        budget = pol.queue_delay_budget
                    if budget is not None and now - req.arrival > budget:
                        reason = "shed"
                if reason:
                    lane.remove(req)
                    self._fail_request(req, reason)
                    n += 1
        return n

    def _enforce_queue_bound(self) -> int:
        """Restore the global admission bound by shedding from the BACK of
        the priority order: when a higher-class arrival over-admitted past
        a full queue (submit's shed-lowest-first contract), the lowest-
        class, youngest waiting request pays for it — batch is shed before
        interactive ever is. Returns requests shed."""
        if not self.max_queue:
            return 0
        self._drain_waiting()
        n = 0
        while len(self._backlog) > self.max_queue:
            victim = max(self._backlog,
                         key=lambda r: (QOS_PRIORITY.get(r.qos, 1),
                                        r.arrival))
            self._backlog.remove(victim)
            self._fail_request(victim, "shed")
            n += 1
        return n

    def _note_admitted(self, req: Request) -> Request:
        req.admitted_time = time.monotonic()
        self.metrics.observe_queue_delay(req.admitted_time - req.arrival,
                                         qos=req.qos)
        return req

    def _next_admissible(self) -> Optional[Request]:
        """Next request the scheduler may start: STRICT PRIORITY across QoS
        classes (QOS_PRIORITY order), FIFO within a class.

        Within each class the preempted lane resumes first, and only once
        the pool can hold its entire remaining run; while one waits,
        nothing at its class or below is admitted (the livelock
        backpressure, scoped per class so a higher-class arrival can still
        jump a starved batch resume). Fresh requests need room for their
        prompt plus one growth page. Single-class traffic reduces to
        the pre-QoS behavior exactly."""
        self._drain_waiting()
        for cls in sorted(QOS_PRIORITY, key=QOS_PRIORITY.get):
            pre = next((r for r in self._preempted if r.qos == cls), None)
            if pre is not None:
                remaining = max(pre.params.max_new_tokens
                                - len(pre.output_tokens), 0)
                if self._room_for(self._pages_for(
                        len(pre.prompt_tokens) + remaining)):
                    self._preempted.remove(pre)
                    return pre
                return None          # backpressure: this class and below wait
            req = next((r for r in self._backlog if r.qos == cls), None)
            if req is None:
                continue
            if not self._room_for(self._pages_for(
                    len(req.prompt_tokens)) + 1):
                return None          # head-of-line within the priority order
            self._backlog.remove(req)
            return self._note_admitted(req)
        return None

    def _prefill_budget(self) -> Optional[int]:
        """The prefill programs one admit pass may send: one for each decode
        step of the round it stands in front of, while a decode stream is
        live. Every live stream waits for the pass's programs and then for
        the round, so its tokens are never further apart than the round and
        as many programs as the round has steps; the work is the same, in
        another order. None where no slot is live (a cold engine, the
        prefill role of a disaggregated pair, the first pass after
        idleness): nobody waits for the pass."""
        if all(s is None for s in self.slots):
            return None
        if self._spec_round():
            return 1        # host-verified: one dispatch, consumed at once
        return self._steps_in_force(
            min(self.decode_steps, self.prefill_interleave_steps))

    def _steps_in_force(self, cap: int) -> int:
        """The length the next round under ``cap`` has as things stand: the
        pacer's last choice where rounds are paced, else the cap."""
        return min(self._pacer.k, cap) if self.pipelined else cap

    def _due_chunkings(self) -> "list[_Chunking]":
        """The in-flight prefills that have had no turn in the admit pass
        under way: by QoS class, the oldest first within a class."""
        return sorted(
            (ch for ch in self._chunkings if ch.turn != self._admit_pass),
            key=lambda ch: QOS_PRIORITY.get(ch.request.qos, 1))

    def _admit(self) -> int:
        """Prefill waiting requests into free slots. Returns admissions.

        Waiting requests are admitted FIRST (each joins ``_chunkings``) and
        then the in-flight prefills advance by one chunk, together where
        the engine has the program for it (``_advance_chunked``): a pass
        above the knee
        carries its prefills' chunks in one program, and not the older
        ones' and then the newcomer's. A prefill that finished leaves its
        lane to the next waiting request within the pass, as it always did
        (short prompts do not queue behind the decode round for a lane):
        the newcomers then run their first chunk, and so on until no lane
        comes free.

        While a decode stream is live the pass sends no more programs than
        ``_prefill_budget`` allows: the prefills take their turn by QoS
        class, the oldest first within a class (it finishes soonest that
        way, and a younger one keeps its lane, slot and pages), and what is
        left waits for the next pass. The hand-on itself does not wait: the
        newcomer is admitted, and only its chunk is deferred."""
        n = 0
        self._admit_pass += 1
        budget = self._prefill_budget()
        programs = self._prefill_programs_dispatched
        while True:
            n += self._admit_waiting()
            due = self._due_chunkings()
            left = None if budget is None else \
                budget - (self._prefill_programs_dispatched - programs)
            if not due or left == 0:
                break
            lanes = len(self._chunkings)
            n += self._advance_chunked(due, left)
            if len(self._chunkings) == lanes:
                break
        self._prefill_chunks_deferred += len(self._due_chunkings())
        if self._prefill_programs_dispatched != programs:
            self._prefill_passes += 1
        # The pass's finished prefills: one batched sampler dispatch + one
        # fetch for the whole admit round.
        self._flush_first_tokens()
        # Prefill-role exports queued this round: one batched KV fetch.
        self._flush_handoffs()
        if n:
            # The device just ran prefill work — the next decode round's
            # host-gap sample would measure admission, not the hot loop.
            self._last_ready_t = None
        return n

    def _admit_waiting(self) -> int:
        """Give free slots and prefill lanes to waiting requests: a prompt
        joins ``_chunkings``, to be advanced by the caller; a handed-off
        request is adopted here. Returns the adoptions."""
        n = 0
        while True:
            if len(self._chunkings) >= self.max_concurrent_prefills:
                # Chunking slots exhausted: a strictly higher-class
                # arrival may evict the lowest-class in-flight chunking
                # (cross-class chunking preemption) and take its slot.
                if not self._maybe_preempt_chunking_for_priority():
                    break
            slot_idx = self._free_slot()
            if slot_idx is None:
                # Slots exhausted: a strictly higher-class arrival may
                # recompute-preempt the lowest running class's youngest
                # slot (cross-class preemption) and take its place.
                if self._maybe_preempt_for_priority():
                    continue
                break
            req = self._next_admissible()
            if req is None:
                break
            if req.adopt is not None:
                # Handed-off request: its KV arrives in the payload —
                # upload instead of prefilling (spans handled inside).
                self._adopt_handoff(req, slot_idx)
                n += 1
                continue
            adapter_hot = self._assign_adapter(req, slot_idx)
            if adapter_hot is None:
                # Adapter-slot backpressure: every packed slot is
                # referenced by a live request — requeue at the FRONT
                # and stop admitting until one drains (the page-
                # exhaustion discipline, for the adapter buffer).
                break
            # The prefix index trims the work to the uncached tail (radix:
            # live COW sharing, host-tier promotion, sub-page resume).
            pages, covered = self._kv_match(req)
            if req.trace_parent is not None:
                # queued → prefill (covers both fresh admissions and
                # preempted-lane resumes, which skip _note_admitted).
                _span_close(req)
                if adapter_hot:
                    # The admission hot-loaded its adapter: surface
                    # the registry pull + packed-buffer scatter as a
                    # first-class phase on the trace.
                    _span_open(req, "engine.adapter_load",
                               adapter=req.adapter)
                    _span_close(req)
                tier = self._kvtier
                if tier is not None and (tier.last_promoted
                                         or tier.last_cow_tokens):
                    # Promotion/COW rode this admission: surface it
                    # as a first-class (near-instant — the transfers
                    # are async-enqueued) phase on the trace.
                    _span_open(req, "engine.kv_migrate",
                               promoted_pages=tier.last_promoted,
                               cow_tokens=tier.last_cow_tokens)
                    _span_close(req)
                _span_open(req, "engine.prefill", cached_tokens=covered)
            self._release_slot_pages(slot_idx)
            self._slot_pages[slot_idx] = list(pages)
            self._table[slot_idx, :] = -1
            self._table[slot_idx, :len(pages)] = pages
            self._dstate.mark_row(slot_idx)
            self._chunkings.append(_Chunking(req, slot_idx, covered))
        return n

    # -- disaggregated handoff (serve/handoff.py) ------------------------------

    def _export_handoff(self, slot_idx: int) -> None:
        """Queue one just-prefilled slot's KV for export: enqueue the
        device-side gather now (program order guarantees it reads the
        pre-overwrite values even if a later admission reuses the slot),
        fetch batched in ``_flush_handoffs``. The pages' ownership moves to
        the ack hold; the slot frees either way."""
        s = self.slots[slot_idx]
        req = s.request
        plen = s.length
        sk_dev = sv_dev = None
        pages = self._slot_pages[slot_idx]
        need = -(-plen // self.page_size)
        ids = jnp.asarray(np.asarray(pages[:need], np.int32))
        k_dev = self.cache["k"][:, ids].reshape(
            self.cfg.n_layers, need * self.page_size,
            self.cfg.n_kv_heads, self.cfg.head_dim)
        v_dev = self.cache["v"][:, ids].reshape(
            self.cfg.n_layers, need * self.page_size,
            self.cfg.n_kv_heads, self.cfg.head_dim)
        if self.kv_quant:
            # int8 pool: the per-token-per-head scale rows ride the
            # same enqueued gather (wire v2 ships them alongside).
            sk_dev = self.cache["ks"][:, ids].reshape(
                self.cfg.n_layers, need * self.page_size,
                self.cfg.n_kv_heads)
            sv_dev = self.cache["vs"][:, ids].reshape(
                self.cfg.n_layers, need * self.page_size,
                self.cfg.n_kv_heads)
        # Ownership transfer: the slot's page refs back the payload
        # until the decode side acks — NOT freed, NOT on the table.
        self._handoff_holds[req.id] = (req, pages)
        self._slot_pages[slot_idx] = []
        self._table[slot_idx, :] = -1
        self._dstate.mark_row(slot_idx)
        self.slots[slot_idx] = None
        self._dstate.mark_slot(slot_idx)
        self._pending_exports.append((req, k_dev, v_dev, sk_dev, sv_dev,
                                      plen))

    def _flush_handoffs(self) -> int:
        """ONE batched device→host fetch for every export queued this
        admit round, then finish each request with its payload attached
        (finish_reason="handoff" — the model server relays from there)."""
        if not self._pending_exports:
            return 0
        from kubeflow_tpu.serve.handoff import payload_from_export

        items, self._pending_exports = self._pending_exports, []
        fetched = jax.device_get(
            [(k, v, sk, sv) for _, k, v, sk, sv, _ in items])  # sync-point: one batched export fetch per admit round
        now = time.monotonic()
        for (req, _, _, _, _, plen), (k, v, sk, sv) in zip(items, fetched):
            req.handoff = payload_from_export(
                req, np.asarray(k), np.asarray(v), plen,
                kv_sk=None if sk is None else np.asarray(sk),
                kv_sv=None if sv is None else np.asarray(sv))
            req.finish_reason = "handoff"
            req.finish_time = now
            self.metrics.observe(req)
            self.metrics.note_handoff(
                "exported", wire_bytes=req.handoff.wire_bytes)
            req.stream.put(None)
            req.done.set()
        return len(items)

    def _adopt_handoff(self, req: Request, slot_idx: int) -> None:
        """Admission for a handed-off request: upload its KV into this
        engine's own pool (alloc + scatter + table-row rebuild, owner
        stamped) and seed the slot exactly where the prefill side
        stopped — length=plen, last_token=first_token, budget intact."""
        p = req.adopt
        plen = p.kv_len
        if req.trace_parent is not None:
            # queued → decode directly: the prefill phase happened on the
            # exporting engine, in the same trace.
            _span_close(req)
            _span_open(req, "engine.decode", slot=slot_idx, adopted=True)
        dt = self.cache["k"].dtype
        cfg = self.cfg
        kv_k = np.asarray(p.kv_k)
        kv_v = np.asarray(p.kv_v)
        if kv_k.dtype != dt:
            kv_k = kv_k.astype(dt)
            kv_v = kv_v.astype(dt)
        kv_sk = None if p.kv_scale_k is None else np.asarray(
            p.kv_scale_k, np.float32)
        kv_sv = None if p.kv_scale_v is None else np.asarray(
            p.kv_scale_v, np.float32)
        pg = self.page_size
        need = -(-plen // pg)
        self._release_slot_pages(slot_idx)
        # Cross-request reuse ACROSS the handoff boundary: pages this
        # decode pool already holds for the prompt's prefix are
        # adopted by reference — only the uncovered tail uploads.
        # Page-aligned match (no COW tail): the upload below is
        # page-granular.
        hit, start = self._kv_match(req, allow_cow=False)
        fresh = self._allocator.alloc(need - len(hit), owner=req.id)
        try:
            pages = list(hit) + fresh
            n2 = 1
            while n2 < len(fresh):
                n2 *= 2
            buf_k = np.zeros((cfg.n_layers, n2 * pg, cfg.n_kv_heads,
                              cfg.head_dim), dt)
            buf_v = np.zeros_like(buf_k)
            buf_k[:, :plen - start] = kv_k[:, start:plen]
            buf_v[:, :plen - start] = kv_v[:, start:plen]
            shape5 = (cfg.n_layers, n2, pg, cfg.n_kv_heads,
                      cfg.head_dim)
            pidx = np.full((n2,), self._num_pages, np.int32)
            pidx[:len(fresh)] = fresh
            if self.kv_quant:
                # Adoption rebuilds pages AND scales: the payload's
                # scale rows scatter into the same fresh pages.
                buf_sk = np.zeros(
                    (cfg.n_layers, n2 * pg, cfg.n_kv_heads), np.float32)
                buf_sv = np.zeros_like(buf_sk)
                buf_sk[:, :plen - start] = kv_sk[:, start:plen]
                buf_sv[:, :plen - start] = kv_sv[:, start:plen]
                shape4 = (cfg.n_layers, n2, pg, cfg.n_kv_heads)
                self.cache = self._adopt_upload(
                    self.cache, jnp.asarray(buf_k.reshape(shape5)),
                    jnp.asarray(buf_v.reshape(shape5)),
                    jnp.asarray(buf_sk.reshape(shape4)),
                    jnp.asarray(buf_sv.reshape(shape4)),
                    jnp.asarray(pidx))
            else:
                self.cache = self._adopt_upload(
                    self.cache, jnp.asarray(buf_k.reshape(shape5)),
                    jnp.asarray(buf_v.reshape(shape5)),
                    jnp.asarray(pidx))
        except Exception:
            # A failed upload must not strand the refs just taken —
            # the request fails loudly, the pool stays balanced.
            self._allocator.free(fresh)
            self._allocator.free(hit)
            raise
        self._slot_pages[slot_idx] = list(pages)
        self._table[slot_idx, :] = -1
        self._table[slot_idx, :need] = pages
        self._dstate.mark_row(slot_idx)
        # The adopted pages hold full-prefix KV — index them so
        # same-prefix traffic landing on this decode engine reuses
        # them (decode writes start at plen, never touching these).
        self._kv_register(p.prompt_tokens, slot_idx, plen)
        self.slots[slot_idx] = _Slot(request=req, length=plen,
                                     last_token=p.first_token,
                                     generated=0,
                                     admit_seq=next(self._admit_seq))
        self._dstate.mark_slot(slot_idx)
        self._dstate.mark_row(slot_idx)
        if self._draft_cfg is not None:
            self._draft_pos[slot_idx] = 0
        self.metrics.note_handoff("adopted", wire_bytes=p.wire_bytes)
        self._finish_if_done(slot_idx)

    def _drain_handoff_releases(self) -> int:
        """Apply server-thread handoff acks/aborts on the scheduler
        thread (the allocator's single owner). Returns releases applied."""
        n = 0
        while True:
            try:
                rid, ok = self._handoff_release.get_nowait()
            except queue.Empty:
                break
            hold = self._handoff_holds.pop(rid, None)
            if hold is not None:
                self._allocator.free(hold[1])
            if not ok:
                self.metrics.note_handoff("failed")
            n += 1
        return n

    def pending_prefill_tokens(self) -> int:
        """Prompt tokens waiting to be prefilled on this engine
        (admission queue + backlog + the unprefilled tails of in-flight
        chunkings) — the token-aware router's prefill-placement signal.
        Approximate under concurrency, like ``queue_depth``."""
        waiting = sum(len(r.prompt_tokens) for r in list(self.waiting.queue))
        backlog = sum(len(r.prompt_tokens) for r in list(self._backlog))
        chunking = sum(max(len(ch.request.prompt_tokens) - ch.pos, 0)
                       for ch in list(self._chunkings))
        return waiting + backlog + chunking

    # -- tiered KV cache (serve/kvtier.py device closures) ---------------------

    def _kv_copy_pages(self, src, dst) -> None:
        """COW tail copy: pool pages ``dst[i] <- src[i]`` in one donated
        dispatch (power-of-two padded; OOB dst ids drop)."""
        n = len(src)
        n2 = 1
        while n2 < n:
            n2 *= 2
        s = np.zeros((n2,), np.int32)
        d = np.full((n2,), -1, np.int32)
        s[:n] = src
        d[:n] = dst
        self.cache = self._kv_copy(self.cache, jnp.asarray(s),
                                   jnp.asarray(d))

    def _kv_upload_pages(self, page_ids, k_blocks, v_blocks,
                         sk_blocks=None, sv_blocks=None) -> None:
        """Host→device promotion: per-page ``[L, pg, KV, Dh]`` blocks
        into ``page_ids`` through the same scatter handoff adoption
        uses — enqueued before the admit's chunk prefill, so program
        order guarantees the prefill's gather reads promoted content.
        One host copy: blobs pack straight into the pow2-padded buffer
        (pad columns stay uninitialized — their OOB ids drop the
        write). int8 pools promote the per-page scale rows
        (``[L, pg, KV]``) through the same dispatch."""
        cfg = self.cfg
        pg = self.page_size
        dt = self.cache["k"].dtype
        n = len(page_ids)
        n2 = 1
        while n2 < n:
            n2 *= 2
        buf_k = np.empty((cfg.n_layers, n2, pg, cfg.n_kv_heads,
                          cfg.head_dim), dt)
        buf_v = np.empty_like(buf_k)
        for j in range(n):
            buf_k[:, j] = k_blocks[j]
            buf_v[:, j] = v_blocks[j]
        pidx = np.full((n2,), self._num_pages, np.int32)
        pidx[:n] = page_ids
        if self.kv_quant:
            if sk_blocks is None:
                raise ValueError(
                    "int8 pool promotion requires scale blocks (wire v2 "
                    "blobs) — got a full-dtype batch")
            buf_sk = np.empty((cfg.n_layers, n2, pg, cfg.n_kv_heads),
                              np.float32)
            buf_sv = np.empty_like(buf_sk)
            for j in range(n):
                buf_sk[:, j] = sk_blocks[j]
                buf_sv[:, j] = sv_blocks[j]
            self.cache = self._adopt_upload(
                self.cache, jnp.asarray(buf_k), jnp.asarray(buf_v),
                jnp.asarray(buf_sk), jnp.asarray(buf_sv),
                jnp.asarray(pidx))
        else:
            self.cache = self._adopt_upload(
                self.cache, jnp.asarray(buf_k), jnp.asarray(buf_v),
                jnp.asarray(pidx))

    def _kv_fetch_pages(self, page_ids):
        """Demotion batch: device-side gather of the pages' planes —
        independent buffers in program order, so the pages can free
        immediately (the handoff-export pattern); the migration thread
        does the blocking ``device_get``. Power-of-two padded (repeat
        the last id) so the gather's trace set stays log-bounded — an
        unpadded per-batch-size gather would retrace on the scheduler
        thread and spike TTFT. int8 pools return 4 planes (k, v,
        scale_k, scale_v); full-dtype pools return 2."""
        n = len(page_ids)
        n2 = 1
        while n2 < n:
            n2 *= 2
        padded = list(page_ids) + [page_ids[-1]] * (n2 - n)
        ids = jnp.asarray(np.asarray(padded, np.int32))
        if self.kv_quant:
            return (self.cache["k"][:, ids], self.cache["v"][:, ids],
                    self.cache["ks"][:, ids], self.cache["vs"][:, ids])
        return self.cache["k"][:, ids], self.cache["v"][:, ids]

    def _kv_register(self, tokens, slot_idx: int, n_tokens: int) -> None:
        """Index ``tokens[:n_tokens]``'s written KV for cross-request
        reuse (radix) or hash the full-page prompt prefix (flat) — in
        the slot occupant's adapter NAMESPACE: KV content is a function
        of (tokens, model variant), so tenants never share pages."""
        if n_tokens <= 0:
            return
        ns = self._slot_namespace(slot_idx)
        if self._kvtier is not None:
            self._kvtier.insert(tokens, self._slot_pages[slot_idx],
                                n_tokens, namespace=ns)
        else:
            self._allocator.register_prefix(
                list(tokens)[:n_tokens],
                self._slot_pages[slot_idx][:n_tokens // self.page_size],
                namespace=ns)

    def _kv_match(self, req: Request, *, allow_cow: bool = True
                  ) -> tuple[list[int], int]:
        """Longest reusable prefix of ``req``'s prompt: (pages now owned
        by the request, tokens covered). Radix: live COW sharing +
        host-tier promotion, possibly sub-page. Flat: the legacy
        full-page chained-hash hit. Over conv layers a match ends at a page
        boundary: a page holds the state it ENDS in, so there is none to
        resume from inside one."""
        ns = req.adapter or ""
        allow_cow = allow_cow and not self._state_pool_bytes
        if self._kvtier is not None:
            pages, covered = self._kvtier.match_and_acquire(
                req.prompt_tokens, owner=req.id, allow_cow=allow_cow,
                namespace=ns)
            return pages, covered
        hit = self._allocator.match_prefix(req.prompt_tokens, owner=req.id,
                                           namespace=ns)
        return list(hit), len(hit) * self.page_size

    # -- multi-tenant LoRA bookkeeping (serve/lora.py) -------------------------

    def _assign_adapter(self, req: Request,
                        slot_idx: int) -> Optional[bool]:
        """Bind ``req``'s adapter (if any) to the engine slot: acquire a
        packed-buffer slot reference, hot-loading on miss. Returns the
        hot-load flag (False = already resident, or base traffic), or
        None when every adapter slot is referenced — the caller requeues
        the request at the backlog FRONT (admission backpressure)."""
        if req.adapter is None or self._lora is None:
            self._slot_aidx[slot_idx] = -1
            self._slot_aname[slot_idx] = None
            return False
        from kubeflow_tpu.serve.lora import AdapterSlotsExhausted

        try:
            aidx, hot = self._lora.acquire(req.adapter, owner=req.id)
        except AdapterSlotsExhausted:
            self._backlog.insert(0, req)
            return None
        self._slot_aidx[slot_idx] = aidx
        self._slot_aname[slot_idx] = req.adapter
        return hot

    def _release_slot_adapter(self, slot_idx: int) -> None:
        """Return the engine slot's adapter reference (every slot-free
        path calls this exactly once — the refcount sanitizer audits the
        balance per owner)."""
        name = self._slot_aname[slot_idx]
        if name is None:
            return
        self._lora.release(name)
        self._slot_aname[slot_idx] = None
        self._slot_aidx[slot_idx] = -1

    def _slot_namespace(self, slot_idx: int) -> str:
        """KV-content namespace of the slot's occupant ("" = base): the
        prefix index keys each adapter's KV apart — same prompt under
        two adapters must never share pages."""
        return self._slot_aname[slot_idx] or ""

    def _kv_pressure(self) -> float:
        """Demotion-urgency ratio for the KV tier (>= 1.0 = urgent).
        Folds the classic pool-occupancy rule with the queue-delay-vs-
        budget ratio (the SAME p95 the SLO autoscaler scrapes off
        /metrics) and adapter hot-load backpressure — when a new tenant
        is waiting on an adapter slot, or admissions already run past
        their delay budget, cold KV should spill to host NOW rather
        than fight the hot-load for HBM headroom."""
        alloc = self._allocator
        quarter = alloc.num_pages // 4
        pool = quarter / max(alloc.available(), 1)
        qd = 0.0
        if self.queue_delay_budget:
            snap = self.metrics.snapshot()
            qd = (snap.get("queue_delay_p95_ms", 0.0) / 1e3
                  / self.queue_delay_budget)
        lora = getattr(self, "_lora", None)
        adapter = 1.0 if (lora is not None and self._backlog
                          and lora.pending_pressure()) else 0.0
        return max(pool, qd, adapter)

    def adapters_resident(self) -> list[str]:
        """Adapters currently hot in the packed buffers — the
        ``kftpu_engine_adapters_resident`` gauge's label set (the
        model-id router's placement signal)."""
        return [] if self._lora is None else self._lora.resident()

    def adapter_stats(self) -> dict:
        """Registry lifecycle counters (empty dict on LoRA-free
        engines) — the /metrics adapter series' source."""
        return {} if self._lora is None else self._lora.snapshot()

    # -- paged bookkeeping -----------------------------------------------------

    def _slot_owner(self, slot_idx: int) -> Optional[str]:
        """Request id owning ``slot_idx`` right now (occupant or in-flight
        chunked prefill) — the refcount sanitizer's leak-attribution
        label."""
        s = self.slots[slot_idx]
        if s is not None:
            return s.request.id
        for ch in self._chunkings:
            if ch.slot == slot_idx:
                return ch.request.id
        return None

    def _ensure_pages(self, slot_idx: int, upto: int) -> bool:
        """Grow ``slot_idx``'s page list to cover positions [0, upto)."""
        need = min(-(-upto // self.page_size), self._mpp)
        have = len(self._slot_pages[slot_idx])
        if need <= have:
            return True
        try:
            # A sequence's first pages are its ring in the window layers'
            # planes: they come from the ids those planes hold.
            new = self._allocator.alloc(
                need - have, ring=max(0, min(need, self._ring) - have),
                first=have == 0, owner=self._slot_owner(slot_idx))
        except PagePoolExhausted:
            return False
        self._table[slot_idx, have:need] = new
        self._slot_pages[slot_idx].extend(new)
        self._dstate.mark_row(slot_idx)
        return True

    def _release_slot_pages(self, idx: int) -> None:
        if self._slot_pages[idx]:
            # Leaf-first (reversed) release: indexed pages enter the
            # reclaimable LRU children-before-parents, so pool-pressure
            # eviction trims cached subtrees from the leaves instead of
            # beheading a whole conversation at its root.
            self._allocator.free(list(reversed(self._slot_pages[idx])))
            self._slot_pages[idx] = []
            self._table[idx, :] = -1
            self._dstate.mark_row(idx)

    def _preempt_slot(self, idx: int) -> None:
        """Recompute preemption (vLLM analog): release the slot's pages and
        requeue its request with prompt+generated-so-far; re-admission
        recomputes (prefix cache permitting) and generation resumes."""
        s = self.slots[idx]
        req = s.request
        if req.trace_parent is not None:
            # decode → queued again: the re-admission recompute shows up
            # as a fresh prefill span on the same trace.
            _span_close(req, preempted=True,
                        tokens=len(req.output_tokens))
            _span_open(req, "engine.queued", requeued=True)
        if self._kvtier is not None:
            # The victim's computed KV (prompt + generated so far) stays
            # matchable — its re-admission usually matches straight back
            # to where it stopped instead of recomputing from token 0.
            self._kv_register(self._context_tokens(s), idx, s.length)
        req.prompt_tokens = list(req.prompt_tokens) \
            + req.output_tokens[req.resumed_from:]
        req.resumed_from = len(req.output_tokens)
        self._release_slot_pages(idx)
        self._release_slot_adapter(idx)
        self.slots[idx] = None
        self._dstate.mark_slot(idx)
        self._preempted.append(req)
        self.metrics.note_preempted(req.qos)

    def _preempt_youngest(self, keep: int) -> bool:
        """Page-pressure preemption victim: the youngest slot of the
        LOWEST-priority running class (all-default traffic reduces to
        plain youngest-first, the pre-QoS behavior)."""
        candidates = [(QOS_PRIORITY.get(s.request.qos, 1), s.admit_seq, i)
                      for i, s in enumerate(self.slots)
                      if s is not None and i != keep]
        if not candidates:
            return False
        _, _, idx = max(candidates)
        self._preempt_slot(idx)
        return True

    def _waiting_priority(self) -> Optional[int]:
        """Best (numerically lowest) QoS rank waiting for admission."""
        self._drain_waiting()
        ranks = [QOS_PRIORITY.get(r.qos, 1)
                 for r in self._backlog + self._preempted]
        return min(ranks) if ranks else None

    def _maybe_preempt_chunking_for_priority(self) -> bool:
        """Cross-class CHUNKING preemption: every chunking slot is held
        and a STRICTLY higher class waits → evict the youngest in-flight
        chunked prefill of the lowest running class. Its request requeues
        through the preempted lane with zero tokens lost (nothing was
        emitted yet), and the chunks already written are registered as
        prefix-cache content BEFORE the pages release — a later resume
        usually match_prefix's straight back to where it stopped. This
        is what keeps a batch long-prompt train from head-of-line
        blocking interactive admissions on a prefill-specialized engine
        (the mixed_interference tail)."""
        if not self.qos_preemption or not self._chunkings:
            return False
        waiting = self._waiting_priority()
        if waiting is None:
            return False
        ranked = sorted(
            ((QOS_PRIORITY.get(ch.request.qos, 1), i)
             for i, ch in enumerate(self._chunkings)))
        vrank, vidx = ranked[-1]
        if vrank <= waiting:
            return False
        ch = self._chunkings[vidx]
        req = ch.request
        if req.trace_parent is not None:
            _span_close(req, preempted=True, chunked=True)
            _span_open(req, "engine.queued", requeued=True)
        if ch.pos:
            # The written chunks hold real prefix KV — index them so
            # the resume's match skips the rework (freed pages linger
            # reclaimable until the pool needs them; the radix index
            # keeps the sub-page tail too).
            self._kv_register(req.prompt_tokens, ch.slot, ch.pos)
        self._chunkings.remove(ch)
        self._release_slot_pages(ch.slot)
        self._release_slot_adapter(ch.slot)
        self._preempted.append(req)
        self.metrics.note_preempted(req.qos)
        return True

    def _maybe_preempt_for_priority(self) -> bool:
        """Cross-class recompute preemption: every slot is busy and a
        STRICTLY higher class waits → evict the youngest slot of the
        lowest running class through the existing preempted lane
        (refcount-balanced: ``_preempt_slot`` frees the pages; the victim
        recomputes on re-admission and strict-priority dequeue keeps it
        behind everything more urgent). Never evicts the waiting class's
        own tier — preemption changes WHO degrades, not whether."""
        if not self.qos_preemption:
            return False
        waiting = self._waiting_priority()
        if waiting is None:
            return False
        victims = [(QOS_PRIORITY.get(s.request.qos, 1), s.admit_seq, i)
                   for i, s in enumerate(self.slots) if s is not None]
        if not victims:
            return False
        vrank, _, vidx = max(victims)
        if vrank <= waiting:
            return False
        self._preempt_slot(vidx)
        return True

    def _finish_if_done(self, idx: int) -> bool:
        s = self.slots[idx]
        assert s is not None
        reason = None
        if s.request.params.stop_token is not None and \
                s.last_token == s.request.params.stop_token:
            reason = "stop"
        elif s.generated >= s.request.params.max_new_tokens:
            reason = "length"
        elif s.length + 1 >= self.max_len:
            reason = "length"
        if reason is None:
            return False
        req = s.request
        req.finish_reason = reason
        req.finish_time = time.monotonic()
        _span_close(req, finish_reason=reason,
                    tokens=len(req.output_tokens))
        req.stream.put(None)
        req.done.set()
        self.metrics.observe(req)
        if self._kvtier is not None:
            # Conversation reuse: index prompt + generated tokens
            # (the last emitted token's KV is not written — valid
            # content is ctx[:s.length]) before the pages release,
            # so the next turn of this conversation matches straight
            # through prompt AND history, partial tail included.
            self._kv_register(self._context_tokens(s), idx, s.length)
        self._release_slot_pages(idx)
        self._release_slot_adapter(idx)
        self.slots[idx] = None
        return True

    def _spec_round(self) -> bool:
        """The live slots' next round is a speculative one: configured, a
        slot is live and every stream greedy."""
        if self.spec_mode == "off":
            return False
        live = [s for s in self.slots if s is not None]
        return bool(live) and all(
            s.request.params.temperature <= 0.0 for s in live)

    def _decode_once(self) -> int:  # hot-loop
        """One decode scheduler pass. Routes greedy-only rounds to the
        speculative path when configured; sampling traffic (and spec-off
        engines) take the pipelined plain path: dispatch round N+1 FIRST,
        then consume round N — so the host's emit/stream work (and the
        reap/admit of the next ``step()``) overlaps device compute.
        Returns work done (tokens emitted + dispatches)."""
        active = [(i, s) for i, s in enumerate(self.slots) if s is not None]
        if self._spec_round():
            # Spec rounds verify on host between dispatches — drain the
            # plain pipeline first so host mirrors are current.
            emitted = self._consume_rounds()
            active = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
            if not active:
                return emitted
            return emitted + self._spec_decode_once(active)
        dispatched = False
        if active and (self._ahead_pass == self._admit_pass
                       or self._round_carried()):
            # The admit pass's chunk program carried this iteration's round
            # (and, where the pass waited for its first tokens, the round
            # behind it went out ahead of the wait).
            dispatched = True
        elif active:
            dispatched = self._dispatch_round(active, paced=self.pipelined)
        # Pipelined: leave the just-dispatched round in flight and consume
        # only the previous one; unpipelined (and trailing) rounds drain.
        keep = 1 if (self.pipelined and dispatched) else 0
        emitted = 1 if dispatched else 0
        while len(self._rounds) > keep:
            emitted += self._consume_round()
        return emitted

    def _slot_state_values(self, idx: int) -> tuple:
        """Current host-side truth for one slot, in device-state scatter
        order (serve/device_state.py STATE_FIELDS)."""
        s = self.slots[idx]
        if s is None:
            return DEAD_SLOT
        p = s.request.params
        budget = max(p.max_new_tokens - s.generated, 0)
        return (s.last_token, s.length, budget > 0, p.temperature, p.top_k,
                p.top_p, -1 if p.stop_token is None else p.stop_token,
                budget, self._slot_aidx[idx])

    def _sync_decode_state(self) -> None:  # hot-loop
        """Flush host scheduler deltas (admissions, reaps, preemptions,
        spec advances, page-table growth) to the device-resident state:
        one upload and one donated program for all of them. Steady-state
        rounds have nothing dirty and sync nothing — the zero-upload
        invariant."""
        slots, rows = len(self._dstate.dirty_slots), \
            len(self._dstate.dirty_rows)
        with self._phase(prof.ENGINE_SYNC_STATE, prof.active() and {
                "slots": slots, "rows": rows}):
            self._dstate.sync(self._slot_state_values,
                              self._table.__getitem__)
        self._state_sync_rounds += bool(slots or rows)

    def _dispatch_round(self, active, paced: bool = False) -> bool:  # hot-loop
        """Enqueue one multi-step decode dispatch over the device-resident
        state (no host blocking — JAX async dispatch). Returns False when
        page-pool pressure preempted every candidate slot.

        The two options cap the round: ``decode_steps``, and the smaller of
        it and ``prefill_interleave_steps`` while a chunked prefill is in
        flight (its next chunk waits for the round). A ``paced`` round, one
        the pipeline overlaps with the host's work, is the shortest length
        of the ladder that hides that work (``RoundPacer``); a round that is
        consumed at once hides nothing and runs at its cap."""
        cap = self._round_cap()
        ready = self._ready_round(
            active, self._pacer.choose(cap) if paced else cap)
        if ready is None:
            return False
        active, mode, gap, context, attrs = ready
        k_steps = attrs["k_steps"]
        with self._phase(prof.ENGINE_DECODE_DISPATCH,
                         prof.active() and attrs):
            out, rows = self._dispatch_decode(k_steps, mode,
                                              self._next_key())
        self._note_round(out, rows, active, k_steps, cap, gap, context,
                         alone=self._sent_no_prefill())
        return True

    def _round_cap(self) -> int:
        """The most steps the next round may have."""
        return (min(self.decode_steps, self.prefill_interleave_steps)
                if self._chunkings else self.decode_steps)

    def _ready_round(self, active, k_steps: int):  # hot-loop
        """What stands before any program that runs decode steps of the
        live slots, the decode program and the chunk program that carries a
        step alike: pages for the steps' writes, the slots' state on the
        device brought up to date. Returns None where nothing is live (or
        page-pool pressure preempted every candidate slot), else (the live
        slots, the sampling mode, the host gap before the dispatch, the
        cache rows the steps attend to, the dispatch span's attributes,
        ``k_steps`` among them: 1 where a sole survivor had room for no
        more)."""
        if not active:
            return None
        # With rounds in flight the device may already be this many steps
        # past the host's slot lengths — page pre-allocation must cover
        # the stale window too or a mid-dispatch write lands unmapped.
        slack = sum(r.k_steps for r in self._rounds)
        # Pre-allocate pages covering every live slot's next k_steps
        # write positions (mid-dispatch page crossings must land on
        # mapped pages); under pool pressure, preempt youngest-first.
        with self._phase(prof.ENGINE_ENSURE_PAGES):
            for i, s in list(active):
                if self.slots[i] is not s:
                    continue    # preempted by an earlier slot's allocation
                upto = min(s.length + slack + k_steps, self.max_len)
                while not self._ensure_pages(i, upto):
                    if self._preempt_youngest(keep=i):
                        continue
                    # Sole survivor: shrink the dispatch to one step;
                    # init guarantees one max-length sequence always
                    # fits, but guard the next write position anyway.
                    k_steps = 1
                    if not self._ensure_pages(
                            i, min(s.length + slack + 1, self.max_len)):
                        self._preempt_slot(i)
                    break
            active = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
        if not active:
            return None
        mode = _mode_for([s.request.params for _, s in active])
        self._sync_decode_state()
        gap = None
        if self._last_ready_t is not None:
            # Host gap: wall time the device spent waiting on the host
            # between rounds. 0 by construction when the next round was
            # already queued before the previous one's results landed.
            gap = 0.0 if self._rounds else max(
                0.0, time.monotonic() - self._last_ready_t)
            self.metrics.observe_host_gap(gap)
        self.metrics.note_dispatch_depth(len(self._rounds))
        # Cache rows the round attends to, over its live slots and steps:
        # step j attends to a slot's rows 0..length+slack+j.
        context = sum(
            k_steps * (s.length + slack) + k_steps * (k_steps + 1) // 2
            for _, s in active)
        attrs = {"round": self.decode_rounds, "k_steps": k_steps,
                 "live": len(active), "context": context,
                 **(self._rows_chosen() if prof.active() else {}),
                 # what the steps have to move beside the weights
                 "live_rows": len(active),
                 "state_bytes": k_steps * len(active)
                 * self._state_bytes_a_row}
        if self.cfg.index_topk:
            # of those rows the ones an indexer selects for its step
            self._round_selected = attrs["selected"] = sum(
                min(self.cfg.index_topk, s.length + slack + j)
                for _, s in active for j in range(1, k_steps + 1))
        if prof.active() and self.cfg.layers_of("window"):
            # rows a window layer's steps attend to: a step at position t
            # sees min(t + 1, window) of them
            w = self.cfg.attn_window
            attrs["window_context"] = sum(
                min(s.length + slack + j + 1, w)
                for _, s in active for j in range(k_steps))
        return active, mode, gap, context, attrs

    def _rows_chosen(self) -> dict:
        """What a dispatch span says of the expert layers' rows, where a
        share is held: the (token, choice) rows routed, held and sent to a
        zero expert that the LAST FETCH read back (``_consume_round``), so
        the programs between the two fetches in front of this dispatch: the
        sums come back with a round's tokens, and a span is written when
        its program is sent. {} where nothing is counted."""
        if MOE_ROWS not in self.cache:
            return {}
        return dict(zip(("rows_routed", "rows_held", "rows_zero"),
                        self._expert_rows_last))

    def _note_round(self, out, rows, active, k_steps: int, cap: int,  # hot-loop
                    gap, context: int, alone: bool) -> None:
        """Count a dispatched round and queue it for its fetch."""
        round_id = self.decode_rounds
        self.decode_rounds += 1
        self._decode_steps_dispatched += k_steps
        self._state_bytes_stepped += k_steps * len(active) \
            * self._state_bytes_a_row
        self._decode_rounds_at_cap += k_steps == cap
        self._decode_context_tokens += context
        if self.cfg.index_topk:
            self._dsa_keys_visible += context
            self._dsa_keys_selected += self._round_selected
        self._rounds.append(_InflightRound(
            out=out, active=list(active), k_steps=k_steps,
            gap_ms=None if gap is None else gap * 1e3, round_id=round_id,
            alone=alone, rows=rows))

    def _dispatch_decode(self, k_steps: int, mode: str, key):  # hot-loop
        """Enqueue the decode program over the device-resident state and
        adopt the state it returns; returns the token buffer's handle and
        the expert rows' sums as the round leaves them (None where every
        expert is held)."""
        if self._lora is not None:
            out, self.cache, st, tbl, rows = self._paged_decode_n(
                self.params, self.cache, self._dstate.arrays,
                self._dstate.table, key, k_steps, mode,
                self._lora.buffers)
        else:
            out, self.cache, st, tbl, rows = self._paged_decode_n(
                self.params, self.cache, self._dstate.arrays,
                self._dstate.table, key, k_steps, mode)
        self._dstate.adopt(st, tbl)
        return out, rows

    def _consume_round(self) -> int:  # hot-loop
        """Fetch and emit the oldest in-flight round's tokens. Slots whose
        occupant changed while the round ran (reaped, preempted,
        re-admitted) are MASKED — a cancelled request's output stream never
        contains post-cancel tokens. Returns tokens emitted."""
        rnd = self._rounds.pop(0)
        with self._phase(prof.ENGINE_FETCH,
                         prof.active() and {"round": rnd.round_id}):
            out, rows = jax.device_get((rnd.out, rnd.rows))  # sync-point: the pipeline's one designed fetch per round
            out = np.asarray(out)
        if rows is not None:
            # int32 sums that wrap: what was added since the last fetch
            seen = np.asarray(rows).astype(np.uint32)
            added = [int(d) for d in seen - self._expert_rows_seen]
            for i, d in enumerate(added):
                self._expert_rows[i] += d
            self._expert_rows_last = (*added, 0)[:3]
            self._expert_rows_seen = seen
        now = time.monotonic()
        self._consumed_k = rnd.k_steps
        if rnd.alone and self._rounds and self._last_ready_t is not None:
            # The next round was queued before this one landed and nothing
            # else ran between the last round's end and this one's: the
            # spacing of the two ready times is this round's steps.
            steps = int((out >= 0).any(axis=0).sum())
            if steps:
                self._pacer.note_step((now - self._last_ready_t) / steps)
        self._last_ready_t = now
        with self._phase(prof.ENGINE_EMIT,
                         prof.active() and {"round": rnd.round_id}) as span:
            emitted, streams = self._emit_round(rnd, out, now)
            if prof.active():
                span.set_metadata(tokens=emitted, streams=streams)
        self._decode_tokens_emitted += emitted
        return emitted

    def _emit_round(self, rnd: "_InflightRound", out,  # hot-loop
                    ready_t: float) -> tuple[int, int]:
        """Hand one fetched round's tokens to their requests, each stamped
        once with the instant the round lay ready on the host. Returns the
        tokens handed on and the streams that got any."""
        emitted = streams = 0
        for i, s in rnd.active:
            if self.slots[i] is not s or s.request.done.is_set():
                continue
            if out[i][0] >= 0:          # it gets a token: stamped before
                s.request.tokens_ready_time = ready_t
            n_emit = 0
            for t in out[i]:
                if t < 0:
                    break               # -1 = emitted nothing further
                tok = int(t)
                s.request.output_tokens.append(tok)
                s.request.stream.put(tok)
                s.last_token = tok
                s.length += 1
                s.generated += 1
                n_emit += 1
            emitted += n_emit
            streams += n_emit > 0
            if n_emit and s.request.first_token_time is None:
                # Adopted (handed-off) requests see their first LOCAL
                # token here — this engine's TTFT is its decode-side
                # scheduling latency, the decode pool's autoscale signal.
                s.request.first_token_time = time.monotonic()
            if s.request.span is not None and n_emit:
                # Round annotation as a span EVENT: one decode round is one
                # device dispatch shared by every slot — a span per round
                # per request would out-cost what it measures.
                if rnd.gap_ms is None:
                    s.request.span.add_event("decode_round", tokens=n_emit,
                                             steps=rnd.k_steps)
                else:
                    s.request.span.add_event("decode_round", tokens=n_emit,
                                             steps=rnd.k_steps,
                                             host_gap_ms=round(rnd.gap_ms,
                                                               3))
            self._finish_if_done(i)
        return emitted, streams

    def _consume_rounds(self, keep: int = 0) -> int:
        """Drain every in-flight round (the pipeline barrier the spec path
        and quiescence paths use) but the ``keep`` sent last."""
        emitted = 0
        while len(self._rounds) > keep:
            emitted += self._consume_round()
        return emitted

    def _round_carried(self) -> bool:
        """Whether the admit pass's chunk program carried this iteration's
        round (a round the pacer makes longer follows it as the decode
        program it always was)."""
        return self._mixed_pass == self._admit_pass \
            and self._steps_in_force(self._round_cap()) == 1

    def _round_ahead(self) -> bool:  # hot-loop
        """Before a pass waits for its first tokens: where its chunk
        program carried this iteration's round, that program is the LAST
        the device has, and the wait would drain the pipeline: the device
        would stand idle from the program's end through the emit of its
        round's tokens, the handlers' turn behind it and the next
        iteration's dispatch (8 ms a prompt ended at 48 streams: PERF.md,
        PR 58). So what the NEXT pass would send first goes out before the
        wait, as every round goes out before the one in front of it is
        consumed (``_decode_once``): a due prefill's chunk carrying the
        next step, in a pass of its own (the prefills this pass's budget
        deferred: a decode-only round in their place would read every
        weight for a step that rides the next chunk program anyway), else
        the next decode round, over the slots live now (behind a due chunk
        that no step rides with, ``ChunkPlan.rides``: a lone chunk of an
        engine whose step rides filled programs only goes ahead as it is and
        the round behind it). A prompt that ends in this pass joins the
        program after it. Returns whether a round went out."""
        if not (self.pipelined and self._round_carried()):
            return False
        with self._transfer_guard():
            due = self._due_chunkings()
            if due:
                self._admit_pass += 1
                self._prefill_passes += bool(self._advance_chunked(due, 1))
                if self._mixed_pass == self._admit_pass:
                    return True
            active = [(i, s) for i, s in enumerate(self.slots)
                      if s is not None]
            if active and self._dispatch_round(active, paced=True):
                self._ahead_pass = self._admit_pass
                return True
        return False

    def _plain_decode_once(self, active) -> int:  # hot-loop
        """Dispatch + consume one plain round synchronously — the
        speculative path's fallback lane (spec rounds are host-verified,
        so there is never a pipeline to overlap with here)."""
        self._dispatch_round(active)
        return self._consume_rounds()

    # -- speculative decoding --------------------------------------------------

    @staticmethod
    def _context_tokens(s: "_Slot") -> list[int]:
        """The slot's TRUE token sequence (prompt + emitted output past any
        preemption fold-back). Invariant: ctx[-1] == s.last_token and
        len(ctx) == s.length + 1 (the last token's KV is not yet written)."""
        req = s.request
        return list(req.prompt_tokens) + req.output_tokens[req.resumed_from:]

    def _spec_decode_once(self, active) -> int:  # hot-loop
        """One draft + batched-verify round (serve/spec_decode.py).

        Each live slot proposes up to ``spec_k`` draft tokens; ONE dispatch
        scores all k+1 positions per slot; greedy verification accepts the
        longest prefix matching the target's own argmax chain plus the
        correction token from the first mismatched position — so outputs
        are token-identical to plain greedy decode while each round emits
        1..k+1 tokens per slot. Rounds where no slot produced a draft fall
        back to the plain multi-step path (which amortizes the dispatch
        better than a draft-less verify would)."""
        t0 = time.monotonic()
        k = self.spec_k
        drafts: dict[int, list[int]] = {}
        if self.spec_mode == "ngram":
            from kubeflow_tpu.serve.spec_decode import ngram_propose

            for i, s in active:
                drafts[i] = ngram_propose(self._context_tokens(s), k,
                                          self._spec_ngram_max,
                                          self._spec_ngram_min)
        else:
            drafts = self._draft_model_propose(active)
        if not any(drafts.values()):
            return self._plain_decode_once(active)
        draft_s = time.monotonic() - t0
        t1 = time.monotonic()
        T = k + 1
        # Pages must cover ALL T verify write positions — a dropped
        # write would corrupt an accepted token's KV. Under pool
        # pressure preempt youngest-first; if even that cannot cover a
        # slot, fall back to plain decode (whose shrink-to-one-step
        # path handles the sole-survivor case).
        for i, s in list(active):
            if self.slots[i] is not s:
                continue    # preempted by an earlier slot's allocation
            upto = min(s.length + T, self.max_len)
            covered = True
            while not self._ensure_pages(i, upto):
                if not self._preempt_youngest(keep=i):
                    covered = False
                    break
            if not covered:
                active = [(j, sl) for j, sl in enumerate(self.slots)
                          if sl is not None]
                return self._plain_decode_once(active) if active else 0
        active = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None]
        if not active:
            return 0
        nb = self.num_slots
        tokens = np.zeros((nb, T), np.int32)
        lengths = np.zeros((nb,), np.int32)
        live = np.zeros((nb,), bool)
        for i, s in active:
            d = drafts.get(i, [])
            tokens[i, 0] = s.last_token
            tokens[i, 1:1 + len(d)] = d
            lengths[i] = s.length
            live[i] = True
        # The verify dispatch shares the device-resident page table
        # with the plain path: dirty rows sync as deltas, the table
        # itself is donated through and adopted back — never a full
        # host upload. (The [B, T] token matrix is inherently host
        # data — the drafts were proposed there.)
        self._sync_decode_state()
        cache_in = {**self.cache, "table": self._dstate.table}
        greedy, cache_out = self._verify(
            self.params, cache_in, jnp.asarray(tokens),
            jnp.asarray(lengths), jnp.asarray(live))
        self.cache = {n: cache_out[n] for n in cache_out if n != "table"}
        self._dstate.adopt(self._dstate.arrays, cache_out["table"])
        with self._phase(prof.ENGINE_FETCH):
            greedy = np.asarray(jax.device_get(greedy))  # sync-point: greedy verification happens host-side
        ready_t = time.monotonic()
        verify_s = ready_t - t1
        emitted = 0
        for i, s in active:
            d = drafts.get(i, [])
            a = 0
            while a < len(d) and d[a] == int(greedy[i, a]):
                a += 1
            # Accepted drafts + the correction/bonus token from the first
            # position whose match broke (free — its logits were computed
            # by the same dispatch). Truncation by budget/stop/max_len
            # always finishes the slot, so the "last emitted token's KV is
            # already written" state it leaves never escapes.
            emit = d[:a] + [int(greedy[i, a])]
            p = s.request.params
            emit = emit[:max(p.max_new_tokens - s.generated, 0)]
            emit = emit[:self.max_len - 1 - s.length]
            if p.stop_token is not None and p.stop_token in emit:
                emit = emit[:emit.index(p.stop_token) + 1]
            s.request.tokens_ready_time = ready_t
            for tok in emit:
                s.request.output_tokens.append(tok)
                s.request.stream.put(tok)
            if emit and s.request.first_token_time is None:
                s.request.first_token_time = time.monotonic()
            if s.request.span is not None and emit:
                s.request.span.add_event("decode_round", spec=True,
                                         drafted=len(d), tokens=len(emit))
            s.last_token = emit[-1]
            s.length += len(emit)
            s.generated += len(emit)
            emitted += len(emit)
            # Spec rounds advance the slot host-side only — the device
            # decode state is stale until the next plain-path sync.
            self._dstate.mark_slot(i)
            self.metrics.observe_spec_round(
                drafted=len(d), accepted=min(a, len(emit)),
                emitted=len(emit),
                draft_s=draft_s / len(active), verify_s=verify_s / len(active))
            # Roll back rejected positions: live KV covers exactly
            # [0, s.length) now — truncate the page table to it so pool
            # refcounts always account for tokens the slot kept.
            self._truncate_slot_pages(i, s.length)
            if self._draft_cfg is not None:
                # Draft KV is valid for everything but the final (bonus)
                # token, which the draft never consumed.
                self._draft_pos[i] = s.length
            self._finish_if_done(i)
        return emitted

    def _draft_model_propose(self, active) -> dict[int, list[int]]:  # hot-loop
        """Run the small draft model k steps ahead for every live slot in
        one dispatch (plus per-slot catch-up chunk prefills for freshly
        (re-)admitted slots whose context the draft hasn't consumed)."""
        k = self.spec_k
        dmax = k + 1
        ctxs: dict[int, list[int]] = {}
        for i, s in active:
            ctx = self._context_tokens(s)
            ctxs[i] = ctx
            # Catch-up: consume all but the last context token through the
            # chunk prefill (a chunk may start anywhere in a page).
            if len(ctx) - self._draft_pos[i] > dmax:
                C = self.chunk_size
                target = len(ctx) - 1
                pos = self._draft_pos[i]
                while pos < target:
                    real = min(C, target - pos)
                    chunk = np.zeros((1, C), np.int32)
                    chunk[0, :real] = ctx[pos:pos + real]
                    self._draft_cache = self._draft_chunkfn(
                        self._draft_params, self._draft_cache,
                        jnp.asarray(chunk), jnp.int32(i), jnp.int32(pos),
                        jnp.int32(real))
                    pos += real
                self._draft_pos[i] = target
        nb = self.num_slots
        deltas = np.zeros((nb, dmax), np.int32)
        dlens = np.zeros((nb,), np.int32)
        dpos = np.zeros((nb,), np.int32)
        live = np.zeros((nb,), bool)
        for i, s in active:
            delta = ctxs[i][self._draft_pos[i]:]
            deltas[i, :len(delta)] = delta
            dlens[i] = len(delta)
            dpos[i] = self._draft_pos[i]
            live[i] = True
        steps = dmax + k - 1
        out, self._draft_cache = self._draft_propose_n(
            self._draft_params, self._draft_cache, jnp.asarray(deltas),
            jnp.asarray(dlens), jnp.asarray(dpos), jnp.asarray(live), steps)
        with self._phase(prof.ENGINE_FETCH):
            out = np.asarray(jax.device_get(out))  # sync-point: drafts are proposed host-side
        drafts: dict[int, list[int]] = {}
        for i, s in active:
            first = int(dlens[i]) - 1    # step that predicts past the ctx
            drafts[i] = [int(t) for t in out[i, first:first + k]]
            # The propose dispatch consumed the delta AND fed k-1 of its own
            # drafts; only the true context counts as consumed — the
            # accepted suffix advances the pointer after verification.
            self._draft_pos[i] = len(ctxs[i])
        return drafts

    def _truncate_slot_pages(self, idx: int, keep_tokens: int) -> None:
        """Free the pages past the ones covering [0, keep_tokens) — the
        KV rollback after a speculative rejection. Decode-grown pages
        are never prefix-registered and keep_tokens never rewinds into the
        prompt, so registered prefix pages are never dropped here."""
        keep = -(-keep_tokens // self.page_size)
        pages = self._slot_pages[idx]
        if len(pages) <= keep:
            return
        drop = pages[keep:]
        self._slot_pages[idx] = pages[:keep]
        self._table[idx, keep:len(pages)] = -1
        self._dstate.mark_row(idx)
        self._allocator.free(drop)

    def _transfer_guard(self):
        """``jax.transfer_guard("disallow")`` in sanitize mode: implicit
        transfers (a stray numpy array riding into a dispatch — the PR-4
        bug class) raise immediately; explicit ``device_put``/
        ``device_get`` at the designed sites stay legal. Scoped to the
        decode path: admission legitimately uploads prompt chunks and
        scalar positions (``jnp.asarray``/``jnp.int32``, which this jax
        still classes as implicit for scalars)."""
        if not self.sanitize:
            return contextlib.nullcontext()
        return jax.transfer_guard("disallow")

    def step(self) -> int:
        """One scheduler iteration: reap dead requests, admit, decode.
        Returns work done (reaps count — a freed slot is admissible work;
        a dispatched round counts too, so the loop never idles with a
        round in flight). Under ``KFTPU_SANITIZE=1`` the decode pass runs
        with implicit transfers disallowed — the runtime half of the
        static device-hygiene rules.

        Every second of an iteration lands in one phase's sum or in the
        loop's own (``_phases``): ``_loop`` holds that timeline across
        iterations and their idle waits, a caller that drives ``step``
        itself gets one an iteration."""
        own_timeline = self._phases.begin()
        try:
            return self._iterate()
        finally:
            if own_timeline:
                self._phases.end()

    def _iterate(self) -> int:
        t0 = self._phases.tick()
        fetch0 = self._phases.total(prof.ENGINE_FETCH)
        self._sched_iterations += 1
        self._programs_at_step = self._prefill_programs_dispatched
        self._consumed_k = None
        with self._phase(prof.ENGINE_REAP):
            n = self._reap_abandoned() + self._enforce_queue_bound() \
                + self._drain_handoff_releases()
        with self._phase(prof.ENGINE_ADMIT):
            n += self._admit()
        if self._kvtier is not None:
            # Demotion scan (host tier): cold sharer-free prefix pages
            # hand off to the background migration thread in batches.
            # Interval-gated inside tick — idle 50 ms polls drive it —
            # and it yields to foreground traffic unless pool pressure
            # says demoting NOW is what saves the cached content.
            with self._phase(prof.ENGINE_KVTIER_TICK):
                busy = bool(self._backlog) or bool(self._chunkings) \
                    or any(s is not None for s in self.slots)
                self._kvtier.tick(busy=busy)
        with self._transfer_guard():
            n += self._decode_once()
        if n == 0:
            # Idle: the next round's host-gap sample would span the idle
            # wait, not the hot loop.
            self._last_ready_t = None
        # The host's own time this iteration (its wall time less its
        # fetches): what a round must hide. Its emit loop, and the handler
        # threads it wakes, grow with the round it consumed: filed under
        # that length. An iteration that sent a prefill program is no
        # sample: its dispatch and first tokens cost the host more, and the
        # device has the chunk's time to spend on it.
        if self._consumed_k is not None and self._sent_no_prefill():
            host_s = self._phases.tick() - t0 \
                - (self._phases.total(prof.ENGINE_FETCH) - fetch0)
            self._pacer.note_host(self._consumed_k, host_s)
        return n

    def _sent_no_prefill(self) -> bool:
        """No prefill program has gone to the device in the scheduler
        iteration under way."""
        return self._prefill_programs_dispatched == self._programs_at_step

    # -- background loop -------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    def _loop(self) -> None:
        self._phases.begin()
        try:
            while not self._stop.is_set():
                if self.step() == 0:
                    # idle: block until a request arrives
                    with self._phase(prof.ENGINE_IDLE):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
        finally:
            self._phases.end()

    def stop(self, timeout: float = 10.0) -> bool:
        """Stop the background scheduler. Returns (and records in
        ``stopped_clean``) whether the thread actually exited: a join
        timeout is NOT success — the leaked thread still owns the device
        buffers, so callers must not silently treat the engine as freed.

        Under ``KFTPU_SANITIZE=recompile`` any steady-state recompiles
        recorded during this engine's lifetime are logged with their
        dispatch-site attribution — the decode hot loop is supposed to
        hold a FIXED trace set once warm (the F6xx contract), and a
        recompile storm here erases the pipelined-dispatch win."""
        from kubeflow_tpu.runtime.sanitize import (
            assert_threads_quiescent, recompile_report,
        )

        rep = recompile_report()
        if rep.get("steady_count"):
            logger.error(
                "recompile sanitizer: %d steady-state recompile(s): %s",
                rep["steady_count"],
                "; ".join(f"{e['fn']} x{e['count']} at {e['site']}"
                          for e in rep["steady"]))
        self._stop.set()
        self._wake.set()
        if self._kvtier is not None:
            self._kvtier.close()
        self.stopped_clean = True
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                self.stopped_clean = False
                logger.error(
                    "engine scheduler thread did not stop within %.1fs; "
                    "leaking a live thread that still holds device buffers",
                    timeout)
            else:
                self._thread = None
        # KFTPU_SANITIZE=threads: every thread whose target is bound to
        # THIS engine must be dead now — a survivor raises with its
        # creation site. No-op when the mode is off.
        assert_threads_quiescent(owner=self, grace_s=timeout)
        # Flight recorder (obs/fleet.py): every engine stop — and, more
        # importantly, every sanitizer-flagged stop — leaves a
        # post-mortem dump when a recorder is installed (or
        # $KFTPU_FLIGHT_DIR is exported). Zero work otherwise.
        try:
            from kubeflow_tpu.obs.fleet import flight_recorder

            rec = flight_recorder()
            if rec is not None:
                rec.snapshot("sanitizer" if rep.get("steady_count")
                             else "engine_stop")
        except Exception as exc:   # a dump failure must not fail stop()
            logger.warning("flight recorder snapshot failed: %s", exc)
        return self.stopped_clean

    # -- convenience -----------------------------------------------------------

    def generate(self, prompt_tokens: list[int],
                 params: Optional[SamplingParams] = None,
                 timeout: float = 120.0) -> list[int]:
        """Blocking single-shot generation (drives steps if no loop runs).
        A timeout cancels the request so the engine frees its slot and KV
        pages instead of decoding for a caller that already gave up."""
        req = self.submit(prompt_tokens, params)
        if self._thread is None:
            while not req.done.is_set():
                self.step()
        try:
            return req.result(timeout)
        except TimeoutError:
            req.cancel()
            raise
