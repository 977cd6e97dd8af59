"""Model server: HTTP protocol surface over one LLM engine or a multi-model
repository.

Implements the three protocol families of the reference's model server in one
stdlib-only server (no fastapi in this image):

- v1 protocol  ((U) kserve kserve/protocol/rest/v1_endpoints.py):
  POST /v1/models/{name}:predict   {"instances": [...]}
  POST /v1/models/{name}:explain   {"instances": [...]} → per-token
       attribution from the configured explainer hop (serve/explain.py)
- v2 open-inference protocol ((U) kserve v2_endpoints.py):
  GET  /v2/models/{name}           metadata
  POST /v2/models/{name}/infer     {"inputs": [{name,shape,datatype,data}]}
- OpenAI-compatible LLM surface ((U) kserve python/huggingfaceserver):
  POST /v1/completions, /v1/chat/completions (stream=true → SSE; the
  "model" body field routes in multi-model mode)

Multi-model mode (≈ model agent + ModelMesh — SURVEY.md §2.3#29): construct
with a ``ModelRepository`` and the server adds the v2 repository API
(``GET /v2/repository/index``, ``POST /v2/repository/models/{m}/load|
unload``) and per-request routing with LRU load-on-demand.

Plus /healthz (readiness), /metrics (Prometheus text format),
/debug/device (device, memory, compile cache, kernels per dispatched program)
and /debug/profile (GET: is a profiler capture of this replica running;
POST /debug/profile/start {"seconds": n} and POST /debug/profile/stop: a
capture of the live replica through obs/profiler.py, the engine's phases in
it as host spans; it goes to the server's own ``profile_dir``, never to a
path a client names, and the server stops it itself after at most
``PROFILE_MAX_SECONDS``).
Threaded stdlib server: handlers block on the engine's request stream; the
engine thread does the batching, so concurrency costs one OS thread per
in-flight request — fine at platform scale, and zero dependencies.
"""

from __future__ import annotations

import http.client
import json
import os
import queue
import re
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional
from urllib.parse import urlparse

from kubeflow_tpu.core.headers import (
    DEADLINE_HEADER, DECODE_ALTS_HEADER, DECODE_BACKEND_HEADER,
    HANDOFF_DTYPE_HEADER, HANDOFF_WIRE_HEADER, MODEL_HEADER, QOS_HEADER,
    TRACE_HEADER,
)
from kubeflow_tpu.obs import profiler
from kubeflow_tpu.obs.fleet import spans_export_payload
from kubeflow_tpu.obs.registry import MetricsRegistry, contract_note_header
from kubeflow_tpu.obs.trace import debug_traces_payload, get_tracer
from kubeflow_tpu.core.serving import QOS_DEFAULT
from kubeflow_tpu.runtime.bootstrap import compile_counters
from kubeflow_tpu.serve.engine import (
    EngineOverloaded, HOST_GAP_BUCKETS, LLMEngine, QUEUE_DELAY_BUCKETS,
    Request, SamplingParams,
)
from kubeflow_tpu.serve.retry import (
    call_with_retry, env_float, handoff_policy,
)
from kubeflow_tpu.serve.router import quiet_handle_error
from kubeflow_tpu.serve.tokenizer import Tokenizer, get_tokenizer

#: First-byte overhead histogram bucket upper bounds (seconds): what the
#: server adds around the engine on a streamed completion, handler entry to
#: ``engine.submit`` returned plus first token to first chunk written.
FIRST_BYTE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.1, 0.5)

#: Streamed chunks a handler thread sums by itself before it folds them into
#: the server's counters: a snapshot misses at most this many a live stream,
#: and no token takes the lock.
STREAM_FOLD_CHUNKS = 32


class StreamSums:
    """A token's way from its round to the socket, summed over streamed
    chunks: ``chunks`` written after the headers; ``write_s`` from
    ``req.stream.get`` returning to the chunk flushed; ``wake_s`` over
    ``wake_n`` tokens from the instant their round lay ready on the
    scheduler's side (``Request.tokens_ready_time``) to ``get`` returning
    them, taken while no later token of their request waited; ``behind``
    the tokens taken while one did. A handler thread keeps its own and
    folds them into the server's (``ModelServer.fold_stream``)."""

    __slots__ = ("chunks", "write_s", "wake_s", "wake_n", "behind")

    def __init__(self) -> None:
        self.clear()

    def clear(self) -> None:
        self.chunks = self.wake_n = self.behind = 0
        self.write_s = self.wake_s = 0.0

#: The longest capture ``POST /debug/profile/start`` may ask for, and the
#: length of one that names none: the port is the one that serves inference,
#: so a client that never sends /stop must not leave the profiler on.
PROFILE_MAX_SECONDS = 30.0

#: Handoff wire versions this server can adopt (serve/handoff.py): v1 =
#: raw K/V planes, v2 = + int8 scale rows. A payload tagged with
#: anything else 409s at submit — the mixed-version-fleet guard.
SUPPORTED_HANDOFF_WIRE = ("1", "2")


def _raise_for_reaped(req: Request) -> None:
    """Map an engine-side terminal failure to the exception the protocol
    layer translates into an explicit HTTP status (504/429/500). A request
    the scheduler reaped returns normally from ``result()`` — with a
    failure ``finish_reason`` and possibly zero output tokens — and MUST
    NOT be served as a successful (empty) completion."""
    if req.finish_reason in ("deadline", "cancelled"):
        raise TimeoutError(
            f"request {req.id} {req.finish_reason} before completion")
    if req.finish_reason == "shed":
        raise EngineOverloaded(
            f"request {req.id} shed: queue delay exceeded budget")
    if req.finish_reason == "error":
        raise RuntimeError(f"request {req.id} failed in-engine")

def open_handoff(decode_url: str, payload, *, chat: bool, qos: str,
                 trace_hdr: Optional[str], deadline_s: Optional[float],
                 timeout: float):
    """POST a KV handoff to a decode replica; returns ``(conn, resp)``
    once the decode side ACKED (HTTP 200 — the payload bytes are in its
    memory, so the prefill side may release its page hold). Raises
    OSError on anything short of an ack, which is the caller's signal to
    ``fail_handoff`` and recompute locally.

    Cross-host hardening (ISSUE 17): connect+send and ack-wait carry
    SEPARATE budgets ($KFTPU_HANDOFF_CONNECT_S / $KFTPU_HANDOFF_ACK_S —
    a dead host fails the connect in seconds; a live-but-wedged decode
    replica fails the ack wait without holding the prefill's pages for
    the whole request deadline), and the POST declares its cache dtype
    and wire version so a mixed-version fleet REJECTS at submit (409 →
    OSError here → retry elsewhere / recompute) instead of corrupting
    pages."""
    connect_s = min(env_float("KFTPU_HANDOFF_CONNECT_S", 5.0), timeout)
    ack_s = min(env_float("KFTPU_HANDOFF_ACK_S", 30.0), timeout)
    parsed = urlparse(decode_url)
    conn = http.client.HTTPConnection(parsed.hostname or "127.0.0.1",
                                      parsed.port or 80, timeout=connect_s)
    headers = {"Content-Type": "application/octet-stream",
               QOS_HEADER: qos,
               HANDOFF_DTYPE_HEADER: payload.cache_dtype or "full",
               HANDOFF_WIRE_HEADER:
                   "2" if payload.cache_dtype else "1"}
    contract_note_header(QOS_HEADER, direction="set")
    contract_note_header(HANDOFF_DTYPE_HEADER, direction="set")
    contract_note_header(HANDOFF_WIRE_HEADER, direction="set")
    if trace_hdr:
        headers[TRACE_HEADER] = trace_hdr
        contract_note_header(TRACE_HEADER, direction="set")
    if deadline_s is not None:
        headers[DEADLINE_HEADER] = str(int(max(deadline_s, 0.0) * 1e3))
        contract_note_header(DEADLINE_HEADER, direction="set")
    path = "/v1/handoff" + ("?chat=1" if chat else "")
    try:
        conn.request("POST", path, body=payload.to_wire(), headers=headers)
        if conn.sock is not None:
            conn.sock.settimeout(ack_s)     # ack-hold budget
        resp = conn.getresponse()
    except (OSError, http.client.HTTPException) as exc:
        conn.close()
        raise OSError(f"handoff POST to {decode_url} failed: {exc}") from exc
    if resp.status != 200:
        body = resp.read()
        conn.close()
        raise OSError(
            f"handoff to {decode_url} rejected: HTTP {resp.status} "
            f"{body[:200]!r}")
    if conn.sock is not None:
        # Acked: the token relay may legitimately idle between decode
        # chunks — fall back to the request-wide budget.
        conn.sock.settimeout(timeout)
    return conn, resp


def open_handoff_with_retry(engine, candidates: list, payload, *,
                            chat: bool, qos: str, trace_fn,
                            deadline_s: Optional[float], timeout: float):
    """Bounded cross-replica handoff retry: attempt ``candidates`` in
    order under the shared jittered-backoff policy (serve/retry.py),
    each attempt a DIFFERENT decode replica — never hammer the one that
    just failed. Returns ``(url, conn, resp)`` on the first ack; raises
    the last OSError once every candidate (or the attempt budget) is
    exhausted — the caller's signal to take the terminal fallback
    (fail_handoff + local recompute, never a dropped request)."""
    from dataclasses import replace

    policy = handoff_policy()
    policy = replace(policy, attempts=max(
        1, min(policy.attempts, len(candidates))))

    def attempt(i: int):
        url = candidates[i]
        conn, resp = open_handoff(url, payload, chat=chat, qos=qos,
                                  trace_hdr=trace_fn(), deadline_s=deadline_s,
                                  timeout=timeout)
        return url, conn, resp

    def on_retry(_attempt: int, _exc) -> None:
        engine.metrics.note_handoff("retried")

    return call_with_retry(attempt, policy=policy, on_retry=on_retry)


def iter_sse_data(resp):
    """Yield the value of every ``data:`` line of an SSE response (the
    decode replica's token chunks), ending at stream end."""
    while True:
        line = resp.readline()
        if not line:
            return
        line = line.strip()
        if not line.startswith(b"data:"):
            continue
        yield line[5:].strip().decode()


_V1_PREDICT = re.compile(r"^/v1/models/([^/:]+):predict$")
_V1_EXPLAIN = re.compile(r"^/v1/models/([^/:]+):explain$")
_V2_MODEL = re.compile(r"^/v2/models/([^/]+)$")
_V2_INFER = re.compile(r"^/v2/models/([^/]+)/infer$")
_REPO_ACTION = re.compile(r"^/v2/repository/models/([^/]+)/(load|unload)$")


class ModelServer:
    def __init__(self, name: str, engine: Optional[LLMEngine] = None, *,
                 repository=None,
                 tokenizer: Optional[Tokenizer] = None,
                 transformer=None,
                 explainer=None,
                 host: str = "127.0.0.1", port: int = 0,
                 grpc_port: Optional[int] = None,
                 profile_dir: Optional[str] = None):
        if (engine is None) == (repository is None):
            raise ValueError("pass exactly one of engine= or repository=")
        self.name = name                  # default model name
        self.engine = engine              # single-model mode only
        self.repository = repository
        self.tokenizer = tokenizer or get_tokenizer("byte")
        # Pre/post-processing hop (≈ kserve transformer — SURVEY.md §2.3):
        # transformer(text, phase) with phase in {"pre", "post"}.
        self.transformer = transformer
        # Explanation hop (≈ kserve explainer, the triad's third leg):
        # explainer(tokens, params=..., cfg=...) -> attribution dict,
        # served on the v1 :explain route (serve/explain.py).
        self.explainer = explainer
        self._in_flight = 0             # guarded_by: _in_flight_lock
        self._in_flight_lock = threading.Lock()
        # What the server adds to a streamed completion's first byte
        # (``counters``, and a histogram on /metrics).
        self._fbo_lock = threading.Lock()
        self._fbo_counts = [0] * (len(FIRST_BYTE_BUCKETS) + 1)  # guarded_by: _fbo_lock
        self._fbo_sum = 0.0             # guarded_by: _fbo_lock
        self._fbo_n = 0                 # guarded_by: _fbo_lock
        # Over every chunk ``_stream_tokens`` writes after the headers; a
        # handler thread folds its own in every STREAM_FOLD_CHUNKS chunks
        # and at its stream's end.
        self._streamed = StreamSums()   # guarded_by: _fbo_lock
        # Where /debug/profile captures go, and the timer that ends the one
        # this server started.
        self.profile_dir = profile_dir or os.path.join(
            tempfile.gettempdir(), f"kftpu-profile-{name}")
        self._profile_lock = threading.Lock()
        self._profile_timer: Optional[threading.Timer] = None  # guarded_by: _profile_lock
        handler = _make_handler(self)
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.httpd.daemon_threads = True
        quiet_handle_error(self.httpd)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        # v2 protocol over gRPC as well as REST (grpc_port=0 → ephemeral).
        self.grpc_server = None
        if grpc_port is not None:
            from kubeflow_tpu.serve.grpc_server import GRPCInferenceServer

            self.grpc_server = GRPCInferenceServer(self, host=host,
                                                   port=grpc_port)

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        if self.engine is not None:
            self.engine.start()
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="model-server")
        self._thread.start()
        if self.grpc_server is not None:
            self.grpc_server.start()

    def stop(self) -> None:
        from kubeflow_tpu.runtime.sanitize import assert_threads_quiescent

        self.httpd.shutdown()
        self.httpd.server_close()
        self.stop_profile()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            # KFTPU_SANITIZE=threads: the serve thread must be dead now
            # (its target binds to httpd, so audit it explicitly).
            assert_threads_quiescent(threads=(self._thread,), grace_s=5.0)
            self._thread = None
        if self.grpc_server is not None:
            self.grpc_server.stop()
        if self.engine is not None:
            self.engine.stop()
        if self.repository is not None:
            self.repository.shutdown()

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    # -- model resolution ------------------------------------------------------

    def model_names(self) -> list[str]:
        if self.repository is None:
            lora = getattr(self.engine, "_lora", None)
            if lora is not None:
                # Multi-tenant LoRA: every registered adapter is a
                # servable model id on this engine.
                return [self.name] + lora.names()
            return [self.name]
        return self.repository.names()

    def resolve_adapter(self, name: Optional[str]) -> Optional[str]:
        """Map a request's model id onto this server's LoRA surface:
        None/base name = base weights; a registered adapter name decodes
        through its packed slot; anything else on a LoRA-enabled engine
        is a 404 (KeyError) — multi-tenant serving must never silently
        fall a tenant through to the base model. LoRA-free servers
        return None (the pre-LoRA lease semantics apply)."""
        if self.repository is not None or name in (None, self.name):
            return None
        lora = getattr(self.engine, "_lora", None)
        if lora is None:
            return None
        if not lora.known(name):
            raise KeyError(
                f"unknown model {name!r}: not a registered adapter "
                f"(serving {self.name})")
        return name

    def lease(self, name: Optional[str], *, strict: bool = False):
        """Context manager: (engine, tokenizer, resolved_name) pinned for the
        request's duration (repository mode leases against LRU eviction).

        ``strict`` (path-addressed endpoints): a single-model server 404s a
        foreign name. Non-strict (OpenAI body "model" field): a foreign name
        is ignored — OpenAI SDK clients always send one, and the
        pre-multi-model server served them."""
        import contextlib

        if self.repository is None:
            if strict and name not in (None, self.name):
                raise KeyError(f"unknown model {name!r} (serving {self.name})")

            @contextlib.contextmanager
            def single():
                yield self.engine, self.tokenizer, self.name

            return single()

        @contextlib.contextmanager
        def leased():
            entry = self.repository.acquire(name or self.name)
            try:
                yield entry.engine, entry.tokenizer, entry.name
            finally:
                self.repository.release(entry)

        return leased()

    def model_config(self, name: str):
        """Model metadata without forcing a load."""
        if self.repository is None:
            if name != self.name:
                lora = getattr(self.engine, "_lora", None)
                if lora is not None and lora.known(name):
                    # An adapter id serves the base architecture.
                    return self.engine.cfg
                raise KeyError(name)
            return self.engine.cfg
        entry = self.repository.peek(name)
        if entry is None:
            raise KeyError(name)
        return entry.cfg

    def explain_text(self, prompt: str, model: Optional[str]) -> dict:
        """Tokenize → attribution handler → per-token scores with their
        decoded token strings (the v1 ``:explain`` payload)."""
        if self.explainer is None:
            raise ValueError("no explainer configured on this service")
        if self.transformer is not None:
            prompt = self.transformer(prompt, "pre")
        with self.lease(model, strict=True) as (engine, tokenizer, _):
            toks = tokenizer.encode(prompt)
            # Attribution is O(S) forwards (leave_one_out batches an [S+1,S]
            # block): an uncapped prompt would OOM the live serving chip.
            limit = min(engine.max_len, engine.cfg.max_seq_len)
            if len(toks) > limit:
                raise ValueError(
                    f"explain prompt is {len(toks)} tokens; limit {limit}")
            cfg = engine.cfg
            if cfg.is_moe and cfg.moe_impl != "dense":
                # Attribution must be batch-independent: dispatch MoE's
                # shared [E, C] capacity buffers couple co-batched rows
                # (leave_one_out's S ablations would perturb each other's
                # expert drops; grad_x_input's scores would depend on
                # capacity luck). Dense MoE routes every token exactly —
                # the same reason decode defaults to dense in the engine.
                import dataclasses as _dc
                cfg = _dc.replace(cfg, moe_impl="dense")
            # mesh: the TP engine's params are sharded (and possibly int8)
            # — the handlers jit with it so GSPMD partitions attribution
            # the same way it partitions serving dispatches.
            out = self.explainer(toks, params=engine.params, cfg=cfg,
                                 mesh=engine.mesh)
            out["tokens"] = [tokenizer.decode([t]) for t in toks]
            out["predicted_text"] = tokenizer.decode([out["target_token"]])
        return out

    def request_timeout(self, body: dict,
                        deadline_s: Optional[float] = None) -> float:
        """Effective per-request budget: the body ``timeout`` capped by the
        remaining client budget from the router's deadline header."""
        timeout = float(body.get("timeout", 300))
        if deadline_s is not None:
            timeout = min(timeout, max(deadline_s, 0.0))
        return timeout

    def generate_text(self, prompt: str, body: dict, model: Optional[str],
                      strict: bool = False,
                      deadline_s: Optional[float] = None,
                      qos: str = QOS_DEFAULT,
                      decode_url: Optional[str] = None,
                      decode_alts: tuple = ()) -> tuple[str, "Request"]:
        """Pre-hop → tokenize → engine → detokenize → post-hop: the one
        generation path every protocol surface (REST v1/v2, OpenAI, gRPC)
        shares.

        Lifecycle: the engine-side request carries a deadline equal to the
        client budget (``deadline_s`` from the router header, capped by the
        body timeout), so the scheduler reaps it — freeing its slot and KV
        pages — the moment the client can no longer use the answer. The
        result wait gets one extra second past that deadline so the normal
        path is the engine's explicit reap; the TimeoutError fallback (a
        wedged scheduler) cancels the orphan so a recovering engine drops
        it instead of decoding dead work."""
        if self.transformer is not None:
            prompt = self.transformer(prompt, "pre")
        timeout = self.request_timeout(body, deadline_s)
        tracer = get_tracer()
        # Multi-tenant LoRA: an adapter id leases the BASE engine and
        # decodes through the adapter's packed slot (resolve_adapter
        # 404s unknown ids on LoRA-enabled engines).
        adapter = self.resolve_adapter(model)
        with self.lease(None if adapter else model,
                        strict=strict) as (engine, tokenizer, _):
            toks = tokenizer.encode(prompt)
            # Disaggregated placement: on a prefill-role engine with a
            # router-stamped decode backend, stop at the first token and
            # hand the KV off; without one, decode locally (the
            # unified-fallback path).
            wants_handoff = engine.role == "prefill" and decode_url
            handoff_flag: Optional[bool] = None
            if engine.role == "prefill":
                handoff_flag = bool(wants_handoff)
            req = engine.submit(toks, self.sampling_from(body, tokenizer),
                                deadline=time.monotonic() + timeout,
                                trace_parent=tracer.current(), qos=qos,
                                handoff=handoff_flag, adapter=adapter)
            try:
                out = req.result(timeout=timeout + 1.0)
            except TimeoutError:
                req.cancel()
                raise
            if req.finish_reason == "handoff":
                text = self._relay_handoff_text(
                    engine, tokenizer, req, toks, body, decode_url,
                    qos=qos, timeout=timeout, decode_alts=decode_alts)
            else:
                _raise_for_reaped(req)
                with tracer.span("server.detokenize", tokens=len(out)):
                    text = tokenizer.decode(
                        [t for t in out if t != tokenizer.eos_id])
        if self.transformer is not None:
            text = self.transformer(text, "post")
        return text, req

    def _relay_handoff_text(self, engine, tokenizer, req, toks: list[int],
                            body: dict, decode_url: str, *, qos: str,
                            timeout: float, decode_alts: tuple = ()) -> str:
        """Non-streaming half of the handoff relay: POST the payload,
        join the decode replica's token pieces after the locally-sampled
        first token. Failure before the ack retries a DIFFERENT decode
        replica (router-stamped alternates, jittered backoff); exhausted
        alternates = recompute locally (handoff contract: failure costs
        a prefill, never the request)."""
        tracer = get_tracer()
        deadline = time.monotonic() + timeout
        candidates = [decode_url] + [u for u in decode_alts
                                     if u and u != decode_url]
        with tracer.span("engine.handoff", backend=decode_url,
                         request=req.id) as sp:
            try:
                used_url, conn, resp = open_handoff_with_retry(
                    engine, candidates, req.handoff, chat=False, qos=qos,
                    trace_fn=lambda: tracer.inject(sp),
                    deadline_s=timeout, timeout=timeout + 5.0)
                sp.set_attrs(backend=used_url)
                if used_url != decode_url:
                    # The placed decode replica died between pick and
                    # handoff; the fleet stitcher reads this event to
                    # attribute the hop as a failover, not a clean
                    # handoff.
                    sp.add_event("connect_failure", backend=decode_url)
            except OSError as exc:
                sp.set_attrs(error=str(exc), fallback="recompute")
                engine.metrics.note_handoff("fallback")
                engine.fail_handoff(req.id)
                return self._recompute_locally(engine, tokenizer, req,
                                               toks, body, qos=qos,
                                               timeout=timeout)
            engine.complete_handoff(req.id)
            # Collect raw token ids (the handoff SSE carries them) and
            # decode the WHOLE sequence once — piecewise decoding would
            # split multi-byte characters the unified path decodes
            # together.
            tokens = list(req.output_tokens)
            try:
                try:
                    for data in iter_sse_data(resp):
                        if data == "[DONE]":
                            break
                        choice = json.loads(data)["choices"][0]
                        tokens.append(int(choice["token"]))
                        if time.monotonic() > deadline + 1.0:
                            raise TimeoutError(
                                f"handoff relay for {req.id} exceeded "
                                "its deadline")
                finally:
                    conn.close()
            except (OSError, ValueError, KeyError) as exc:
                # Post-ack failure: the decode side died mid-stream. The
                # pages are gone (ack released them) and tokens may have
                # reached nobody — surface an explicit error.
                raise RuntimeError(
                    f"decode replica failed mid-handoff for {req.id}: "
                    f"{exc}") from exc
            sp.set_attrs(tokens=len(tokens))
        return tokenizer.decode(
            [t for t in tokens if t != tokenizer.eos_id])

    def _recompute_locally(self, engine, tokenizer, req, toks: list[int],
                           body: dict, *, qos: str, timeout: float) -> str:
        """Handoff failure = recompute: re-run the request as a unified
        local decode (the prefix cache usually makes the second prefill
        one admission)."""
        req2 = engine.submit(toks, self.sampling_from(body, tokenizer),
                             deadline=time.monotonic() + timeout,
                             trace_parent=get_tracer().current(), qos=qos,
                             handoff=False, request_id=f"{req.id}-recompute")
        try:
            out = req2.result(timeout=timeout + 1.0)
        except TimeoutError:
            req2.cancel()
            raise
        _raise_for_reaped(req2)
        return tokenizer.decode([t for t in out if t != tokenizer.eos_id])

    # -- request plumbing ------------------------------------------------------

    def track(self, delta: int) -> None:
        with self._in_flight_lock:
            self._in_flight += delta

    @property
    def in_flight(self) -> int:
        with self._in_flight_lock:
            return self._in_flight

    @staticmethod
    def sampling_from(body: dict[str, Any],
                      tokenizer: Tokenizer) -> SamplingParams:
        return SamplingParams(
            max_new_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            stop_token=tokenizer.eos_id,
        )

    def observe_first_byte_overhead(self, seconds: float) -> None:
        with self._fbo_lock:
            i = 0
            while i < len(FIRST_BYTE_BUCKETS) \
                    and seconds > FIRST_BYTE_BUCKETS[i]:
                i += 1
            self._fbo_counts[i] += 1
            self._fbo_sum += seconds
            self._fbo_n += 1

    def first_byte_overhead_histogram(self) -> tuple[list[int], float, int]:
        with self._fbo_lock:
            return list(self._fbo_counts), self._fbo_sum, self._fbo_n

    def fold_stream(self, mine: StreamSums) -> None:
        """Add one handler thread's sums to the server's and zero them."""
        with self._fbo_lock:
            total = self._streamed
            for field in StreamSums.__slots__:
                setattr(total, field,
                        getattr(total, field) + getattr(mine, field))
        mine.clear()

    def start_profile(self, seconds: Optional[float] = None) -> dict:
        """Start a profiler capture of this replica into ``profile_dir``
        (obs/profiler.py: raises ``RuntimeError`` while one is active). It
        ends with ``stop_profile`` or by itself after ``seconds``, at most
        ``PROFILE_MAX_SECONDS``."""
        seconds = min(float(seconds or PROFILE_MAX_SECONDS),
                      PROFILE_MAX_SECONDS)
        with self._profile_lock:
            profiler.start(self.profile_dir)
            timer = threading.Timer(seconds, lambda: self.stop_profile(timer))
            timer.daemon = True
            self._profile_timer = timer
            timer.start()
        return {"active": True, "dir": self.profile_dir, "seconds": seconds}

    def stop_profile(self, only: Optional[threading.Timer] = None) -> dict:
        """Stop the capture ``start_profile`` began (one begun elsewhere in
        the process is not this server's to stop). A timer passes itself as
        ``only`` and stops nothing but the capture it was set for."""
        with self._profile_lock:
            timer = self._profile_timer
            if timer is None or only not in (None, timer):
                return {"active": profiler.active(), "dir": ""}
            self._profile_timer = None
            timer.cancel()
            return {"active": False, "dir": profiler.stop()}

    def counters(self) -> dict[str, float]:
        """One total snapshot of the server's own running sums and counts
        (the engine has its own, ``LLMEngine.counters``): every key exists
        from construction on and only ever grows."""
        with self._fbo_lock:
            t = self._streamed
            return {"first_byte_overhead_sum_s": self._fbo_sum,
                    "first_byte_overhead_n": self._fbo_n,
                    # ``StreamSums``; ``write`` holds the piece decoded,
                    # ``json.dumps``, write, flush and the waits for the
                    # interpreter lock in between, handler threads summed
                    "stream_chunks_n": t.chunks,
                    "stream_write_sum_s": t.write_s,
                    "stream_wake_sum_s": t.wake_s,
                    "stream_wake_n": t.wake_n,
                    "stream_behind_n": t.behind}

    def metrics_registry(self) -> MetricsRegistry:
        """Scrape-time registry over the live engine counters — the model
        server's half of the platform's single exposition path
        (obs/registry.py)."""
        reg = serving_metrics_registry(self._live_engines(),
                                       in_flight=self.in_flight)
        counts, total, n = self.first_byte_overhead_histogram()
        reg.histogram("kftpu_serving_first_byte_overhead_seconds",
                      FIRST_BYTE_BUCKETS).set_cumulative(
                          counts, total, n, model=self.name)
        return reg

    def _live_engines(self) -> list[tuple[str, LLMEngine]]:
        engines: list[tuple[str, LLMEngine]] = []
        if self.engine is not None:
            engines.append((self.name, self.engine))
        elif self.repository is not None:
            # peek only: a scrape must not touch LRU recency or load
            # anything.
            for item in self.repository.index():
                entry = self.repository.peek(item["name"])
                if entry is not None and entry.engine is not None:
                    engines.append((entry.name, entry.engine))
        return engines

    def device_payload(self) -> dict:
        """``GET /debug/device``: what this replica runs on, for a parent
        that must not touch the chip itself — device, memory and compile
        cache (runtime/device_report.py) plus, per model, what the
        engine's start cost (its constructor by phase, the programs it ran
        once) and the Pallas kernels of each program it has dispatched."""
        from kubeflow_tpu.runtime.device_report import device_report

        engines = self._live_engines()
        return {**device_report(),
                "start": {name: {"phases": eng.start_phase_seconds(),
                                 "programs": eng.start_programs()}
                          for name, eng in engines},
                "programs": {name: dict(eng.program_kernels)
                             for name, eng in engines}}

    def metrics_text(self) -> str:
        return self.metrics_registry().render()


def serving_metrics_registry(engines: list, *,
                             in_flight: int = 0) -> MetricsRegistry:
    """Build the serving ``/metrics`` registry for a set of ``(name,
    engine)`` pairs — the ONE definition of every ``kftpu_serving_*`` /
    ``kftpu_engine_*`` series. The model server scrapes through it, and
    the loadgen's direct-engine target renders the SAME exposition for
    its attribution join, so "engine-internal signals" always means the
    production series, never a parallel bookkeeping path."""
    reg = MetricsRegistry()
    requests_total = reg.counter("kftpu_serving_requests_total")
    tokens_total = reg.counter("kftpu_serving_tokens_total")
    reg.gauge("kftpu_serving_in_flight").set(in_flight)
    queue_depth = reg.gauge("kftpu_serving_queue_depth")
    shed = reg.counter("kftpu_serving_requests_shed_total")
    cancelled = reg.counter("kftpu_serving_requests_cancelled_total")
    expired = reg.counter("kftpu_serving_requests_expired_total")
    qdelay = reg.histogram("kftpu_serving_queue_delay_seconds",
                           QUEUE_DELAY_BUCKETS)
    # Multi-tenant QoS: per-class SLO attainment (the series the
    # signal-driven autoscaler weighs) + shed/preemption attribution.
    preempt = reg.counter("kftpu_serving_preemptions_total")
    qos_requests = reg.counter("kftpu_serving_qos_requests_total")
    qos_shed = reg.counter("kftpu_serving_qos_requests_shed_total")
    qos_preempt = reg.counter("kftpu_serving_qos_preemptions_total")
    qos_ttft = reg.gauge("kftpu_serving_qos_ttft_p95_ms")
    qos_qd = reg.gauge("kftpu_serving_qos_queue_delay_p95_ms")
    qos_qdelay = reg.histogram("kftpu_serving_qos_queue_delay_seconds",
                               QUEUE_DELAY_BUCKETS)
    # Decode hot-loop health (pipelined dispatch): per-round host gap
    # + how many rounds ride in flight. A pipelined engine shows
    # near-zero gaps and depth 1; gaps growing toward the round time
    # mean the host (detokenize/stream/admit) is the bottleneck again.
    host_gap = reg.histogram("kftpu_engine_host_gap_seconds",
                             HOST_GAP_BUCKETS)
    depth = reg.gauge("kftpu_engine_dispatch_depth")
    # The scheduler thread's seconds by phase (``sched_phase_seconds``,
    # which ``LLMEngine.counters`` carries as ``sched_<phase>_sum_s``;
    # ``phase="other"``: under none): what the benchmark's readers
    # difference over a window, for the operator's ``rate()``. ``fetch``
    # and ``idle`` are waits; the rest is the host's own.
    sched_phase = reg.counter("kftpu_engine_sched_phase_seconds_total")
    # What a replica's start cost: the engine's constructor by start phase
    # (``start_phase_seconds``: place, pool, relay, warm, other; constants
    # once it is built, so a gauge), and the PROCESS's compiles
    # (runtime/bootstrap.py::watch_compiles, no ``model``): seconds in
    # XLA's compile or the cache's retrieval (``backend``, which holds
    # ``retrieval``) and tracing and lowering, the persistent cache's
    # hits and misses, and the programs compiled or loaded
    # (``kftpu_compiles_total``: hits, misses and the compiles no cache was
    # asked for alike). A replica whose compiles grow under traffic is
    # compiling in front of its clients: the alert is on this count, which
    # moves by one a program however short the compile.
    start_phase = reg.gauge("kftpu_engine_start_seconds")
    compile_s = reg.counter("kftpu_compile_seconds_total")
    compiled = reg.counter("kftpu_compiles_total")
    cache_requests = reg.counter("kftpu_compile_cache_requests_total")
    compiles = compile_counters()
    for kind in ("backend", "retrieval", "trace_lower"):
        compile_s.inc(compiles[f"compile_{kind}_sum_s"], kind=kind)
    compiled.inc(compiles["compile_backend_n"])
    cache_requests.inc(compiles["compile_cache_hits"], result="hit")
    cache_requests.inc(compiles["compile_cache_misses"], result="miss")
    # Disaggregated serving: the token-aware router's placement signals
    # (pending prefill tokens → prefill pool, resident KV pages → decode
    # pool) plus the handoff lifecycle counters.
    pending_prefill = reg.gauge("kftpu_engine_pending_prefill_tokens")
    # Tiered KV cache: resident is split REFERENCED (live requests'
    # pages — real load, the decode router's placement signal) vs
    # CACHED (ref-0 reclaimable prefix content — freely evictable, so
    # capacity, not load), plus the host-RAM overflow tier's occupancy
    # and the radix/tier lifecycle counters (serve/kvtier.py).
    pages_resident = reg.gauge("kftpu_engine_kv_pages_resident")
    pages_cached = reg.gauge("kftpu_engine_kv_pages_cached")
    pages_host = reg.gauge("kftpu_engine_kv_pages_host")
    prefix_hits = reg.counter("kftpu_engine_kv_prefix_hits_total")
    prefix_tokens = reg.counter("kftpu_engine_kv_prefix_tokens_reused_total")
    cow_copies = reg.counter("kftpu_engine_kv_cow_copies_total")
    pages_demoted = reg.counter("kftpu_engine_kv_pages_demoted_total")
    pages_promoted = reg.counter("kftpu_engine_kv_pages_promoted_total")
    handoffs_out = reg.counter("kftpu_engine_handoffs_exported_total")
    handoffs_in = reg.counter("kftpu_engine_handoffs_adopted_total")
    handoffs_bad = reg.counter("kftpu_engine_handoffs_failed_total")
    # Fleet-wide KV fabric (ISSUE 17): the remote third tier's occupancy
    # and store traffic, its degrade paths (deadline/corrupt — each one
    # is a request that RESOLVED via recompute), the tier-pressure ratio
    # the autoscaler folds, and the cross-host handoff failure budget
    # (retried = moved to another decode replica; fallback = recomputed
    # locally after exhausting them).
    pages_remote = reg.gauge("kftpu_engine_kv_pages_remote")
    remote_demote_b = reg.counter(
        "kftpu_engine_kv_remote_demoted_bytes_total")
    remote_promote_b = reg.counter(
        "kftpu_engine_kv_remote_promoted_bytes_total")
    remote_timeouts = reg.counter(
        "kftpu_engine_kv_remote_promote_timeouts_total")
    remote_corrupt = reg.counter(
        "kftpu_engine_kv_remote_blobs_corrupt_total")
    tier_pressure = reg.gauge("kftpu_engine_kv_tier_pressure")
    handoffs_retried = reg.counter("kftpu_engine_handoffs_retried_total")
    handoffs_fb = reg.counter("kftpu_engine_handoffs_fallback_total")
    # Quantized KV fabric (ops/quantization.py kv path): whether the
    # pool stores int8, the pool's token density (the ~1.9x-at-equal-HBM
    # claim's series), and the actual wire bytes moved by handoff export/
    # adopt and tier demote/promote — int8+scales blobs read ~half the
    # full-dtype bytes, and THESE counters are where that shows up.
    kvq_enabled = reg.gauge("kftpu_engine_kv_quant_enabled")
    kvq_density = reg.gauge("kftpu_engine_kv_quant_tokens_per_mib")
    pool_bytes = reg.gauge("kftpu_engine_kv_pool_bytes")
    states_started = reg.counter("kftpu_engine_sequence_states_started_total")
    state_stepped = reg.counter("kftpu_engine_state_bytes_stepped_total")
    ho_bytes_out = reg.counter("kftpu_engine_kv_handoff_bytes_exported_total")
    ho_bytes_in = reg.counter("kftpu_engine_kv_handoff_bytes_adopted_total")
    wire_demote = reg.counter("kftpu_engine_kv_wire_bytes_demoted_total")
    wire_promote = reg.counter("kftpu_engine_kv_wire_bytes_promoted_total")
    # Multi-tenant LoRA (serve/lora.py): which adapters are HOT on this
    # engine (one ``adapter=``-labeled sample per resident adapter — the
    # model-id router's placement signal; a 0 sample without the label
    # when none are) plus the hot-load/evict lifecycle counters.
    adapters_resident = reg.gauge("kftpu_engine_adapters_resident")
    adapter_loads = reg.counter("kftpu_engine_adapter_loads_total")
    adapter_evictions = reg.counter("kftpu_engine_adapter_evictions_total")
    for name, engine in engines:
        snap = engine.metrics.snapshot()
        requests_total.inc(snap["requests_completed"], model=name)
        tokens_total.inc(snap["tokens_generated"], model=name)
        for k in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                  "tpot_p50_ms", "queue_delay_p95_ms",
                  "requests_per_sec", "tokens_per_sec",
                  "spec_acceptance_rate", "spec_tokens_per_step",
                  "spec_draft_overhead", "host_gap_p50_ms",
                  "host_gap_p99_ms"):
            if k in snap:
                reg.gauge(f"kftpu_serving_{k}").set(snap[k], model=name)
        # Load-shedding / lifecycle surface: queue depth, shed and reap
        # counters, and the queue-delay histogram — the dashboards that
        # show an overload knee BEFORE clients start timing out.
        queue_depth.set(engine.queue_depth(), model=name)
        shed.inc(snap["requests_shed"], model=name)
        cancelled.inc(snap["requests_cancelled"], model=name)
        expired.inc(snap["requests_expired"], model=name)
        _, counts, qsum, qn = engine.metrics.queue_delay_histogram()
        qdelay.set_cumulative(counts, qsum, qn, model=name)
        preempt.inc(snap.get("preemptions", 0), model=name)
        for cls, c in snap.get("qos", {}).items():
            qos_requests.inc(c["completed"], model=name, qos=cls)
            qos_shed.inc(c["shed"], model=name, qos=cls)
            qos_preempt.inc(c["preempted"], model=name, qos=cls)
            if "ttft_p95_ms" in c:
                qos_ttft.set(c["ttft_p95_ms"], model=name, qos=cls)
            if "queue_delay_p95_ms" in c:
                qos_qd.set(c["queue_delay_p95_ms"], model=name, qos=cls)
            _, ccounts, csum, cn = \
                engine.metrics.queue_delay_histogram(cls)
            qos_qdelay.set_cumulative(ccounts, csum, cn,
                                      model=name, qos=cls)
        _, hcounts, hsum, hn = engine.metrics.host_gap_histogram()
        host_gap.set_cumulative(hcounts, hsum, hn, model=name)
        depth.set(snap.get("dispatch_depth", 0), model=name)
        for phase, seconds in engine.sched_phase_seconds().items():
            sched_phase.inc(seconds, model=name, phase=phase)
        for phase, seconds in engine.start_phase_seconds().items():
            start_phase.set(seconds, model=name, phase=phase)
        pending_prefill.set(engine.pending_prefill_tokens(), model=name)
        pages_resident.set(engine.kv_pages_in_use(), model=name)
        pages_cached.set(engine.kv_pages_cached(), model=name)
        pages_host.set(engine.kv_pages_host(), model=name)
        tier = engine.kv_tier_stats()
        prefix_hits.inc(tier.get("prefix_hits", 0), model=name)
        prefix_tokens.inc(tier.get("tokens_matched", 0), model=name)
        cow_copies.inc(tier.get("cow_copies", 0), model=name)
        pages_demoted.inc(tier.get("pages_demoted", 0), model=name)
        pages_promoted.inc(tier.get("pages_promoted", 0), model=name)
        handoffs_out.inc(snap.get("handoffs_exported", 0), model=name)
        handoffs_in.inc(snap.get("handoffs_adopted", 0), model=name)
        handoffs_bad.inc(snap.get("handoffs_failed", 0), model=name)
        handoffs_retried.inc(snap.get("handoffs_retried", 0), model=name)
        handoffs_fb.inc(snap.get("handoffs_fallback", 0), model=name)
        pages_remote.set(engine.kv_pages_remote(), model=name)
        remote_demote_b.inc(tier.get("remote_demote_bytes", 0), model=name)
        remote_promote_b.inc(tier.get("remote_promote_bytes", 0),
                             model=name)
        remote_timeouts.inc(tier.get("remote_promote_timeouts", 0),
                            model=name)
        remote_corrupt.inc(tier.get("remote_blobs_corrupt", 0), model=name)
        tier_pressure.set(round(engine.kv_tier_pressure(), 3), model=name)
        density = engine.kv_pool_density()
        kvq_enabled.set(density["quant"], model=name)
        kvq_density.set(round(density["tokens_per_mib"], 1), model=name)
        # the pool by what a plane holds: rows a token (and tails a page),
        # or an entry a SEQUENCE (linear-attention state), and the
        # sequences whose state began from zeros
        counters = engine.counters()
        for planes in ("token", "sequence"):
            pool_bytes.set(counters[f"kv_{planes}_pool_bytes"], model=name,
                           planes=planes)
        states_started.inc(counters["state_sequences_started"], model=name)
        state_stepped.inc(counters["state_bytes_stepped"], model=name)
        ho_bytes_out.inc(snap.get("handoff_bytes_exported", 0), model=name)
        ho_bytes_in.inc(snap.get("handoff_bytes_adopted", 0), model=name)
        wire_demote.inc(tier.get("demote_wire_bytes", 0), model=name)
        wire_promote.inc(tier.get("promote_wire_bytes", 0), model=name)
        resident = engine.adapters_resident()
        for a in resident:
            adapters_resident.set(1, model=name, adapter=a)
        if not resident:
            adapters_resident.set(0, model=name)
        astats = engine.adapter_stats()
        adapter_loads.inc(astats.get("loads", 0), model=name)
        adapter_evictions.inc(astats.get("evictions", 0), model=name)
    return reg


def _make_handler(server: ModelServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args) -> None:  # quiet
            pass

        # -- helpers ----------------------------------------------------------

        def _json(self, code: int, obj: Any,
                  headers: Optional[dict] = None) -> None:
            data = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def _deadline_s(self) -> Optional[float]:
            """Remaining client budget (seconds) from the router's deadline
            header; None when the request carries no deadline."""
            hdr = self.headers.get(DEADLINE_HEADER)
            contract_note_header(DEADLINE_HEADER, direction="read")
            if not hdr:
                return None
            try:
                return max(float(hdr) / 1e3, 0.0)
            except ValueError:
                return None

        def _text(self, code: int, text: str, ctype="text/plain") -> None:
            data = text.encode()
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _body(self) -> dict:
            n = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(n) or b"{}")

        # -- GET ---------------------------------------------------------------

        def do_GET(self) -> None:
            if self.path in ("/healthz", "/v2/health/ready", "/v2/health/live"):
                self._json(200, {"status": "ok", "name": server.name})
                return
            if self.path == "/metrics":
                self._text(200, server.metrics_text())
                return
            if self.path == "/debug/device":
                return self._json(200, server.device_payload())
            if self.path.startswith("/debug/traces"):
                return self._json(200, debug_traces_payload(self.path))
            if self.path == "/debug/profile":
                return self._json(200, {"active": profiler.active()})
            if self.path.startswith("/debug/spans/export"):
                # Fleet-trace drain (obs/fleet.py): completed spans +
                # this process's clock, for cross-host stitching.
                return self._json(200, spans_export_payload(
                    process=f"server:{server.name}"))
            if self.path == "/v1/models":
                self._json(200, {"models": server.model_names()})
                return
            if self.path == "/v2/repository/index":
                if server.repository is None:
                    self._json(200, {"models": [
                        {"name": server.name, "state": "READY"}]})
                else:
                    self._json(200, {"models": server.repository.index()})
                return
            m = _V2_MODEL.match(self.path)
            if m:
                try:
                    cfg = server.model_config(m.group(1))
                except KeyError:
                    return self._json(404, {"error": f"no model {m.group(1)}"})
                self._json(200, {
                    "name": m.group(1),
                    "platform": "kubeflow-tpu-llm",
                    "inputs": [{"name": "text", "datatype": "BYTES",
                                "shape": [-1]}],
                    "outputs": [{"name": "text", "datatype": "BYTES",
                                 "shape": [-1]}],
                    "config": {"vocab_size": cfg.vocab_size,
                               "max_seq_len": cfg.max_seq_len},
                })
                return
            self._json(404, {"error": f"not found: {self.path}"})

        # -- POST --------------------------------------------------------------

        def do_POST(self) -> None:
            self._t_entry = time.monotonic()
            server.track(1)
            tracer = get_tracer()
            contract_note_header(TRACE_HEADER, direction="read")
            try:
                # Joins the router's trace via X-Kftpu-Trace (or roots a new
                # one for direct-to-replica requests); every generation path
                # below parents its engine-side spans on this span through
                # the contextvar.
                with tracer.span(
                        "server.request",
                        parent=tracer.extract(
                            self.headers.get(TRACE_HEADER)),
                        path=self.path, server=server.name):
                    if self.path.split("?", 1)[0] == "/v1/handoff":
                        # Binary payload — must not ride the JSON drain.
                        return self._handoff()
                    # Always drain the body first: HTTP/1.1 keep-alive
                    # breaks if unread bytes remain on the connection.
                    body = self._body()
                    if self.path.startswith("/debug/profile/"):
                        return self._profile(self.path.rsplit("/", 1)[1],
                                             body)
                    repo = _REPO_ACTION.match(self.path)
                    if repo:
                        return self._repository_action(repo.group(1),
                                                       repo.group(2))
                    m = _V1_PREDICT.match(self.path)
                    if m:
                        return self._v1_predict(body, m.group(1))
                    m = _V1_EXPLAIN.match(self.path)
                    if m:
                        return self._v1_explain(body, m.group(1))
                    m = _V2_INFER.match(self.path)
                    if m:
                        return self._v2_infer(body, m.group(1))
                    if self.path == "/v1/completions":
                        return self._completions(body, chat=False)
                    if self.path == "/v1/chat/completions":
                        return self._completions(body, chat=True)
                    self._json(404, {"error": f"not found: {self.path}"})
            except KeyError as exc:
                self._json(404, {"error": str(exc)})
            except ValueError as exc:
                self._json(400, {"error": str(exc)})
            except EngineOverloaded as exc:
                # Bounded admission: shed fast with an explicit retry hint
                # instead of queueing the client into a timeout.
                self._json(429, {"error": str(exc)}, headers={
                    "Retry-After": str(max(1, int(exc.retry_after)))})
            except TimeoutError as exc:
                self._json(504, {"error": str(exc)})
            except Exception as exc:   # surface, don't hide
                self._json(500, {"error": f"{type(exc).__name__}: {exc}"})
            finally:
                server.track(-1)

        def _profile(self, action: str, body: dict) -> None:
            """Operator capture of the live replica (obs/profiler.py)."""
            if action == "start":
                try:
                    return self._json(
                        200, server.start_profile(body.get("seconds")))
                except RuntimeError as exc:        # one capture at a time
                    return self._json(409, {"error": str(exc)})
            if action == "stop":
                return self._json(200, server.stop_profile())
            self._json(404, {"error": f"not found: {self.path}"})

        def _repository_action(self, name: str, action: str) -> None:
            if server.repository is None:
                return self._json(400, {"error": "single-model server"})
            if action == "load":
                server.repository.load(name)
            else:
                server.repository.unload(name)
            self._json(200, {"name": name, "state": "READY"
                             if action == "load" else "UNLOADED"})

        def _qos(self, body: dict) -> str:
            """QoS class from the ``X-Kftpu-Qos`` header (body ``qos``
            field as the headerless fallback). Unknown classes fail loudly
            (engine.submit raises → HTTP 400) rather than silently
            demoting a tenant to the default tier."""
            contract_note_header(QOS_HEADER, direction="read")
            raw = self.headers.get(QOS_HEADER) or body.get("qos") \
                or QOS_DEFAULT
            return str(raw).strip().lower()

        def _decode_backend(self) -> Optional[str]:
            """Decode-pool backend the token-aware router picked for this
            request's KV handoff (absent = unified local decode)."""
            contract_note_header(DECODE_BACKEND_HEADER, direction="read")
            url = self.headers.get(DECODE_BACKEND_HEADER)
            return url.strip() if url else None

        def _decode_alts(self) -> tuple:
            """Alternate decode backends for the handoff's bounded
            cross-replica retry (router-stamped; absent = no retry)."""
            contract_note_header(DECODE_ALTS_HEADER, direction="read")
            raw = self.headers.get(DECODE_ALTS_HEADER) or ""
            return tuple(u.strip() for u in raw.split(",") if u.strip())

        def _generate_text(self, prompt: str, body: dict,
                           model: Optional[str],
                           strict: bool = False) -> tuple[str, Request]:
            return server.generate_text(prompt, body, model, strict=strict,
                                        deadline_s=self._deadline_s(),
                                        qos=self._qos(body),
                                        decode_url=self._decode_backend(),
                                        decode_alts=self._decode_alts())

        def _v1_predict(self, body: dict, model: str) -> None:
            instances = body.get("instances")
            if not isinstance(instances, list):
                raise ValueError("body must contain 'instances': [...]")
            preds = [self._generate_text(str(inst), body, model,
                                         strict=True)[0]
                     for inst in instances]
            self._json(200, {"predictions": preds})

        def _v1_explain(self, body: dict, model: str) -> None:
            instances = body.get("instances")
            if not isinstance(instances, list):
                raise ValueError("body must contain 'instances': [...]")
            exps = [server.explain_text(str(inst), model)
                    for inst in instances]
            self._json(200, {"explanations": exps})

        def _v2_infer(self, body: dict, model: str) -> None:
            inputs = body.get("inputs")
            if not isinstance(inputs, list) or not inputs:
                raise ValueError("body must contain 'inputs': [...]")
            texts = []
            for inp in inputs:
                for datum in inp.get("data", []):
                    texts.append(self._generate_text(str(datum), body,
                                                     model, strict=True)[0])
            self._json(200, {
                "model_name": model,
                "outputs": [{"name": "text", "datatype": "BYTES",
                             "shape": [len(texts)], "data": texts}],
            })

        def _model_id(self, body: dict) -> Optional[str]:
            """Requested model id: the X-Kftpu-Model header (the fleet
            router's routing key) wins; the OpenAI ``"model"`` body
            field is the headerless fallback."""
            contract_note_header(MODEL_HEADER, direction="read")
            hdr = self.headers.get(MODEL_HEADER)
            return hdr.strip() if hdr else body.get("model")

        def _completions(self, body: dict, *, chat: bool) -> None:
            model = self._model_id(body)
            if chat:
                msgs = body.get("messages", [])
                prompt = "\n".join(f"{m.get('role', 'user')}: {m.get('content', '')}"
                                   for m in msgs) + "\nassistant:"
            else:
                prompt = body.get("prompt", "")
                if isinstance(prompt, list):
                    prompt = prompt[0] if prompt else ""
            if body.get("stream"):
                return self._completions_stream(prompt, body, chat=chat,
                                                model=model)
            text, req = self._generate_text(prompt, body, model)
            usage = {"prompt_tokens": len(req.prompt_tokens),
                     "completion_tokens": len(req.output_tokens),
                     "total_tokens": len(req.prompt_tokens) + len(req.output_tokens)}
            if chat:
                choice = {"index": 0, "finish_reason": req.finish_reason,
                          "message": {"role": "assistant", "content": text}}
                obj = "chat.completion"
            else:
                # token_ids: what the engine emitted, before detokenizing —
                # a client with its own tokenizer decodes these (the
                # bundled byte tokenizer has no text for ids above 258).
                choice = {"index": 0, "finish_reason": req.finish_reason,
                          "text": text,
                          "token_ids": list(req.output_tokens)}
                obj = "text_completion"
            self._json(200, {
                "id": req.id, "object": obj, "created": int(time.time()),
                "model": model or server.name, "choices": [choice],
                "usage": usage,
            })

        def _send_sse_headers(self) -> None:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

        def _chunk(self, data: str) -> None:
            payload = f"data: {data}\n\n".encode()
            self.wfile.write(f"{len(payload):x}\r\n".encode()
                             + payload + b"\r\n")
            self.wfile.flush()

        def _stream_tokens(self, req, tokenizer, *, chat: bool,
                           model: Optional[str], timeout: float,
                           with_token_ids: bool = False,
                           entry_overhead_s: Optional[float] = None
                           ) -> None:
            """Send SSE headers and stream one engine request's tokens
            to the client (the local-decode half of every streaming
            path: unified, decode-side adoption, and the recompute
            fallback). ``with_token_ids`` adds the raw token id to each
            chunk — the handoff relay uses it so a non-streaming caller
            can re-decode the WHOLE sequence at once (piecewise byte
            decoding would mangle multi-byte characters).
            ``entry_overhead_s`` (handler entry to ``engine.submit``
            returned): given, the first chunk written adds the time since
            the engine's first token and the sum is observed as the
            server's first-byte overhead."""
            self._send_sse_headers()
            first_byte_due = entry_overhead_s is not None
            mine = StreamSums()
            try:
                while True:
                    try:
                        tok = req.stream.get(timeout=timeout + 1.0)
                    except queue.Empty:
                        # Engine never finished within the deadline
                        # (its own reaper should have; this is the
                        # wedged-scheduler fallback): cancel so a
                        # recovering engine drops the orphan.
                        req.cancel()
                        break
                    if tok is None:
                        break
                    if tok == tokenizer.eos_id:
                        continue
                    t_got = time.monotonic()
                    # The stamp first, then whether a later token waits: a
                    # round that lands between the two reads makes this
                    # token one that fell behind, never a sample against a
                    # stamp that is not its own.
                    ready_t = req.tokens_ready_time
                    caught_up = req.stream.empty()
                    piece = tokenizer.decode([tok])
                    if chat:
                        delta = {"choices": [
                            {"index": 0, "delta": {"content": piece}}]}
                    else:
                        delta = {"choices": [{"index": 0,
                                              "text": piece}]}
                    if with_token_ids:
                        delta["choices"][0]["token"] = tok
                    self._chunk(json.dumps({"id": req.id, "object": "chunk",
                                            "model": model or server.name,
                                            **delta}))
                    t_sent = time.monotonic()
                    mine.chunks += 1
                    mine.write_s += t_sent - t_got
                    if caught_up and ready_t is not None \
                            and ready_t <= t_got:
                        mine.wake_s += t_got - ready_t
                        mine.wake_n += 1
                    else:
                        mine.behind += 1
                    if mine.chunks == STREAM_FOLD_CHUNKS:
                        server.fold_stream(mine)
                    if first_byte_due:
                        first_byte_due = False
                        if req.first_token_time is not None:
                            server.observe_first_byte_overhead(
                                entry_overhead_s + t_sent
                                - req.first_token_time)
            except OSError:
                # Client hung up mid-stream: free the slot and its KV
                # pages now instead of decoding to completion for a
                # reader that is gone.
                req.cancel()
                self.close_connection = True
                return
            finally:
                server.fold_stream(mine)
            self._chunk("[DONE]")
            self.wfile.write(b"0\r\n\r\n")

        def _completions_stream(self, prompt: str, body: dict, *, chat: bool,
                                model: Optional[str]) -> None:
            # The pre-hook applies to the prompt like the non-streaming path;
            # the post-hook cannot (output streams piecewise) — a documented
            # transformer limitation, matching kserve's non-streaming scope.
            if server.transformer is not None:
                prompt = server.transformer(prompt, "pre")
            timeout = server.request_timeout(body, self._deadline_s())
            adapter = server.resolve_adapter(model)
            with server.lease(None if adapter else model) \
                    as (engine, tokenizer, _):
                toks = tokenizer.encode(prompt)
                decode_url = self._decode_backend()
                wants_handoff = engine.role == "prefill" and decode_url
                handoff_flag: Optional[bool] = None
                if engine.role == "prefill":
                    handoff_flag = bool(wants_handoff)
                req = engine.submit(toks,
                                    server.sampling_from(body, tokenizer),
                                    deadline=time.monotonic() + timeout,
                                    trace_parent=get_tracer().current(),
                                    qos=self._qos(body),
                                    handoff=handoff_flag, adapter=adapter)
                entry_overhead_s = time.monotonic() - self._t_entry
                if wants_handoff:
                    return self._stream_disaggregated(
                        engine, tokenizer, req, toks, body, decode_url,
                        chat=chat, model=model, timeout=timeout,
                        decode_alts=self._decode_alts())
                self._stream_tokens(req, tokenizer, chat=chat, model=model,
                                    timeout=timeout,
                                    entry_overhead_s=entry_overhead_s)

        def _stream_disaggregated(self, engine, tokenizer, req,
                                  toks: list[int], body: dict,
                                  decode_url: str, *, chat: bool,
                                  model: Optional[str],
                                  timeout: float,
                                  decode_alts: tuple = ()) -> None:
            """Streaming handoff relay. The client's SSE response opens
            only AFTER the decode side acks (or the fallback engages) —
            a prefill replica dying mid-handoff therefore dies with
            ZERO response bytes on the wire, which is exactly the
            condition under which the router's connect-failure retry
            can requeue the request onto a surviving pool."""
            tracer = get_tracer()
            if not req.done.wait(timeout + 1.0):
                req.cancel()
                return self._json(504, {"error": f"request {req.id} timed "
                                        "out in prefill"})
            if req.finish_reason != "handoff":
                # Finished at the first token (stop/length) — nothing to
                # hand off; stream the one-token answer. Reap failures
                # surface through the usual mapping.
                if req.finish_reason in ("stop", "length"):
                    return self._stream_tokens(req, tokenizer, chat=chat,
                                               model=model, timeout=timeout)
                _raise_for_reaped(req)
                raise RuntimeError(
                    f"request {req.id} ended {req.finish_reason!r}")
            candidates = [decode_url] + [u for u in decode_alts
                                         if u and u != decode_url]
            with tracer.span("engine.handoff", backend=decode_url,
                             request=req.id) as sp:
                try:
                    used_url, conn, resp = open_handoff_with_retry(
                        engine, candidates, req.handoff, chat=chat,
                        qos=self._qos(body),
                        trace_fn=lambda: tracer.inject(sp),
                        deadline_s=timeout, timeout=timeout + 5.0)
                    sp.set_attrs(backend=used_url)
                    if used_url != decode_url:
                        # Placed decode replica died between pick and
                        # handoff — mark the span so the fleet stitcher
                        # attributes this hop as a failover.
                        sp.add_event("connect_failure",
                                     backend=decode_url)
                except OSError as exc:
                    # Every replica exhausted, never acked: recompute
                    # locally (failure = recompute, never a drop).
                    sp.set_attrs(error=str(exc), fallback="recompute")
                    engine.metrics.note_handoff("fallback")
                    engine.fail_handoff(req.id)
                    req2 = engine.submit(
                        toks, server.sampling_from(body, tokenizer),
                        deadline=time.monotonic() + timeout,
                        trace_parent=tracer.current(),
                        qos=self._qos(body), handoff=False,
                        request_id=f"{req.id}-recompute")
                    return self._stream_tokens(req2, tokenizer, chat=chat,
                                               model=model, timeout=timeout)
                engine.complete_handoff(req.id)
            self._send_sse_headers()
            try:
                # First token was sampled prefill-side; its chunk opens
                # the client stream, then decode chunks relay verbatim.
                first = [t for t in req.output_tokens
                         if t != tokenizer.eos_id]
                if first:
                    piece = tokenizer.decode(first)
                    delta = ({"choices": [{"index": 0,
                                           "delta": {"content": piece}}]}
                             if chat else
                             {"choices": [{"index": 0, "text": piece}]})
                    self._chunk(json.dumps({"id": req.id, "object": "chunk",
                                            "model": model or server.name,
                                            **delta}))
                done = False
                try:
                    for data in iter_sse_data(resp):
                        self._chunk(data)
                        if data == "[DONE]":
                            done = True
                            break
                finally:
                    conn.close()
                if done:
                    self.wfile.write(b"0\r\n\r\n")
                    return
                # Upstream ended without [DONE]: the decode side died
                # mid-stream — close so the client sees an explicit error.
                self.close_connection = True
            except OSError:
                self.close_connection = True

        def _handoff(self) -> None:
            """Decode side of the handoff: adopt the payload into this
            engine's pool and stream the SECOND token onward as SSE.
            Sending the 200 response line IS the ack — the payload bytes
            are in this process's memory, so the prefill side's page
            hold can release."""
            if server.engine is None:
                return self._json(
                    400, {"error": "handoff needs a single-engine server"})
            from kubeflow_tpu.serve.handoff import HandoffPayload

            # Capability negotiation BEFORE touching the wire blob
            # (ISSUE 17): a mixed-version or mixed-dtype fleet must
            # reject the submit cleanly — an explicit 409 the prefill
            # side turns into retry-elsewhere/recompute — never decode
            # bytes it would misinterpret into corrupt pages.
            contract_note_header(HANDOFF_WIRE_HEADER, direction="read")
            contract_note_header(HANDOFF_DTYPE_HEADER, direction="read")
            wire_v = (self.headers.get(HANDOFF_WIRE_HEADER) or "").strip()
            if wire_v and wire_v not in SUPPORTED_HANDOFF_WIRE:
                return self._json(409, {
                    "error": f"handoff wire version {wire_v!r} not "
                             f"supported (speaks {SUPPORTED_HANDOFF_WIRE})"})
            dtype = (self.headers.get(HANDOFF_DTYPE_HEADER) or "").strip()
            want = "int8" if server.engine.kv_quant else "full"
            if dtype and dtype != want:
                return self._json(409, {
                    "error": f"handoff cache-dtype mismatch: payload is "
                             f"{dtype!r}, this pool stores {want!r}"})
            n = int(self.headers.get("Content-Length", 0))
            raw = self.rfile.read(n)
            chat = "chat=1" in (self.path.split("?", 1) + [""])[1]
            payload = HandoffPayload.from_wire(raw)
            deadline_s = self._deadline_s()
            timeout = deadline_s if deadline_s is not None else 300.0
            try:
                req = server.engine.submit_handoff(
                    payload, deadline=time.monotonic() + timeout,
                    trace_parent=get_tracer().current())
            except ValueError as exc:
                # submit_handoff's own validation (shape/dtype/deadline)
                # is the headerless fleet's backstop — same clean reject.
                return self._json(409, {"error": str(exc)})
            self._stream_tokens(req, server.tokenizer, chat=chat,
                                model=None, timeout=timeout,
                                with_token_ids=True)

    return Handler
