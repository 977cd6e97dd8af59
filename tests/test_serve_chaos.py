"""Serve-side chaos suite (ISSUE 2 tentpole #5): two real model servers
behind the hardened router, faults injected mid-traffic via serve/faults.py.

Invariants asserted after EVERY scenario:
- no hangs: every client thread joins within its bound;
- every in-flight request completes (200) or fails with an explicit HTTP
  error — never a silent stall;
- the router recovers: a fresh request succeeds afterwards;
- paged-KV refcounts balance: once quiescent, both engines hold zero pages.

The kill scenario runs LAST — it destroys one replica for good."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest
import jax

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.faults import ChaosProxy, kill_model_server
from kubeflow_tpu.serve.router import DEADLINE_HEADER, Router
from kubeflow_tpu.serve.server import ModelServer

EXPLICIT_STATUSES = {200, 429, 500, 502, 503, 504}


@pytest.fixture(scope="module")
def stack():
    cfg = preset("tiny", vocab_size=512)      # byte tokenizer fits
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def mk(name):
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4,
                         # Explicit: every scenario here runs with a decode
                         # round potentially in flight (ISSUE 4) — the
                         # quiescence audits below must hold regardless.
                         pipelined_decode=True),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    a, b = mk("replica-a"), mk("replica-b")
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.4,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [a.url, b.url]})
    router.start()
    yield a, b, router
    router.stop()
    for s in (a, b):
        try:
            s.stop()
        except OSError:
            pass


def completion(url: str, *, timeout_s: float = 10.0, max_tokens: int = 8,
               prompt: str = "chaos", qos: str = "") -> int:
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "timeout": timeout_s}).encode()
    headers = {"Content-Type": "application/json",
               DEADLINE_HEADER: str(int(timeout_s * 1e3))}
    if qos:
        headers["X-Kftpu-Qos"] = qos
    req = urllib.request.Request(
        url + "/v1/completions", data=body, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
            return r.status
    except urllib.error.HTTPError as exc:
        exc.read()
        return exc.code
    except OSError:
        return 502    # connection-level failure: explicit, not a hang


def fire(url: str, n: int, concurrency: int = 4, *,
         mid_fault=None, fault_after: int = 2, **kw) -> list[int]:
    """Closed-loop client pool; optionally triggers ``mid_fault()`` once
    ``fault_after`` requests have completed. Asserts the no-hang bound."""
    results: list[int] = []
    lock = threading.Lock()
    it = iter(range(n))
    fault_fired = threading.Event()

    def client():
        while True:
            with lock:
                nxt = next(it, None)
            if nxt is None:
                return
            status = completion(url, **kw)
            with lock:
                results.append(status)
                if (mid_fault is not None and not fault_fired.is_set()
                        and len(results) >= fault_after):
                    fault_fired.set()
                    mid_fault()

    threads = [threading.Thread(target=client)
               for _ in range(max(1, concurrency))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90.0)
        assert not t.is_alive(), "client thread hung (no-hang invariant)"
    assert len(results) == n
    return results


def audit_quiescent(*servers, deadline_s: float = 20.0) -> None:
    """Post-scenario refcount audit: cancel anything stranded (the operator
    analog of process teardown), drive the reaper, assert zero page leaks.
    Handoff holds (pages backing an exported-but-never-acked payload)
    count as stranded state too — their requests cancel and the reaper
    must free them."""
    for srv in servers:
        eng = srv.engine
        for s in eng.slots:
            if s is not None:
                s.request.cancel()
        for lane in (eng._backlog, eng._preempted):
            for req in lane:
                req.cancel()
        for ch in list(eng._chunkings):
            ch.request.cancel()
        for hreq, _pages in list(eng._handoff_holds.values()):
            hreq.cancel()
        deadline = time.monotonic() + deadline_s
        while eng.kv_pages_in_use() > 0 or eng._handoff_holds:
            eng.step()
            assert time.monotonic() < deadline, \
                f"{srv.name}: KV pages leaked after scenario"
        eng._allocator.assert_quiescent()
        # Pipelined dispatch: the reap path must also have drained any
        # decode round left in flight by the scenario.
        while eng._rounds:
            eng.step()
        assert not eng._rounds, f"{srv.name}: in-flight round stranded"


def test_chaos_5xx_burst_ejects_then_recovers(stack):
    a, b, router = stack
    proxy = ChaosProxy(a.url)
    proxy.start()
    try:
        router.set_backends({"latest": [proxy.url, b.url]})
        proxy.fail_next(4, code=503)
        results = fire(router.url, 12, timeout_s=10.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 6, results
        assert router.snapshot()["ejections"] >= 1
        assert proxy.stats["injected_5xx"] >= 2     # burst actually landed
        # Recovery: end the burst (ejection may have diverted traffic
        # before the backend consumed all 4 injected faults), let the
        # ejection window pass, then traffic must be clean — including the
        # half-open probe that reinstates the backend.
        proxy.fail_next(0)
        time.sleep(0.5)
        assert all(s == 200 for s in fire(router.url, 4, timeout_s=10.0))
    finally:
        proxy.stop()
        router.set_backends({"latest": [a.url, b.url]})
    audit_quiescent(a, b)


def test_chaos_wedged_replica_fails_within_deadline(stack):
    a, b, router = stack
    proxy = ChaosProxy(a.url)
    proxy.start()
    try:
        router.set_backends({"latest": [proxy.url, b.url]})
        proxy.wedge()
        t0 = time.monotonic()
        results = fire(router.url, 8, timeout_s=3.0)
        elapsed = time.monotonic() - t0
        assert set(results) <= EXPLICIT_STATUSES
        # The healthy replica keeps serving: wedged picks retry onto b
        # after the deadline-bounded upstream wait.
        assert results.count(200) >= 4, results
        assert elapsed < 60.0
        proxy.unwedge()
        time.sleep(0.5)
        assert all(s == 200 for s in fire(router.url, 4, timeout_s=10.0))
    finally:
        proxy.stop()
        router.set_backends({"latest": [a.url, b.url]})
    audit_quiescent(a, b)


def test_chaos_scale_down_under_load_drains_cleanly(stack):
    """Scale-down analog: replica a leaves the rotation while its request
    is still streaming — the in-flight request completes, new traffic goes
    to b, and a's engine drains to zero pages."""
    a, b, router = stack
    router.set_backends({"latest": [a.url]})
    got: dict = {}

    def long_request():
        got["status"] = completion(router.url, timeout_s=15.0,
                                   max_tokens=48)

    t = threading.Thread(target=long_request)
    t.start()
    # wait until a is actually serving it
    deadline = time.monotonic() + 10.0
    while a.in_flight == 0 and not got:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    router.set_backends({"latest": [b.url]})     # a retired mid-request
    t.join(timeout=30.0)
    assert not t.is_alive(), "in-flight request hung through scale-down"
    assert got["status"] == 200, "draining replica dropped its request"
    assert all(s == 200 for s in fire(router.url, 4, timeout_s=10.0))
    router.set_backends({"latest": [a.url, b.url]})
    audit_quiescent(a, b)


def test_chaos_halt_with_round_in_flight_reaps_clean():
    """ISSUE 4: the scheduler halting between dispatch and consume — the
    worst spot a SIGKILL can land with pipelined dispatch — must leave a
    state the recovery audit can still balance: the stranded in-flight
    round drains, cancelled requests mask their late tokens, and every
    paged-KV refcount returns to zero."""
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=96, paged=True,
                     page_size=16, chunked_prefill_tokens=16,
                     decode_steps=4, pipelined_decode=True),
        params=params)
    reqs = [eng.submit([i + 1] * 20, SamplingParams(max_new_tokens=60))
            for i in range(2)]
    for _ in range(3):
        eng.step()
    assert eng._rounds, "pipelining should have a round in flight here"
    emitted_at_halt = [len(r.output_tokens) for r in reqs]
    # SIGKILL analog: the loop never consumes that round. Recovery cancels
    # the stranded requests and drives step() like a supervisor would.
    for r in reqs:
        r.cancel()
    deadline = time.monotonic() + 20.0
    while eng.kv_pages_in_use() > 0 or eng._rounds:
        eng.step()
        assert time.monotonic() < deadline, "recovery did not quiesce"
    eng._allocator.assert_quiescent()
    assert all(r.done.is_set() and r.finish_reason == "cancelled"
               for r in reqs)
    # The stranded round's results never leaked into cancelled streams.
    assert [len(r.output_tokens) for r in reqs] == emitted_at_halt


def test_chaos_qos_overload_sheds_batch_first(stack):
    """ISSUE 6 acceptance (``qos_overload``): ~2x sustained overload with
    mixed interactive+batch classes through the router. Invariants:

    - batch absorbs ALL shedding (429 at the door + queue sheds);
      interactive is never shed;
    - interactive queue-delay p95 stays within its declared budget —
      delivered by strict-priority dequeue + cross-class preemption, not
      by shedding (its shed count is zero);
    - after the storm (preemptions included), every engine drains to
      zero pages and ``assert_quiescent`` holds."""
    from kubeflow_tpu.core.serving import QoSClassPolicy

    a, b, router = stack
    I_BUDGET_S = 5.0
    engines = [a.engine, b.engine]
    for eng in engines:
        eng.max_queue = 4
        eng.qos_policies = {
            "batch": QoSClassPolicy(max_queue=1),
            "interactive": QoSClassPolicy(queue_delay_budget=I_BUDGET_S)}
    try:
        results: dict[str, list[int]] = {"interactive": [], "batch": []}
        threads = []
        for cls, nclients in (("interactive", 3), ("batch", 3)):
            def pool(cls=cls):
                got = fire(router.url, 9, concurrency=3, timeout_s=10.0,
                           max_tokens=6, qos=cls)
                results[cls].extend(got)
            t = threading.Thread(target=pool)
            threads.append(t)
            t.start()
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive(), "client pool hung under qos overload"
        for cls in results:
            assert set(results[cls]) <= EXPLICIT_STATUSES, results[cls]
        # Graceful, prioritized degradation: interactive all served.
        assert all(s == 200 for s in results["interactive"]), \
            results["interactive"]
        shed = {"interactive": 0, "batch": 0}
        qd_p95 = []
        for eng in engines:
            snap = eng.metrics.snapshot()
            for cls in shed:
                shed[cls] += snap.get("qos", {}).get(cls, {}).get("shed", 0)
            qcls = snap.get("qos", {}).get("interactive", {})
            if "queue_delay_p95_ms" in qcls:
                qd_p95.append(qcls["queue_delay_p95_ms"])
        assert shed["interactive"] == 0, "interactive was shed under overload"
        if 429 in results["batch"]:
            assert shed["batch"] > 0
        assert qd_p95, "no interactive queue-delay signal recorded"
        assert max(qd_p95) <= I_BUDGET_S * 1e3, \
            f"interactive queue-delay p95 {max(qd_p95):.0f}ms over budget"
    finally:
        for eng in engines:
            eng.max_queue = 0
            eng.qos_policies = {}
    audit_quiescent(a, b)


@pytest.mark.slow  # tier-1 budget: sanitizer fleet under kill loop, ~8s
def test_chaos_refcount_sanitizer_kill_mid_traffic(monkeypatch):
    """ISSUE 7: one chaos scenario end-to-end under
    ``KFTPU_SANITIZE=refcount`` — SIGKILL analog mid-traffic, then the
    recovery audit must produce a PER-OWNER zero-leak report: every page
    reference was stamped with the request that took it, and every stamp
    was popped by a balancing free. Self-contained stack (the sanitize
    mode must be on BEFORE the engines build their allocators)."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def mk(name):
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4,
                         pipelined_decode=True),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    a, b = mk("rc-a"), mk("rc-b")
    assert a.engine._allocator.refcount_debug, \
        "refcount mode not active at allocator construction"
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.4,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [a.url, b.url]})
    router.start()
    try:
        results = fire(router.url, 12, timeout_s=6.0,
                       mid_fault=lambda: kill_model_server(b),
                       fault_after=2)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        audit_quiescent(a, b)
        for srv in (a, b):
            alloc = srv.engine._allocator
            # traffic really was stamped, and every stamp was popped:
            # the per-owner report must be EMPTY, not merely small
            assert alloc.stats["stamped_allocs"] > 0, \
                f"{srv.name}: no stamped page traffic recorded"
            report = alloc.leak_report_by_owner()
            assert report == {}, \
                f"{srv.name}: per-owner leaks after recovery: {report}"
            alloc.assert_quiescent()
    finally:
        router.stop()
        for s in (a, b):
            try:
                s.stop()
            except OSError:
                pass


@pytest.mark.slow  # tier-1 budget: ~8s; the handoff module's
# unified-fallback test keeps the recompute lane in tier-1
def test_chaos_prefill_kill_mid_handoff_unified_fallback(monkeypatch):
    """ISSUE 12: SIGKILL the PREFILL replica of a disaggregated fleet
    mid-handoff, under ``KFTPU_SANITIZE=refcount``. Invariants:

    - a handoff hold stranded by the kill (pages exported, decode side
      never acked) reaps refcount-balanced — ``assert_quiescent`` holds
      on BOTH pools and the per-owner report names ZERO leaks;
    - continuing traffic requeues onto the surviving pool: the router's
      token-aware placement falls back to the decode replica serving
      whole requests locally (unified fallback), explicitly — no hangs."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def mk(name, role):
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4,
                         role=role),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    pre, dec = mk("pre-a", "prefill"), mk("dec-b", "decode")
    assert pre.engine._allocator.refcount_debug
    proxy = ChaosProxy(pre.url)   # the prefill replica's "process"
    proxy.start()
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=5.0,
                    max_retries=2, upstream_timeout=30.0)
    router.scrape_interval = 0.1
    router.set_pools({"prefill": [proxy.url], "decode": [dec.url]})
    router.start()
    try:
        # Disaggregated traffic flows: prefill → handoff → decode.
        results = fire(router.url, 6, timeout_s=10.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        assert pre.engine.metrics.snapshot()["handoffs_exported"] >= 1
        assert dec.engine.metrics.snapshot()["handoffs_adopted"] >= 1
        # Strand a MID-handoff state: exported (pages in the ack hold),
        # decode side never told — exactly where a SIGKILL lands between
        # export and ack.
        from kubeflow_tpu.serve.engine import SamplingParams as SP

        orphan = pre.engine.submit([7] * 24, SP(max_new_tokens=8),
                                   handoff=True)
        assert orphan.done.wait(20.0)
        assert orphan.finish_reason == "handoff"
        assert pre.engine._handoff_holds, "no hold backing the payload"
        held_pages = pre.engine.kv_pages_in_use()
        assert held_pages > 0
        # SIGKILL the prefill replica mid-handoff.
        proxy.drop()
        kill_model_server(pre)
        time.sleep(0.5)     # scrape loop ejects the corpse from the pool
        # Continuing traffic lands on the SURVIVING pool (the decode
        # replica serving whole requests locally — unified fallback).
        results = fire(router.url, 8, timeout_s=10.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        assert router.snapshot()["disagg_fallbacks"] >= 1
        # Recovery audit: BOTH pools balance their books; in refcount
        # mode the per-owner report must be EMPTY, not merely small.
        audit_quiescent(pre, dec)
        for srv in (pre, dec):
            alloc = srv.engine._allocator
            assert alloc.stats["stamped_allocs"] > 0
            report = alloc.leak_report_by_owner()
            assert report == {}, \
                f"{srv.name}: per-owner leaks after mid-handoff kill: " \
                f"{report}"
            alloc.assert_quiescent()
    finally:
        proxy.stop()
        router.stop()
        for s in (pre, dec):
            try:
                s.stop()
            except OSError:
                pass


@pytest.mark.slow  # tier-1 budget: ~10s; COW-cancel also pinned by kvtier
def test_chaos_cancel_while_shared(monkeypatch):
    """Tiered KV cache (ISSUE 13): cancel a request whose prefix pages
    are SHARED ref>0 with another in-flight request. The co-sharer must
    finish with correct greedy output (its references pin the pages),
    and after it completes the per-owner report must name ZERO leaks on
    the device tier — the cancel freed exactly the victim's own
    references, never the shared content."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    mk = lambda prefix: LLMEngine(  # noqa: E731
        cfg, BatchingSpec(max_batch_size=2, max_seq_len=96, paged=True,
                          page_size=16, chunked_prefill_tokens=16,
                          decode_steps=4,
                          enable_prefix_caching=prefix),
        params=params)
    eng, base = mk(True), mk(False)
    assert eng._allocator.refcount_debug
    sp = SamplingParams(max_new_tokens=24)
    prompt = [9, 2, 9, 4, 9, 6, 9, 8] * 4
    victim = eng.submit(list(prompt), sp)
    for _ in range(4):
        eng.step()                      # victim prefills + registers
    sharer = eng.submit(list(prompt), sp)
    for _ in range(3):
        eng.step()                      # sharer matches ref>0 pages
    assert eng.kv_tier_stats()["prefix_hits"] >= 1
    victim.cancel()                     # mid-decode, pages shared
    deadline = time.monotonic() + 30.0
    while not sharer.done.is_set():
        eng.step()
        assert time.monotonic() < deadline, "sharer hung after cancel"
    assert victim.finish_reason == "cancelled"
    b = base.submit(list(prompt), sp)
    while not b.done.is_set():
        base.step()
    assert list(sharer.output_tokens) == list(b.output_tokens)
    while eng.kv_pages_in_use() > 0:
        eng.step()
        assert time.monotonic() < deadline
    assert eng._allocator.leak_report_by_owner() == {}
    eng._allocator.assert_quiescent()


@pytest.mark.slow
def test_chaos_kill_mid_migration(monkeypatch):
    """Tiered KV cache (ISSUE 13): SIGKILL a replica while a device→host
    demotion batch is IN FLIGHT on its migration thread. Invariants:
    traffic keeps resolving explicitly on the survivor; the per-owner
    refcount audit names ZERO leaks on BOTH replicas' device pools (the
    demoted pages were freed scheduler-side before the kill — a dead
    migration thread can strand host blobs, never device pages); and
    the host tier stays within budget with no phantom occupancy."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    import kubeflow_tpu.serve.kvtier as kvtier

    real_wire = kvtier.pages_to_wire

    def slow_wire(k, v):
        time.sleep(0.25)                # widen the mid-migration window
        return real_wire(k, v)

    monkeypatch.setattr(kvtier, "pages_to_wire", slow_wire)
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def mk(name):
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4,
                         host_kv_pages=48, kv_demote_after_s=0.05),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    a, b = mk("mig-a"), mk("mig-b")
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.4,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [b.url, a.url]})
    router.start()
    try:
        results = fire(router.url, 8, timeout_s=6.0)
        assert set(results) <= EXPLICIT_STATUSES
        # Wait for a migration batch to be in flight (or already
        # landed) on b, then kill it mid-flight.
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            with b.engine._kvtier._lock:
                migrating = b.engine._kvtier._migrating
            if migrating > 0 or b.engine.kv_pages_host() > 0:
                break
            time.sleep(0.01)
        assert migrating > 0 or b.engine.kv_pages_host() > 0, \
            "no demotion ever started on b"
        kill_model_server(b)
        # Survivor keeps serving explicitly.
        results = fire(router.url, 8, timeout_s=6.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        audit_quiescent(a, b)
        for srv in (a, b):
            alloc = srv.engine._allocator
            assert alloc.stats["stamped_allocs"] > 0
            report = alloc.leak_report_by_owner()
            assert report == {}, \
                f"{srv.name}: per-owner leaks after mid-migration kill: " \
                f"{report}"
            alloc.assert_quiescent()
            # Host-tier books: in-flight batches drain (the daemon
            # thread survives the server kill) and occupancy stays
            # consistent with the budget — no phantom pages.
            tier = srv.engine._kvtier
            tier.drain_migrations(timeout_s=10.0)
            snap = tier.snapshot()
            assert 0 <= snap["host_pages_resident"] <= 48
            assert snap["migrating_pages"] == 0
    finally:
        router.stop()
        for s in (a, b):
            try:
                s.stop()
            except OSError:
                pass


@pytest.mark.slow
def test_chaos_int8_prefill_kill_mid_handoff(monkeypatch):
    """Quantized fabric under SIGKILL mid-handoff: an int8-pool prefill
    replica dies between export and ack. The hold backed int8 pages AND
    their scale rows — the per-owner audit must name ZERO leaks on both
    replicas (scales share page identity, so a page freed is its scale
    row freed), and the surviving int8 decode replica keeps serving
    token-consistently with a fresh int8 reference engine."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def spec(role):
        return BatchingSpec(max_batch_size=2, max_seq_len=96,
                            paged=True, page_size=16,
                            chunked_prefill_tokens=16, decode_steps=4,
                            kv_cache_dtype="int8", role=role)

    def mk(name, role):
        srv = ModelServer(name, LLMEngine(cfg, spec(role), params=params),
                          port=0)
        srv.start()
        return srv

    pre, dec = mk("q-pre", "prefill"), mk("q-dec", "decode")
    assert pre.engine.kv_quant and "ks" in pre.engine.cache
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=5.0,
                    max_retries=2, upstream_timeout=30.0)
    router.scrape_interval = 0.1
    router.set_pools({"prefill": [pre.url], "decode": [dec.url]})
    router.start()
    try:
        results = fire(router.url, 6, timeout_s=10.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        assert pre.engine.metrics.snapshot()["handoff_bytes_exported"] > 0
        # Strand a mid-handoff hold (quantized pages + scale rows), then
        # SIGKILL the prefill replica.
        orphan = pre.engine.submit([7] * 24, SamplingParams(max_new_tokens=8),
                                   handoff=True)
        assert orphan.done.wait(20.0)
        assert orphan.finish_reason == "handoff"
        assert orphan.handoff.cache_dtype == "int8"
        assert pre.engine.kv_pages_in_use() > 0
        kill_model_server(pre)
        time.sleep(0.5)
        # Survivor still serves; unified fallback on the decode pool.
        results = fire(router.url, 8, timeout_s=10.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        # Token consistency: the survivor's local decode matches a fresh
        # int8 engine on the same prompt (its pool was never corrupted
        # by the dead peer's half-shipped blob).
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        prompt = [3, 1, 4, 1, 5, 9] * 4
        got = dec.engine.generate(list(prompt), sp)
        want = LLMEngine(cfg, spec("unified"),
                         params=params).generate(list(prompt), sp)
        assert got == want, (got, want)
        audit_quiescent(pre, dec)
        for srv in (pre, dec):
            alloc = srv.engine._allocator
            assert alloc.stats["stamped_allocs"] > 0
            assert alloc.leak_report_by_owner() == {}
            alloc.assert_quiescent()
    finally:
        router.stop()
        for s in (pre, dec):
            try:
                s.stop()
            except OSError:
                pass


@pytest.mark.slow
def test_chaos_int8_kill_mid_migration(monkeypatch):
    """Quantized pool under SIGKILL mid-demotion: the migration batch in
    flight carries int8 pages + scale rows (5-tuple queue items → v2
    blobs). Device books must balance to zero per owner on both
    replicas, the host tier stays within budget, and the survivor keeps
    serving token-consistently."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    import kubeflow_tpu.serve.kvtier as kvtier

    real_wire = kvtier.pages_to_wire

    def slow_wire(k, v, **kw):
        time.sleep(0.25)                # widen the mid-migration window
        return real_wire(k, v, **kw)

    monkeypatch.setattr(kvtier, "pages_to_wire", slow_wire)
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def spec():
        return BatchingSpec(max_batch_size=2, max_seq_len=96,
                            paged=True, page_size=16,
                            chunked_prefill_tokens=16, decode_steps=4,
                            kv_cache_dtype="int8",
                            host_kv_pages=48, kv_demote_after_s=0.05)

    def mk(name):
        srv = ModelServer(name, LLMEngine(cfg, spec(), params=params),
                          port=0)
        srv.start()
        return srv

    a, b = mk("qmig-a"), mk("qmig-b")
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.4,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [b.url, a.url]})
    router.start()
    try:
        results = fire(router.url, 8, timeout_s=6.0)
        assert set(results) <= EXPLICIT_STATUSES
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            with b.engine._kvtier._lock:
                migrating = b.engine._kvtier._migrating
            if migrating > 0 or b.engine.kv_pages_host() > 0:
                break
            time.sleep(0.01)
        assert migrating > 0 or b.engine.kv_pages_host() > 0, \
            "no demotion ever started on b"
        kill_model_server(b)
        results = fire(router.url, 8, timeout_s=6.0)
        assert set(results) <= EXPLICIT_STATUSES
        assert results.count(200) >= 4, results
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        prompt = [2, 7, 1, 8, 2, 8] * 4
        got = a.engine.generate(list(prompt), sp)
        want = LLMEngine(cfg, spec(), params=params).generate(
            list(prompt), sp)
        assert got == want, (got, want)
        audit_quiescent(a, b)
        for srv in (a, b):
            alloc = srv.engine._allocator
            assert alloc.stats["stamped_allocs"] > 0
            assert alloc.leak_report_by_owner() == {}
            alloc.assert_quiescent()
            tier = srv.engine._kvtier
            tier.drain_migrations(timeout_s=10.0)
            snap = tier.snapshot()
            assert 0 <= snap["host_pages_resident"] <= 48
            assert snap["migrating_pages"] == 0
    finally:
        router.stop()
        for s in (a, b):
            try:
                s.stop()
            except OSError:
                pass


def test_chaos_zz_replica_kill_mid_traffic(stack):
    """SIGKILL analog mid-traffic (runs last: b never comes back). Requests
    racing the kill resolve explicitly; the router ejects the corpse and
    recovers on the survivor; the dead engine's stranded state reaps to
    zero page leaks."""
    a, b, router = stack

    results = fire(router.url, 12, timeout_s=6.0,
                   mid_fault=lambda: kill_model_server(b), fault_after=2)
    assert set(results) <= EXPLICIT_STATUSES
    assert results.count(200) >= 4, results
    # Router recovered: the survivor serves fresh traffic.
    assert all(s == 200 for s in fire(router.url, 4, timeout_s=10.0))
    snap = router.snapshot()
    assert snap["connect_failures"] >= 1 or snap["http_5xx"] >= 1
    # The killed replica's engine halted where it stood; the reaper must
    # still balance its books (the scheduler loop is dead, so we drive
    # step() by hand — exactly what a recovering supervisor would do).
    audit_quiescent(a, b)
