"""Multi-tenant QoS in the engine scheduler (ISSUE 6 tentpole layer 1+2):
per-class admission quotas and queue-delay budgets, strict-priority
dequeue, shed-lowest-first under overload, cross-class recompute
preemption, and the per-class observability surface (EngineMetrics qos
labels, X-Kftpu-Qos header end-to-end).

The engine fixture is module-scoped and manually stepped; QoS knobs
(qos_policies, max_queue) are plain attributes mutated per test, the
test_serve_lifecycle idiom."""

import json
import time
import urllib.error
import urllib.request

import pytest
import jax

from kubeflow_tpu.core.serving import (
    BatchingSpec, QOS_CLASSES, QoSClassPolicy,
)
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import (
    EngineOverloaded, LLMEngine, SamplingParams,
)


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)     # byte tokenizer fits


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    # Paged so every scenario also audits page-refcount balance.
    return LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=64, paged=True,
                     page_size=8, chunked_prefill_tokens=8,
                     decode_steps=4),
        params=params)


def _drain(engine, reqs=(), max_steps=800):
    for _ in range(max_steps):
        worked = engine.step()
        if worked == 0 and all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("engine did not quiesce")


def _quiesce(engine):
    assert engine.kv_pages_in_use() == 0
    engine._allocator.assert_quiescent()


def test_unknown_qos_class_rejected(engine):
    with pytest.raises(ValueError, match="unknown QoS class"):
        engine.submit([1, 2, 3], SamplingParams(max_new_tokens=2),
                      qos="platinum")


def test_priority_dequeue_interactive_jumps_batch(engine):
    """A later-arriving interactive request is admitted before earlier
    batch requests once a slot frees (strict-priority, FIFO in class)."""
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=24),
                              qos="batch") for i in range(2)]
    engine.step()                          # both slots busy
    engine.qos_preemption = False          # isolate dequeue order
    try:
        b_first = engine.submit([7] * 4, SamplingParams(max_new_tokens=2),
                                qos="batch")
        i_later = engine.submit([8] * 4, SamplingParams(max_new_tokens=2),
                                qos="interactive")
        _drain(engine, blockers + [b_first, i_later])
        assert i_later.first_token_time < b_first.first_token_time, \
            "interactive arrival did not dequeue before queued batch"
    finally:
        engine.qos_preemption = True
    _quiesce(engine)


def test_cross_class_preemption_recompute(engine):
    """An interactive arrival recompute-preempts a running batch slot via
    the preempted lane; the victim resumes later and still completes with
    its full token budget — refcount-balanced throughout."""
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=40),
                              qos="batch") for i in range(2)]
    engine.step()
    before = engine.metrics.snapshot().get("preemptions", 0)
    urgent = engine.submit([9] * 4, SamplingParams(max_new_tokens=4),
                           qos="interactive")
    _drain(engine, blockers + [urgent])
    snap = engine.metrics.snapshot()
    assert snap["preemptions"] > before, "no cross-class preemption fired"
    assert snap["qos"]["batch"]["preempted"] >= 1
    assert snap["qos"]["interactive"].get("preempted", 0) == 0
    assert urgent.finish_reason in ("stop", "length")
    # Preempted batch work resumed and finished with its full budget.
    assert all(b.finish_reason in ("stop", "length") for b in blockers)
    assert all(len(b.output_tokens) == 40 or b.finish_reason == "stop"
               for b in blockers)
    _quiesce(engine)


def test_preemption_never_evicts_same_or_higher_class(engine):
    """A standard arrival must not preempt standard or interactive slots
    — preemption changes WHO degrades, never whether."""
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=16),
                              qos="interactive") for i in range(2)]
    engine.step()
    before = engine.metrics.snapshot().get("preemptions", 0)
    waiting = engine.submit([5] * 4, SamplingParams(max_new_tokens=2),
                            qos="standard")
    for _ in range(3):
        engine.step()
    assert engine.metrics.snapshot().get("preemptions", 0) == before
    _drain(engine, blockers + [waiting])
    _quiesce(engine)


def test_overload_sheds_only_batch_until_exhausted(engine):
    """ISSUE 6 satellite: a mixed interactive+batch backlog over the
    global quota sheds ONLY batch (429 at the door + scheduler-side shed)
    until batch is exhausted; per-class shed counters pin attribution."""
    engine.max_queue = 3
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=48),
                              qos="interactive") for i in range(2)]
    engine.step()                           # fill both slots
    try:
        shed0 = {c: engine.metrics.snapshot().get("qos", {})
                 .get(c, {}).get("shed", 0) for c in QOS_CLASSES}
        queued_batch = [engine.submit([6] * 4,
                                      SamplingParams(max_new_tokens=2),
                                      qos="batch") for _ in range(2)]
        queued_int = engine.submit([7] * 4, SamplingParams(max_new_tokens=2),
                                   qos="interactive")
        # Queue is now full (3). A batch arrival is the lowest class
        # present → 429 at the door, with Retry-After and its class.
        with pytest.raises(EngineOverloaded) as exc:
            engine.submit([8] * 4, SamplingParams(max_new_tokens=2),
                          qos="batch")
        assert exc.value.qos == "batch"
        assert exc.value.retry_after > 0
        # Interactive arrivals over-admit while lower classes wait: the
        # scheduler sheds queued batch to restore the bound. Repeat until
        # batch is exhausted from the queue.
        over_int = [engine.submit([9] * 4, SamplingParams(max_new_tokens=2),
                                  qos="interactive") for _ in range(2)]
        engine._drain_waiting()
        engine._enforce_queue_bound()
        assert all(b.done.is_set() and b.finish_reason == "shed"
                   for b in queued_batch), "queued batch was not shed first"
        assert not queued_int.done.is_set(), "interactive was shed"
        assert not any(r.done.is_set() for r in over_int)
        shed = engine.metrics.snapshot()["qos"]
        assert shed["batch"]["shed"] - shed0["batch"] == 3   # 1x429 + 2 queue
        assert shed["interactive"]["shed"] - shed0["interactive"] == 0
        # Batch exhausted: now the lowest class present is interactive —
        # a further interactive arrival 429s rather than shedding peers.
        with pytest.raises(EngineOverloaded) as exc:
            engine.submit([9] * 4, SamplingParams(max_new_tokens=2),
                          qos="interactive")
        assert exc.value.qos == "interactive"
        _drain(engine, blockers + [queued_int] + over_int)
    finally:
        engine.max_queue = 0
    _quiesce(engine)


def test_per_class_admission_quota(engine):
    """A class's own max_queue 429s that class even when the shared queue
    has room — and leaves other classes unaffected."""
    engine.qos_policies = {"batch": QoSClassPolicy(max_queue=1)}
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=24),
                              qos="standard") for i in range(2)]
    engine.step()
    try:
        q = engine.submit([5] * 4, SamplingParams(max_new_tokens=2),
                          qos="batch")
        with pytest.raises(EngineOverloaded) as exc:
            engine.submit([6] * 4, SamplingParams(max_new_tokens=2),
                          qos="batch")
        assert exc.value.qos == "batch"
        ok = engine.submit([7] * 4, SamplingParams(max_new_tokens=2),
                           qos="standard")     # other classes unaffected
        _drain(engine, blockers + [q, ok])
        assert ok.finish_reason in ("stop", "length")
    finally:
        engine.qos_policies = {}
    _quiesce(engine)


def test_per_class_queue_delay_budget(engine):
    """A tight batch queue-delay budget sheds stale queued batch while a
    budget-less interactive entry survives the same wait."""
    engine.qos_policies = {
        "batch": QoSClassPolicy(queue_delay_budget=0.02)}
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=32),
                              qos="interactive") for i in range(2)]
    engine.step()
    try:
        b = engine.submit([5] * 4, SamplingParams(max_new_tokens=2),
                          qos="batch")
        i = engine.submit([6] * 4, SamplingParams(max_new_tokens=2),
                          qos="interactive")
        time.sleep(0.05)
        engine.step()
        assert b.done.is_set() and b.finish_reason == "shed"
        assert not (i.done.is_set() and i.finish_reason == "shed")
        _drain(engine, blockers + [i])
    finally:
        engine.qos_policies = {}
    _quiesce(engine)


def test_preemption_storm_quiescent(engine):
    """Repeated interactive bursts preempting batch (the chaos-adjacent
    storm): every request resolves, refcounts balance, zero page leaks."""
    batch = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=24),
                           qos="batch") for i in range(4)]
    engine.step()
    storm = []
    for wave in range(3):
        storm.extend(engine.submit([wave + 10] * 4,
                                   SamplingParams(max_new_tokens=3),
                                   qos="interactive") for _ in range(2))
        for _ in range(6):
            engine.step()
    _drain(engine, batch + storm)
    assert all(r.finish_reason in ("stop", "length") for r in batch + storm)
    assert engine.metrics.snapshot()["preemptions"] >= 1
    _quiesce(engine)


def test_qos_metrics_snapshot_and_histogram(engine):
    """Per-class snapshot carries completion counts and latency p95s; the
    per-class queue-delay histogram partitions the aggregate."""
    reqs = [engine.submit([c + 1] * 4, SamplingParams(max_new_tokens=2),
                          qos=cls)
            for c, cls in enumerate(("interactive", "batch"))]
    _drain(engine, reqs)
    snap = engine.metrics.snapshot()
    for cls in ("interactive", "batch"):
        assert snap["qos"][cls]["completed"] >= 1
        assert "ttft_p95_ms" in snap["qos"][cls]
    _, agg_counts, _, agg_n = engine.metrics.queue_delay_histogram()
    per_class_n = sum(
        engine.metrics.queue_delay_histogram(cls)[3]
        for cls in engine.metrics.qos_classes())
    assert per_class_n == agg_n
    assert agg_n == sum(agg_counts)
    _quiesce(engine)


# -- header propagation through the HTTP surface ------------------------------

@pytest.fixture(scope="module")
def served(cfg, params):
    from kubeflow_tpu.serve.server import ModelServer

    eng = LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=64, paged=True,
                     page_size=8, chunked_prefill_tokens=8,
                     decode_steps=4),
        params=params)
    srv = ModelServer("qos-svc", eng, port=0)
    srv.start()
    yield srv
    srv.stop()


def _post(url, body, headers=None):
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def test_qos_header_reaches_engine_metrics(served):
    status, _ = _post(served.url + "/v1/completions",
                      {"prompt": "hi", "max_tokens": 2},
                      headers={"X-Kftpu-Qos": "interactive"})
    assert status == 200
    status, _ = _post(served.url + "/v1/completions",
                      {"prompt": "hi", "max_tokens": 2, "qos": "batch"})
    assert status == 200
    snap = served.engine.metrics.snapshot()
    assert snap["qos"]["interactive"]["completed"] >= 1   # via header
    assert snap["qos"]["batch"]["completed"] >= 1         # via body field
    text = served.metrics_text()
    assert 'kftpu_serving_qos_requests_total{model="qos-svc",' \
           'qos="interactive"}' in text
    assert "kftpu_serving_qos_ttft_p95_ms" in text
    assert "kftpu_serving_ttft_p95_ms" in text
    assert "kftpu_serving_qos_queue_delay_seconds_bucket" in text


def test_unknown_qos_header_is_400(served):
    status, body = _post(served.url + "/v1/completions",
                         {"prompt": "hi", "max_tokens": 2},
                         headers={"X-Kftpu-Qos": "platinum"})
    assert status == 400
    assert "unknown QoS class" in body["error"]


def test_router_forwards_qos_header(served):
    from kubeflow_tpu.serve.router import Router

    router = Router(queue_timeout=5.0)
    router.set_backends({"latest": [served.url]})
    router.start()
    try:
        before = served.engine.metrics.snapshot().get("qos", {}) \
            .get("batch", {}).get("completed", 0)
        status, _ = _post(router.url + "/v1/completions",
                          {"prompt": "hi", "max_tokens": 2},
                          headers={"X-Kftpu-Qos": "batch"})
        assert status == 200
        after = served.engine.metrics.snapshot()["qos"]["batch"]["completed"]
        assert after == before + 1, "qos header lost at the router hop"
    finally:
        router.stop()
