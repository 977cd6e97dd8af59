"""Request-lifecycle hardening: deadlines & cancellation (slot + paged-KV
reaping), bounded admission with load shedding, queue-delay budget, and the
stop()/stopped_clean contract (ISSUE 2 tentpole + satellites).

The engine fixture is module-scoped and manually stepped: lifecycle knobs
(max_queue, queue_delay_budget) are plain attributes mutated per test, so
one compiled engine serves every scenario."""

import threading
import time

import pytest
import jax

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import (
    EngineOverloaded, LLMEngine, SamplingParams,
)


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    # Paged so every scenario also audits page-refcount balance; small
    # decode_steps so deadline reaping gets frequent scheduler control.
    return LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=64, paged=True,
                     page_size=8, chunked_prefill_tokens=8,
                     decode_steps=4),
        params=params)


def _drain(engine, reqs=(), max_steps=500):
    for _ in range(max_steps):
        worked = engine.step()
        if worked == 0 and all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("engine did not quiesce")


def test_cancel_frees_slot_and_pages_mid_flight(engine):
    req = engine.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=48))
    engine.step()                      # admit + a few decode steps
    assert not req.done.is_set()
    assert engine.kv_pages_in_use() > 0
    req.cancel()
    engine.step()                      # reaper runs first in step()
    assert req.done.is_set()
    assert req.finish_reason == "cancelled"
    assert engine.kv_pages_in_use() == 0, "cancel leaked KV pages"
    # The freed slot and pages serve the next request (acceptance: reuse).
    out = engine.generate([5, 6, 7], SamplingParams(max_new_tokens=4),
                          timeout=60)
    assert len(out) == 4
    assert engine.kv_pages_in_use() == 0
    assert engine.metrics.snapshot()["requests_cancelled"] >= 1


def test_deadline_reaps_live_slot_before_completion(engine):
    req = engine.submit([9, 8, 7], SamplingParams(max_new_tokens=48),
                        deadline=time.monotonic() + 0.05)
    engine.step()                      # admitted, decoding
    emitted_early = len(req.output_tokens)
    time.sleep(0.08)
    for _ in range(50):
        engine.step()
        if req.done.is_set():
            break
    assert req.finish_reason == "deadline"
    assert len(req.output_tokens) < 48, "deadline did not cut generation"
    assert emitted_early <= len(req.output_tokens)
    assert engine.kv_pages_in_use() == 0
    assert engine.metrics.snapshot()["requests_expired"] >= 1


def test_deadline_reaps_queued_request_without_decoding(engine):
    blockers = [engine.submit([i + 1] * 8, SamplingParams(max_new_tokens=24))
                for i in range(2)]     # occupy both slots
    engine.step()
    late = engine.submit([4, 4, 4], SamplingParams(max_new_tokens=4),
                         deadline=time.monotonic() + 0.02)
    time.sleep(0.05)
    engine.step()
    assert late.done.is_set()
    assert late.finish_reason == "deadline"
    assert late.output_tokens == []    # never touched the device
    _drain(engine, blockers)
    assert all(b.finish_reason in ("stop", "length") for b in blockers)
    assert engine.kv_pages_in_use() == 0


def test_bounded_admission_sheds_at_the_door(engine):
    engine.max_queue = 2
    try:
        # No stepping: everything parks in the admission queue.
        a = engine.submit([1, 2], SamplingParams(max_new_tokens=2))
        b = engine.submit([3, 4], SamplingParams(max_new_tokens=2))
        before = engine.metrics.snapshot()["requests_shed"]
        with pytest.raises(EngineOverloaded) as exc:
            engine.submit([5, 6], SamplingParams(max_new_tokens=2))
        assert exc.value.retry_after > 0
        assert engine.metrics.snapshot()["requests_shed"] == before + 1
    finally:
        engine.max_queue = 0
    _drain(engine, [a, b])
    assert engine.kv_pages_in_use() == 0


def test_queue_delay_budget_sheds_stale_requests(engine):
    engine.queue_delay_budget = 0.05
    try:
        blockers = [engine.submit([i + 1] * 8,
                                  SamplingParams(max_new_tokens=24))
                    for i in range(2)]
        engine.step()                  # both slots busy
        stale = engine.submit([7, 7], SamplingParams(max_new_tokens=2))
        time.sleep(0.08)
        engine.step()
        assert stale.done.is_set()
        assert stale.finish_reason == "shed"
        _drain(engine, blockers)
    finally:
        engine.queue_delay_budget = None
    assert engine.kv_pages_in_use() == 0


def test_overload_sheds_excess_but_keeps_capacity(engine):
    """Acceptance: offered load > capacity with a low bound -> excess shed
    with EngineOverloaded, admitted requests all complete (no collapse)."""
    engine.max_queue = 2
    admitted, shed = [], 0
    try:
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                engine.step()
                time.sleep(0.001)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        for i in range(16):
            try:
                admitted.append(engine.submit(
                    [i % 50 + 1] * 4, SamplingParams(max_new_tokens=12)))
            except EngineOverloaded:
                shed += 1
            time.sleep(0.002)
        deadline = time.monotonic() + 60
        while not all(r.done.is_set() for r in admitted):
            assert time.monotonic() < deadline, "admitted requests hung"
            time.sleep(0.01)
        stop.set()
        t.join(timeout=5.0)
    finally:
        engine.max_queue = 0
    assert shed > 0, "offered load never tripped the bound"
    assert all(r.finish_reason in ("stop", "length") for r in admitted)
    _drain(engine, admitted)
    assert engine.kv_pages_in_use() == 0


def test_queue_delay_histogram_populated(engine):
    # A request of its own: the suite's tests are dealt out over workers one
    # by one, so this may be the first test to meet its worker's engine.
    _drain(engine, [engine.submit([1, 2, 3],
                                  SamplingParams(max_new_tokens=2))])
    _, counts, _, n = engine.metrics.queue_delay_histogram()
    assert n > 0 and sum(counts) == n


def test_stop_clean_sets_flag(cfg, params):
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=1, max_seq_len=32,
                                      page_size=16,
                                      chunked_prefill_tokens=16),
                    params=params)
    assert eng.stopped_clean is None
    eng.start()
    assert eng.stop() is True
    assert eng.stopped_clean is True


def test_stop_surfaces_wedged_thread(cfg, params):
    """Satellite: a join timeout must not be silent success — the leaked
    thread still holds device buffers."""
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=1, max_seq_len=32,
                                      page_size=16,
                                      chunked_prefill_tokens=16),
                    params=params)
    release = threading.Event()
    eng._thread = threading.Thread(target=release.wait, daemon=True)
    eng._thread.start()
    assert eng.stop(timeout=0.1) is False
    assert eng.stopped_clean is False
    release.set()


def test_generate_timeout_cancels_orphan(engine):
    """Satellite: generate()'s TimeoutError must not orphan the request
    mid-engine — cancel() lets the scheduler free its slot and pages."""
    engine.start()
    try:
        # 2 ms: far below even a fully-warmed engine's 48-token run (the
        # pipelined hot loop finishes 48 tokens in ~17 ms on CPU — the old
        # 20 ms bound stopped timing out once decode stopped blocking on
        # per-round host fetches).
        with pytest.raises(TimeoutError):
            engine.generate([2] * 8, SamplingParams(max_new_tokens=48),
                            timeout=0.002)
        deadline = time.monotonic() + 10
        while engine.kv_pages_in_use() > 0:
            assert time.monotonic() < deadline, \
                "timed-out generate leaked its slot/pages"
            time.sleep(0.01)
    finally:
        assert engine.stop() is True
