"""Continuous-batching engine correctness: slot decode must reproduce the
full-forward greedy path exactly (the serving analog of sharded-vs-unsharded
numerics tests, SURVEY.md §4 rebuild translation (d))."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(cfg, params):
    return LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=4, max_seq_len=96,
                     page_size=16, chunked_prefill_tokens=64),
        params=params)


def reference_greedy(params, cfg, prompt, n_new):
    """Argmax continuation by full re-forward each step (no cache)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _, _ = decoder_forward(
            params, jnp.asarray([toks], jnp.int32), cfg)
        toks.append(int(jax.device_get(jnp.argmax(logits[0, -1]))))
    return toks[len(prompt):]


@pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
def test_single_request_matches_full_forward(engine, params, cfg):
    prompt = [5, 17, 3, 99, 42]
    got = engine.generate(prompt, SamplingParams(max_new_tokens=12))
    want = reference_greedy(params, cfg, prompt, 12)
    assert got == want


@pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
def test_interleaved_requests_match_solo(engine, params, cfg):
    """Requests admitted mid-decode of others must not perturb each other."""
    prompts = [[1, 2, 3], [7] * 20, [9, 8, 7, 6, 5, 4], [30, 31]]
    want = [reference_greedy(params, cfg, p, 8) for p in prompts]

    # Stagger: submit 0 and 1, decode a bit, then 2 and 3 join.
    reqs = [engine.submit(prompts[0], SamplingParams(max_new_tokens=8)),
            engine.submit(prompts[1], SamplingParams(max_new_tokens=8))]
    for _ in range(3):
        engine.step()
    reqs += [engine.submit(prompts[2], SamplingParams(max_new_tokens=8)),
             engine.submit(prompts[3], SamplingParams(max_new_tokens=8))]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    for r, w in zip(reqs, want):
        assert r.output_tokens == w


@pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
def test_slot_reuse_is_clean(engine, params, cfg):
    """A slot freed by a long request must serve a short one untainted."""
    long = engine.generate([2] * 40, SamplingParams(max_new_tokens=10))
    short = engine.generate([11, 12], SamplingParams(max_new_tokens=6))
    assert short == reference_greedy(params, cfg, [11, 12], 6)
    assert long == reference_greedy(params, cfg, [2] * 40, 10)


def test_stop_token_and_metrics(engine):
    req = engine.submit([3, 1, 4], SamplingParams(max_new_tokens=50))
    while not req.done.is_set():
        engine.step()
    # force a stop-token run: use the first emitted token as the stop token
    stop = req.output_tokens[0]
    req2 = engine.submit([3, 1, 4], SamplingParams(max_new_tokens=50,
                                                   stop_token=stop))
    while not req2.done.is_set():
        engine.step()
    assert req2.finish_reason == "stop"
    assert req2.output_tokens[-1] == stop
    snap = engine.metrics.snapshot()
    assert snap["requests_completed"] >= 2
    assert snap["ttft_p50_ms"] > 0
    assert req.ttft is not None and req.ttft > 0


def test_background_loop_and_streaming(cfg, params):
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                                      page_size=16,
                                      chunked_prefill_tokens=16),
                    params=params)
    eng.start()
    try:
        req = eng.submit([8, 6, 4], SamplingParams(max_new_tokens=5))
        streamed = []
        while True:
            tok = req.stream.get(timeout=30)
            if tok is None:
                break
            streamed.append(tok)
        assert streamed == req.output_tokens
        assert len(streamed) == 5
    finally:
        eng.stop()


def test_sampling_respects_temperature(engine):
    """temperature>0 with a fixed engine rng still yields valid tokens and
    differs across draws (smoke, not a statistical test)."""
    outs = {tuple(engine.generate([1, 2, 3, 4],
                                  SamplingParams(max_new_tokens=6,
                                                 temperature=1.5, top_k=50)))
            for _ in range(4)}
    assert len(outs) > 1
    assert all(0 <= t < engine.cfg.vocab_size for o in outs for t in o)


class TestMultiStepDecode:
    """K decode steps per dispatch must be invisible to outputs: greedy
    streams match the single-step engine exactly, stop/budget rules fire
    mid-dispatch, and per-slot sampling params are honored."""

    def make_engine(self, cfg, params, decode_steps):
        return LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=96, page_size=16,
            chunked_prefill_tokens=64,
            decode_steps=decode_steps), params=params)

    def test_matches_single_step_greedy(self, cfg, params):
        prompts = [[5, 17, 3], [7] * 12, [1, 2]]
        outs = []
        for k in (1, 4):
            eng = self.make_engine(cfg, params, k)
            reqs = [eng.submit(p, SamplingParams(max_new_tokens=n))
                    for p, n in zip(prompts, (11, 6, 3))]
            while not all(r.done.is_set() for r in reqs):
                eng.step()
            outs.append([list(r.output_tokens) for r in reqs])
        assert outs[0] == outs[1]

    def test_stop_token_mid_dispatch(self, cfg, params):
        eng = self.make_engine(cfg, params, 8)
        probe = eng.generate([3, 1, 4], SamplingParams(max_new_tokens=8))
        stop = probe[3]                    # fires mid-way through a dispatch
        req = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=50,
                                                   stop_token=stop))
        while not req.done.is_set():
            eng.step()
        assert req.finish_reason == "stop"
        assert req.output_tokens == probe[:4]

    def test_budget_honored_mid_dispatch(self, cfg, params):
        eng = self.make_engine(cfg, params, 8)
        req = eng.submit([9, 9, 2], SamplingParams(max_new_tokens=5))
        while not req.done.is_set():
            eng.step()
        assert len(req.output_tokens) == 5
        assert req.finish_reason == "length"


class TestPerSlotSampling:
    """Each slot's temperature/top_k/top_p apply to that slot alone."""

    def test_top_k_not_shared_across_slots(self, cfg, params):
        """A top_k=1 slot decoding next to a top_k=0 (full categorical) slot
        must still sample greedily — round-1 took max(top_k) over the batch,
        silently truncating every slot alike."""
        from kubeflow_tpu.serve.engine import _sample_batch

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(2, 64)) * 3, jnp.float32)
        argmaxes = np.asarray(jax.device_get(jnp.argmax(logits, axis=-1)))
        temps = jnp.asarray([1.0, 1.0], jnp.float32)
        top_k = jnp.asarray([1, 0], jnp.int32)
        top_p = jnp.asarray([1.0, 1.0], jnp.float32)
        row0, row1 = set(), set()
        for i in range(64):
            got = np.asarray(jax.device_get(_sample_batch(
                logits, jax.random.PRNGKey(i), temps, top_k, top_p)))
            row0.add(int(got[0]))
            row1.add(int(got[1]))
        assert row0 == {int(argmaxes[0])}   # top_k=1 == greedy, every draw
        assert len(row1) > 4                # full categorical explores

    def test_top_p_nucleus(self):
        from kubeflow_tpu.serve.engine import _sample_batch

        # Probabilities ~ [0.5, 0.3, 0.2]: top_p=0.6 keeps {0, 1} only
        # (exclusive cumsum: 0.0, 0.5 < 0.6, 0.8 ≥ 0.6).
        logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.2]], jnp.float32))
        seen = set()
        for i in range(100):
            got = _sample_batch(logits, jax.random.PRNGKey(i),
                                jnp.asarray([1.0]), jnp.asarray([0]),
                                jnp.asarray([0.6]))
            seen.add(int(jax.device_get(got)[0]))
        assert seen == {0, 1}

    def test_temperature_zero_is_greedy_per_slot(self):
        from kubeflow_tpu.serve.engine import _sample_batch

        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
        got = _sample_batch(logits, jax.random.PRNGKey(0),
                            jnp.asarray([0.0, 0.0]), jnp.asarray([0, 0]),
                            jnp.asarray([1.0, 1.0]))
        assert np.array_equal(np.asarray(jax.device_get(got)),
                              np.asarray(jax.device_get(
                                  jnp.argmax(logits, axis=-1))))


class TestChunkedPrefill:
    """Chunked prefill: long prompts stream through fixed chunks with decode
    interleaving, producing the same output as one-shot prefill."""

    def make_engine(self, chunk):
        cfg = preset("tiny", vocab_size=512)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        return LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=128,
            page_size=16, chunked_prefill_tokens=chunk),
            params=params)

    def test_matches_one_shot(self):
        prompt = list(range(1, 50))          # 49 tokens
        params = SamplingParams(max_new_tokens=6, temperature=0.0)
        outs = []
        for chunk in (0, 16):                # 0 = disabled (one-shot)
            eng = self.make_engine(chunk)
            req = eng.submit(prompt, params)
            for _ in range(200):
                eng.step()
                if req.done.is_set():
                    break
            assert req.done.is_set()
            outs.append(list(req.output_tokens))
        assert outs[0] == outs[1], outs      # greedy: must match exactly

    def test_decode_interleaves_during_long_prefill(self):
        eng = self.make_engine(16)
        short = eng.submit(list(range(1, 9)),
                           SamplingParams(max_new_tokens=40, temperature=0.0))
        eng.step()                           # short admitted + first decode
        produced_before = len(short.output_tokens)
        long_req = eng.submit(list(range(1, 60)),
                              SamplingParams(max_new_tokens=4,
                                             temperature=0.0))
        # While the long prompt chunks through, the short stream keeps
        # producing tokens every step.
        for _ in range(3):
            eng.step()
        assert len(short.output_tokens) >= produced_before + 3
        for _ in range(200):
            eng.step()
            if long_req.done.is_set() and short.done.is_set():
                break
        assert long_req.done.is_set() and short.done.is_set()
        assert len(long_req.output_tokens) == 4

    def test_interleaved_decode_does_not_corrupt_chunked_kv(self):
        """Decode dispatches running while a chunked prefill holds its slot
        must leave that slot's already-written KV untouched: the chunked
        request's greedy output must equal the solo one-shot output.
        (Regression: placeholder rows once wrote KV at position 0.)"""
        long_prompt = list(range(7, 56))     # prompt[0] != 0 matters here
        want = None
        eng = self.make_engine(0)            # one-shot oracle, no traffic
        solo = eng.submit(long_prompt,
                          SamplingParams(max_new_tokens=6, temperature=0.0))
        for _ in range(200):
            eng.step()
            if solo.done.is_set():
                break
        want = list(solo.output_tokens)

        eng = self.make_engine(16)
        short = eng.submit([9, 8, 7],
                           SamplingParams(max_new_tokens=60, temperature=0.0))
        eng.step()                           # short admitted and decoding
        long_req = eng.submit(long_prompt,
                              SamplingParams(max_new_tokens=6,
                                             temperature=0.0))
        for _ in range(300):
            eng.step()                       # decode interleaves every chunk
            if long_req.done.is_set():
                break
        assert long_req.done.is_set()
        assert list(long_req.output_tokens) == want

    def test_slot_reserved_during_chunking(self):
        eng = self.make_engine(16)           # 2 slots
        long_req = eng.submit(list(range(1, 60)),
                              SamplingParams(max_new_tokens=2))
        eng.step()                           # chunk 1 of the long prompt
        s1 = eng.submit(list(range(1, 5)), SamplingParams(max_new_tokens=2))
        s2 = eng.submit(list(range(1, 5)), SamplingParams(max_new_tokens=2))
        for _ in range(200):
            eng.step()
            if long_req.done.is_set() and s1.done.is_set() and s2.done.is_set():
                break
        assert long_req.done.is_set() and s1.done.is_set() and s2.done.is_set()

