"""Speculative decoding correctness: greedy spec output must be
TOKEN-IDENTICAL to the non-speculative engine on BOTH draft sources (acceptance rate only moves throughput, never tokens), KV
rollback after rejections must leave page refcounts balanced, and the
engine must fall back to plain decode whenever greedy verification would
not be exact (sampling traffic)."""

import jax
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.spec_decode import ngram_propose


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


PROMPTS = [[5, 17, 3, 99, 42], list(range(1, 50)), [7] * 20,
           [9, 8, 7, 6, 5, 4]]
# A repetitive prompt: the n-gram drafter finds matches immediately, so
# acceptance (and rejection, when the model diverges from the template)
# both exercise for real.
TEMPLATED = [[4, 8, 15, 16, 23, 42] * 6 + [4, 8, 15],
             list(range(10, 26)) * 3 + [10, 11]]


def make_engine(cfg, params, *, spec=None, paged=True, slots=4,
                draft_params=None, decode_steps=4):
    return LLMEngine(cfg, BatchingSpec(
        max_batch_size=slots, max_seq_len=128, chunked_prefill_tokens=32,
        paged=paged, page_size=16,
        decode_steps=decode_steps,
        speculative=spec or SpeculativeSpec()), params=params,
        draft_params=draft_params)


def run_all(eng, reqs, max_steps=800):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def gen_all(eng, prompts, max_new=12):
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    reqs = [eng.submit(list(p), sp) for p in prompts]
    run_all(eng, reqs)
    return [list(r.output_tokens) for r in reqs]


DRAFT = SpeculativeSpec(mode="draft_model", k=4,
                        draft={"preset": "tiny",
                               "overrides": {"vocab_size": 512,
                                             "n_layers": 1}})


class TestNgramPropose:
    def test_matches_most_recent_occurrence(self):
        ctx = [1, 2, 3, 9, 9, 1, 2, 3, 7, 7, 1, 2, 3]
        # suffix [1,2,3] last occurred at index 5 -> propose [7, 7, 1, 2]
        assert ngram_propose(ctx, 4, 3, 1) == [7, 7, 1, 2]

    def test_prefers_longer_ngrams(self):
        ctx = [5, 1, 2, 8, 0, 1, 2, 8]       # 3-gram [1,2,8] -> [0, 1, 2, 8]
        assert ngram_propose(ctx, 4, 3, 1) == [0, 1, 2, 8]

    def test_no_match_returns_empty(self):
        assert ngram_propose([1, 2, 3, 4, 5], 4, 3, 1) == []

    def test_truncates_to_k(self):
        ctx = [1, 2, 3, 4, 5, 6, 1, 2]
        assert ngram_propose(ctx, 2, 2, 1) == [3, 4]


class TestSpecExactMatch:
    """The acceptance-criteria core: every draft source reproduces the
    plain greedy engine token-for-token."""

    @pytest.fixture(scope="class")
    def want(self, cfg, params):
        return gen_all(make_engine(cfg, params), PROMPTS)

    @pytest.fixture(scope="class")
    def want_templated(self, cfg, params):
        return gen_all(make_engine(cfg, params), TEMPLATED, max_new=20)

    def test_ngram_paged(self, cfg, params, want):
        # One decode step a dispatch, so the drafter is consulted at every
        # token: under these random weights a generation repeats a token
        # now and then, and a drafter consulted once in four tokens (the
        # plain fallback's dispatch) never stands on one.
        eng = make_engine(cfg, params, paged=True, decode_steps=1,
                          spec=SpeculativeSpec(mode="ngram", k=4))
        assert gen_all(eng, PROMPTS) == want
        snap = eng.metrics.snapshot()
        assert snap["spec_rounds"] > 0
        assert "spec_acceptance_rate" in snap
        assert snap["spec_tokens_per_step"] >= 1.0

    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~8s; draft-model exact
    # match stays fast via test_self_draft_accepts_almost_everything and
    # test_serve_one_backend.py
    def test_draft_model_paged(self, cfg, params, want):
        eng = make_engine(cfg, params, paged=True, spec=DRAFT)
        assert gen_all(eng, PROMPTS) == want
        assert eng.metrics.snapshot()["spec_rounds"] > 0

    def test_ngram_templated_prompts_accept_and_match(self, cfg, params,
                                                      want_templated):
        """The drafter proposes where the generation repeats itself (it is
        consulted at every token: one decode step a dispatch); outputs still
        match exactly, and some drafts are accepted."""
        eng = make_engine(cfg, params, decode_steps=1,
                          spec=SpeculativeSpec(mode="ngram", k=4))
        assert gen_all(eng, TEMPLATED, max_new=20) == want_templated
        assert eng.metrics.spec_drafted > 0
        assert eng.metrics.spec_accepted > 0

    def test_self_draft_accepts_almost_everything(self, cfg, params, want):
        """Draft == target: the argmax chains coincide, so acceptance is
        near-total and rounds emit multiple tokens."""
        spec = SpeculativeSpec(mode="draft_model", k=4,
                               draft={"preset": "tiny",
                                      "overrides": {"vocab_size": 512}})
        eng = make_engine(cfg, params, spec=spec, draft_params=params)
        assert gen_all(eng, PROMPTS) == want
        snap = eng.metrics.snapshot()
        assert snap["spec_acceptance_rate"] > 0.5
        assert snap["spec_tokens_per_step"] > 1.5

    def test_longer_k_still_exact(self, cfg, params, want):
        eng = make_engine(cfg, params, paged=True,
                          spec=SpeculativeSpec(mode="ngram", k=8))
        assert gen_all(eng, PROMPTS) == want

    @pytest.mark.slow  # tier-1 budget: three engines for one stop probe, ~9s
    def test_stop_token_inside_accepted_run(self, cfg, params):
        """A stop token appearing mid-round (inside the accepted prefix or
        as the bonus token) must truncate the emission exactly where the
        plain engine stops."""
        plain = make_engine(cfg, params)
        probe = gen_all(plain, [PROMPTS[0]], max_new=12)[0]
        stop = probe[5]
        sp = SamplingParams(max_new_tokens=50, stop_token=stop)
        weng = make_engine(cfg, params)
        want_req = weng.submit(list(PROMPTS[0]), sp)
        run_all(weng, [want_req])
        eng = make_engine(cfg, params, spec=SpeculativeSpec(mode="ngram", k=4))
        req = eng.submit(list(PROMPTS[0]), sp)
        run_all(eng, [req])
        assert req.output_tokens == want_req.output_tokens
        assert req.finish_reason == want_req.finish_reason

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_budget_exact_mid_round(self, cfg, params):
        """max_new_tokens falling inside a round's emission truncates it
        exactly (never over-generates)."""
        for n in (1, 3, 7):
            eng = make_engine(cfg, params, paged=True,
                              spec=SpeculativeSpec(mode="ngram", k=4))
            out = gen_all(eng, [TEMPLATED[0]], max_new=n)
            assert len(out[0]) == n

    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~10s; the fallback
    # branch itself is cheap — the cost is the sampled decode
    def test_sampling_traffic_falls_back_to_plain(self, cfg, params):
        eng = make_engine(cfg, params, spec=SpeculativeSpec(mode="ngram", k=4))
        sp = SamplingParams(max_new_tokens=6, temperature=1.2, top_k=20)
        req = eng.submit(list(PROMPTS[0]), sp)
        run_all(eng, [req])
        assert len(req.output_tokens) == 6
        assert "spec_rounds" not in eng.metrics.snapshot()


class TestPagedRollback:
    """Rejection rollback: the page table truncates to the accepted length
    and the pool's refcount accounting balances — no leak, no double free."""

    def _assert_balanced(self, eng):
        alloc = eng._allocator
        held = sum(len(p) for p in eng._slot_pages)
        # After all requests finish, no slot holds pages and every ref is 0
        # (prefix-cached pages linger at ref 0 in the reclaimable map).
        if all(s is None for s in eng.slots):
            assert held == 0
            assert alloc.in_use() == 0
            assert int(alloc._ref.sum()) == 0
            assert alloc.available() == alloc.num_pages

    @pytest.mark.slow   # ~7s: refcount balance also pinned by the
    # sanitizer + chaos suites
    def test_rejection_heavy_refcounts_balance(self, cfg, params):
        """A deliberately-bad draft model rejects nearly every round —
        maximal rollback traffic — and the pool must come back whole."""
        want = gen_all(make_engine(cfg, params), PROMPTS)
        eng = make_engine(cfg, params, paged=True, spec=DRAFT)
        assert gen_all(eng, PROMPTS) == want
        self._assert_balanced(eng)

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_rollback_truncates_table(self, cfg, params):
        """Mid-flight: after any spec round, a slot's page list covers
        exactly ceil(length/page) pages — rejected-tail pages were freed."""
        eng = make_engine(cfg, params, paged=True,
                          spec=SpeculativeSpec(mode="ngram", k=8))
        sp = SamplingParams(max_new_tokens=40, temperature=0.0)
        req = eng.submit(list(TEMPLATED[0]), sp)
        checked = 0
        for _ in range(400):
            eng.step()
            for i, s in enumerate(eng.slots):
                if s is None:
                    continue
                have = len(eng._slot_pages[i])
                need = -(-s.length // eng.page_size)
                assert need <= have <= need + 2, (have, need)
                checked += 1
            if req.done.is_set():
                break
        assert req.done.is_set() and checked > 0
        self._assert_balanced(eng)

    @pytest.mark.slow  # tier-1 budget: 48-token double prefill, ~14s
    def test_prefix_cache_pages_survive_rollback(self, cfg, params):
        """Rollback never frees registered prompt pages out from under the
        prefix cache: a second identical prompt still hits."""
        eng = make_engine(cfg, params, paged=True,
                          spec=SpeculativeSpec(mode="ngram", k=4))
        sp = SamplingParams(max_new_tokens=10, temperature=0.0)
        prompt = list(range(1, 49))
        r1 = eng.submit(prompt, sp)
        run_all(eng, [r1])
        r2 = eng.submit(prompt, sp)
        run_all(eng, [r2])
        assert eng._allocator.stats["prefix_hits"] >= 1
        assert list(r1.output_tokens) == list(r2.output_tokens)
        self._assert_balanced(eng)

    @pytest.mark.parametrize("spec", [
        SpeculativeSpec(mode="ngram", k=4), DRAFT], ids=["ngram", "draft"])
    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_pool_pressure_with_spec_still_exact(self, cfg, params, spec):
        """A pool too small for all slots: recompute preemption + spec
        coexist (including the draft-cache reset on re-admission) and
        outputs stay exact."""
        sp = SamplingParams(max_new_tokens=24, temperature=0.0)
        prompts = [list(range(1, 30)), list(range(2, 60)),
                   list(range(3, 40))]
        want_eng = make_engine(cfg, params)
        wreqs = [want_eng.submit(list(p), sp) for p in prompts]
        run_all(want_eng, wreqs)
        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
            max_pages=8, enable_prefix_caching=False,
            chunked_prefill_tokens=16,
            speculative=spec), params=params)
        reqs = [eng.submit(list(p), sp) for p in prompts]
        run_all(eng, reqs, max_steps=2000)
        assert [list(r.output_tokens) for r in reqs] == \
            [list(r.output_tokens) for r in wreqs]
        self._assert_balanced(eng)


class TestDraftModelConfig:
    def test_vocab_mismatch_rejected(self, cfg, params):
        with pytest.raises(ValueError, match="vocab"):
            make_engine(cfg, params, spec=SpeculativeSpec(
                mode="draft_model", k=4,
                draft={"preset": "tiny"}))     # vocab 256 != 512

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="speculative mode"):
            SpeculativeSpec(mode="medusa")

    def test_spec_roundtrips_through_batching_config(self, cfg, params):
        """The ISVC controller ships BatchingSpec.model_dump() to replicas;
        the nested speculative spec must survive the round trip."""
        b = BatchingSpec(max_batch_size=2, max_seq_len=64,
                         page_size=16, chunked_prefill_tokens=16,
                         speculative=SpeculativeSpec(mode="ngram", k=6))
        again = BatchingSpec(**b.model_dump())
        assert again.speculative.mode == "ngram"
        assert again.speculative.k == 6
        eng = LLMEngine(cfg, again, params=params)
        assert eng.spec_mode == "ngram" and eng.spec_k == 6
