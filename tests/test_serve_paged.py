"""Paged KV cache correctness: the engine must reproduce the greedy
outputs of a plain full-recompute ``decoder_forward`` loop exactly (a
reference that shares no cache code with it), decouple HBM from
slots × max_seq_len, reuse shared-prefix pages, chunk several long prompts
concurrently, and survive pool pressure via recompute preemption — the vLLM
feature set ((U) kserve huggingfaceserver vLLM backend, SURVEY.md §2.3#27),
exact-match tested like every other serving path."""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import PageAllocator, PagePoolExhausted


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def make_paged(cfg, params, *, max_pages=None, page=16, chunk=32, slots=4,
               prefix=True, prefills=2, prefix_index="radix"):
    return LLMEngine(cfg, BatchingSpec(
        max_batch_size=slots, max_seq_len=128, paged=True, page_size=page,
        max_pages=max_pages, enable_prefix_caching=prefix,
        prefix_index=prefix_index,
        chunked_prefill_tokens=chunk, max_concurrent_prefills=prefills),
        params=params)


def reference_greedy(cfg, params, prompts, max_new):
    """Greedy continuations from a full recompute of the whole sequence a
    token: no cache, no pages, no engine. One program: the sequence is
    padded to 128 and position n-1 read (causal attention never looks at
    the padding behind it)."""
    fwd = jax.jit(lambda t: decoder_forward(params, t, cfg)[0][0])
    outs = []
    for p in prompts:
        seq = list(p)
        for _ in range(max_new):
            toks = jnp.zeros((1, 128), jnp.int32).at[0, :len(seq)].set(
                jnp.asarray(seq, jnp.int32))
            seq.append(int(jnp.argmax(fwd(toks)[len(seq) - 1])))
        outs.append(seq[len(p):])
    return outs


def run_all(eng, reqs, max_steps=500):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


class TestPagedAllocator:
    def test_alloc_free_refcount(self):
        a = PageAllocator(4, 8)
        p = a.alloc(3)
        assert len(set(p)) == 3 and a.available() == 1
        a.incref([p[0]])
        a.free(p)
        assert a.available() == 3            # p[0] still referenced
        a.free([p[0]])
        assert a.available() == 4

    def test_exhaustion_raises(self):
        a = PageAllocator(2, 8)
        a.alloc(2)
        with pytest.raises(PagePoolExhausted):
            a.alloc(1)

    def test_prefix_match_and_eviction(self):
        a = PageAllocator(4, 4)
        toks = list(range(1, 13))            # 3 full pages
        pages = a.alloc(3)
        a.register_prefix(toks, pages)
        a.free(pages)                        # ref 0 -> cached, reclaimable
        hit = a.match_prefix(toks + [99])
        assert hit == pages                  # full-page prefix reused
        a.free(hit)
        # Allocating everything evicts the cached pages LRU.
        a.alloc(4)
        assert a.match_prefix(toks + [99]) == []
        assert a.stats["evictions"] >= 1

    def test_match_capped_before_last_token(self):
        """A fully-cached prompt must still leave >=1 token to prefill (the
        first sampled token needs real logits)."""
        a = PageAllocator(4, 4)
        toks = list(range(8))                # exactly 2 pages
        pages = a.alloc(2)
        a.register_prefix(toks, pages)
        hit = a.match_prefix(toks)           # same 8-token prompt
        assert len(hit) <= 1                 # (8-1)//4 = 1 page max

    def test_match_cap_edges(self):
        """The one-token-short cap, walked across the page boundary —
        the contract the radix index must preserve (its cap is the same
        ``len(tokens) - 1``): a page-multiple prompt reuses all but the
        last page; one extra token unlocks it."""
        a = PageAllocator(8, 4)
        toks = list(range(1, 13))            # 3 full pages
        pages = a.alloc(3)
        a.register_prefix(toks, pages)
        a.free(pages)
        assert len(a.match_prefix(toks)) == 2          # (12-1)//4
        for h in (a.match_prefix(toks + [99]),):       # 13 tokens
            assert len(h) == 3
            a.free(h)
        assert len(a.match_prefix(toks[:5])) == 1      # (5-1)//4
        assert len(a.match_prefix(toks[:4])) == 0      # (4-1)//4 = 0

    def test_match_partial_chain_break(self):
        """A chain whose middle page was evicted must stop at the break
        (never skip-match disjoint pages)."""
        a = PageAllocator(4, 4)
        toks = list(range(1, 13))
        pages = a.alloc(3)
        a.register_prefix(toks, pages)
        a.free(pages)
        # Evict the middle page's content by dropping its hash entry
        # the way LRU eviction does.
        key = a._key_of.pop(pages[1])
        a._by_key.pop(key)
        hit = a.match_prefix(toks + [99])
        assert hit == [pages[0]]
        a.free(hit)


class TestPagedExactMatch:
    def test_matches_contiguous_greedy(self, cfg, params):
        """The engine's greedy tokens (chunked prefill into pages, paged
        multi-step decode) against the full-recompute reference."""
        prompts = [[5, 17, 3, 99, 42], list(range(1, 50)), [7] * 20,
                   [9, 8, 7, 6, 5, 4]]
        sp = SamplingParams(max_new_tokens=10, temperature=0.0)
        eng = make_paged(cfg, params)
        reqs = [eng.submit(p, sp) for p in prompts]
        run_all(eng, reqs)
        got = [list(r.output_tokens) for r in reqs]
        assert got == reference_greedy(cfg, params, prompts, 10)

    @pytest.mark.slow   # ~7s: capacity/slot decoupling; pool accounting
    # stays fast-covered by the allocator units + TestPagedExactMatch
    def test_hbm_decoupled_from_slots(self, cfg, params):
        """A pool far below slots × max_len still serves mixed traffic: the
        whole point of paging on v5e."""
        # 4 slots x 128 = 512 positions contiguous; pool = 12 pages x 16
        # = 192 positions.
        eng = make_paged(cfg, params, max_pages=12, page=16)
        assert eng.cache["k"].shape[1] == 12
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        reqs = [eng.submit(p, sp) for p in
                ([1, 2, 3], list(range(1, 40)), [4] * 10, [9, 9])]
        run_all(eng, reqs)
        assert [list(r.output_tokens) for r in reqs] == reference_greedy(
            cfg, params,
            ([1, 2, 3], list(range(1, 40)), [4] * 10, [9, 9]), 8)

    def test_sampled_modes_run(self, cfg, params):
        eng = make_paged(cfg, params)
        reqs = [eng.submit([1, 2, 3, 4],
                           SamplingParams(max_new_tokens=5, temperature=0.8,
                                          top_k=7)),
                eng.submit([5, 6], SamplingParams(max_new_tokens=5))]
        run_all(eng, reqs)
        assert all(len(r.output_tokens) == 5 for r in reqs)


class TestPrefixCaching:
    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~10s;
    # test_identical_prompt_twice_exact keeps prefix reuse fast-covered
    def test_shared_prefix_reuses_pages(self, cfg, params):
        system = list(range(40, 90))         # 50-token shared "system prompt"
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        eng = make_paged(cfg, params, page=16, chunk=32)
        r1 = eng.submit(system + [1, 2, 3], sp)
        run_all(eng, [r1])
        stats0 = dict(eng._allocator.stats)
        r2 = eng.submit(system + [7, 8, 9], sp)
        run_all(eng, [r2])
        assert eng._allocator.stats["prefix_hits"] == stats0["prefix_hits"] + 1
        # And the reuse must not perturb outputs: compare vs cold engines.
        cold = make_paged(cfg, params, prefix=False)
        c1 = cold.submit(system + [1, 2, 3], sp)
        c2 = cold.submit(system + [7, 8, 9], sp)
        run_all(cold, [c1, c2])
        assert list(r1.output_tokens) == list(c1.output_tokens)
        assert list(r2.output_tokens) == list(c2.output_tokens)

    def test_identical_prompt_twice_exact(self, cfg, params):
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        prompt = list(range(1, 49))          # 48 tokens = 3 full pages
        eng = make_paged(cfg, params, page=16, chunk=16)
        r1 = eng.submit(prompt, sp)
        run_all(eng, [r1])
        r2 = eng.submit(prompt, sp)
        run_all(eng, [r2])
        assert list(r1.output_tokens) == list(r2.output_tokens)
        assert eng._allocator.stats["prefix_hits"] >= 1


class TestConcurrentChunkedPrefills:
    @pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
    def test_two_long_prompts_chunk_concurrently(self, cfg, params):
        """Two long prompts admitted together must BOTH be mid-chunking at
        once (no head-of-line blocking) and finish with exact outputs."""
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        long_a = list(range(1, 100))
        long_b = list(range(3, 90))
        eng = make_paged(cfg, params, chunk=32, prefills=2)
        ra, rb = eng.submit(long_a, sp), eng.submit(long_b, sp)
        eng._admit()
        assert len(eng._chunkings) == 2      # both in flight
        run_all(eng, [ra, rb])
        solo = make_paged(cfg, params, chunk=32, prefills=1)
        sa, sb = solo.submit(long_a, sp), solo.submit(long_b, sp)
        run_all(solo, [sa, sb])
        assert list(ra.output_tokens) == list(sa.output_tokens)
        assert list(rb.output_tokens) == list(sb.output_tokens)


class TestPreemption:
    @pytest.mark.slow   # ~7s: preempt/resume also chaos-covered
    def test_pool_pressure_preempts_and_resumes(self, cfg, params):
        """A pool too small for all slots forces recompute preemption; every
        request still finishes with the exact greedy output."""
        sp = SamplingParams(max_new_tokens=24, temperature=0.0)
        prompts = [list(range(1, 30)), list(range(2, 60)),
                   list(range(3, 40))]
        # 8 pages x 16 = 128 positions: one max-len sequence fits, three
        # growing sequences cannot — someone must be preempted.
        eng = make_paged(cfg, params, max_pages=8, page=16, chunk=16,
                         prefix=False)
        reqs = [eng.submit(p, sp) for p in prompts]
        run_all(eng, reqs, max_steps=2000)
        assert [list(r.output_tokens) for r in reqs] == \
            reference_greedy(cfg, params, prompts, 24)


class TestReviewRegressions:
    @pytest.mark.slow  # tier-1 budget: long-prompt chunked prefill, ~9s
    def test_chunk_window_crossing_max_len_via_prefix_hit(self, cfg, params):
        """Prefix hits start tail chunks at page — not chunk — alignment, so
        the final chunk's C-wide window can cross max_seq_len; the padded
        cache row must keep the output exact (regression: the window used to
        clamp and overwrite earlier KV)."""
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        shared = list(range(1, 51))          # 50 tokens -> 3 full 16-pages
        long_tail = shared[:48] + list(range(60, 97))   # 85 tokens total
        eng = make_paged(cfg, params, page=16, chunk=32)
        warm = eng.submit(shared, sp)
        run_all(eng, [warm])
        r = eng.submit(long_tail, sp)        # hits 3 pages -> pos starts 48
        run_all(eng, [r])
        assert eng._allocator.stats["prefix_hits"] >= 1
        cold = make_paged(cfg, params, page=16, chunk=32, prefix=False)
        c = cold.submit(long_tail, sp)
        run_all(cold, [c])
        assert list(r.output_tokens) == list(c.output_tokens)

    def test_paged_with_chunking_disabled_falls_back_to_page_chunks(
            self, cfg, params):
        """chunked_prefill_tokens=0 must not hang the engine (regression: zero-token chunks looped
        forever)."""
        eng = make_paged(cfg, params, chunk=0)
        assert eng.chunk_size == eng.page_size
        r = eng.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4,
                                                       temperature=0.0))
        run_all(eng, [r])
        assert len(r.output_tokens) == 4

    @pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
    def test_concurrent_prefills_starved_pool_does_not_deadlock(
            self, cfg, params):
        """Two long prompts whose combined prefills exceed the pool: the
        starved chunking must abort/requeue (its pages are invisible to
        decode preemption), not deadlock (regression)."""
        sp = SamplingParams(max_new_tokens=6, temperature=0.0)
        a, b = list(range(1, 81)), list(range(2, 82))
        # 8 pages x 16 = 128 = max_len: one sequence fits; two 5-page
        # prompts cannot prefill together.
        eng = make_paged(cfg, params, max_pages=8, page=16, chunk=16,
                         prefix=False, prefills=2)
        ra, rb = eng.submit(a, sp), eng.submit(b, sp)
        run_all(eng, [ra, rb], max_steps=2000)
        solo = make_paged(cfg, params, chunk=16, prefix=False, prefills=1)
        sa, sb = solo.submit(a, sp), solo.submit(b, sp)
        run_all(solo, [sa, sb])
        assert list(ra.output_tokens) == list(sa.output_tokens)
        assert list(rb.output_tokens) == list(sb.output_tokens)


class TestFlatIndexPreserved:
    """The legacy flat chained-hash path (prefix_index='flat') must keep
    its exact behavior after the radix swap — the match_prefix edges the
    new subsystem must preserve, exercised through the engine."""

    @pytest.mark.slow
    def test_chunking_preempt_resume_page_aligned_flat(self, cfg, params):
        """Cross-class chunking preemption registers written chunks and
        the resume's match_prefix lands page-aligned (the engine's
        chunking-preemption path), with output identical to a cold
        engine."""
        from kubeflow_tpu.core.serving import QoSSpec

        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        long_p = list(range(1, 70))          # 69 tokens: 4 full 16-pages
        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=128, paged=True, page_size=16,
            prefix_index="flat", chunked_prefill_tokens=16,
            max_concurrent_prefills=1, qos=QoSSpec(preemption=True)),
            params=params)
        r1 = eng.submit(long_p, sp, qos="batch")
        for _ in range(2):
            eng.step()                       # a couple of chunks land
        r2 = eng.submit([5, 6, 7, 8] * 3, sp, qos="interactive")
        run_all(eng, [r1, r2])
        assert eng.metrics.snapshot()["preemptions"] >= 1
        assert eng._allocator.stats["prefix_hits"] >= 1   # the resume
        cold = make_paged(cfg, params, prefix=False, chunk=16)
        c1 = cold.submit(long_p, sp)
        c2 = cold.submit([5, 6, 7, 8] * 3, sp)
        run_all(cold, [c1, c2])
        assert list(r1.output_tokens) == list(c1.output_tokens)
        assert list(r2.output_tokens) == list(c2.output_tokens)
        assert eng.kv_pages_in_use() == 0

    @pytest.mark.slow
    def test_spec_rollback_with_shared_pages_flat(self, cfg, params):
        """Speculative rollback truncation never frees a shared
        (registered, ref>0) prefix page on the flat index either."""
        from kubeflow_tpu.core.serving import SpeculativeSpec

        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
            prefix_index="flat", chunked_prefill_tokens=16,
            speculative=SpeculativeSpec(mode="ngram", k=3)),
            params=params)
        sp = SamplingParams(max_new_tokens=12, temperature=0.0)
        p = [5, 3, 5, 3, 5, 3, 1, 2] * 3
        r1 = eng.submit(list(p), sp)
        for _ in range(6):
            eng.step()
        r2 = eng.submit(list(p) + [4, 4], sp)
        run_all(eng, [r1, r2])
        base = make_paged(cfg, params, prefix=False)
        b1 = base.submit(list(p), sp)
        run_all(base, [b1])
        b2 = base.submit(list(p) + [4, 4], sp)
        run_all(base, [b2])
        assert list(r1.output_tokens) == list(b1.output_tokens)
        assert list(r2.output_tokens) == list(b2.output_tokens)
        assert eng.kv_pages_in_use() == 0
        eng._allocator.assert_quiescent()


class TestPagedAttentionKernel:
    """The Pallas paged-attention decode kernel (ops/paged_attention.py)
    must agree exactly with the gather+XLA oracle (interpret mode off-TPU)."""

    def _setup(self, B=3, H=8, K=2, D=16, pg=8, mpp=4, P=10):
        import numpy as np

        rng = np.random.default_rng(0)
        pool_k = jnp.asarray(rng.normal(size=(P, pg, K, D)), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(P, pg, K, D)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        table = jnp.asarray([[3, 1, 7, -1], [0, 2, -1, -1], [5, 4, 9, 6]],
                            jnp.int32)
        lengths = jnp.asarray([19, 9, 30], jnp.int32)
        return q, pool_k, pool_v, table, lengths

    def test_matches_gather_oracle(self, cfg):
        import dataclasses

        from kubeflow_tpu.ops.paged_attention import paged_decode_attention
        from kubeflow_tpu.serve.paged import _decode_attention, paged_gather

        q, pk, pv, table, lengths = self._setup()
        out = paged_decode_attention(q, pk, pv, table, lengths)
        c = dataclasses.replace(cfg, n_heads=8, n_kv_heads=2, head_dim=16)
        ref = _decode_attention(q, paged_gather(pk, table),
                                paged_gather(pv, table), lengths, c)
        assert float(jnp.abs(out - ref).max()) < 2e-5

    def test_int8_in_kernel_dequant_matches_gather_oracle(self, cfg):
        """int8 pages + scale rows through the kernel's in-VMEM dequant
        must match the gather+dequantize_kv oracle — same math, so the
        only gap is fp32 accumulation order (~1e-6)."""
        import dataclasses

        from kubeflow_tpu.ops.paged_attention import paged_decode_attention
        from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv
        from kubeflow_tpu.serve.paged import _decode_attention, paged_gather

        q, pk, pv, table, lengths = self._setup()
        qk, sk = quantize_kv(pk)           # [P,pg,K,D] int8, [P,pg,K] f32
        qv, sv = quantize_kv(pv)
        out = paged_decode_attention(q, qk, qv, table, lengths,
                                     pool_ks=sk, pool_vs=sv)
        c = dataclasses.replace(cfg, n_heads=8, n_kv_heads=2, head_dim=16)
        dk = dequantize_kv(qk, sk, jnp.float32)
        dv = dequantize_kv(qv, sv, jnp.float32)
        ref = _decode_attention(q, paged_gather(dk, table),
                                paged_gather(dv, table), lengths, c)
        assert float(jnp.abs(out - ref).max()) < 2e-5
        # And the quantization itself stays within its error band of the
        # full-precision attention (sanity that scales weren't dropped).
        full = paged_decode_attention(q, pk, pv, table, lengths)
        assert float(jnp.abs(out - full).max()) < 0.05

    def test_int8_kernel_requires_scale_pair(self):
        from kubeflow_tpu.ops.paged_attention import paged_decode_attention
        from kubeflow_tpu.ops.quantization import quantize_kv

        q, pk, pv, table, lengths = self._setup()
        qk, sk = quantize_kv(pk)
        qv, _ = quantize_kv(pv)
        with pytest.raises(ValueError, match="together"):
            paged_decode_attention(q, qk, qv, table, lengths, pool_ks=sk)

    def test_unmapped_and_partial_pages_masked(self):
        """Garbage in unmapped (-1) pages and beyond-length positions must
        not leak into the output: shrinking lengths changes results only
        through real positions."""
        from kubeflow_tpu.ops.paged_attention import paged_decode_attention

        q, pk, pv, table, lengths = self._setup()
        base = paged_decode_attention(q, pk, pv, table, lengths)
        # Poison every unmapped page's content: output must be identical.
        poisoned_k = pk.at[8].set(999.0)    # page 8 is unmapped everywhere
        poisoned_v = pv.at[8].set(999.0)
        out = paged_decode_attention(q, poisoned_k, poisoned_v, table,
                                     lengths)
        assert float(jnp.abs(out - base).max()) == 0.0

    @pytest.mark.slow   # ~12s e2e; the kernel-level pallas-vs-gather
    # equivalence tests above stay fast
    def test_engine_pallas_matches_gather_end_to_end(self):
        """The whole paged engine under attn_impl=pallas (interpret mode)
        must reproduce the gather path's greedy outputs. float32 config:
        the kernel accumulates fp32 where the gather path rounds probs to
        the cache dtype, so in bf16 the two are numerically equal but not
        bitwise — f32 keeps the ~1e-7 gap far below any argmax tie."""
        fcfg = preset("tiny", vocab_size=512, dtype="float32")
        fparams = init_decoder_params(jax.random.PRNGKey(0), fcfg)
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        prompts = [[5, 17, 3, 99, 42], list(range(1, 40)), [7] * 20]

        def run(impl):
            eng = LLMEngine(fcfg, BatchingSpec(
                max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32, paged_attn_impl=impl),
                params=fparams)
            reqs = [eng.submit(p, sp) for p in prompts]
            run_all(eng, reqs)
            return [list(r.output_tokens) for r in reqs]

        assert run("pallas") == run("gather")

    @pytest.mark.slow   # interpret-mode kernel e2e, ~15s
    def test_engine_int8_pallas_matches_gather_end_to_end(self):
        """int8 pool + in-kernel dequant vs int8 pool + gather+dequant:
        both read the SAME quantized pages, so greedy outputs must be
        token-identical (the dequant happens in different places but is
        the same math; f32 config keeps the fp-accumulation gap far
        below any argmax tie)."""
        fcfg = preset("tiny", vocab_size=512, dtype="float32")
        fparams = init_decoder_params(jax.random.PRNGKey(0), fcfg)
        sp = SamplingParams(max_new_tokens=8, temperature=0.0)
        prompts = [[5, 17, 3, 99, 42], list(range(1, 40)), [7] * 20]

        def run(impl):
            eng = LLMEngine(fcfg, BatchingSpec(
                max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32, kv_cache_dtype="int8",
                paged_attn_impl=impl), params=fparams)
            reqs = [eng.submit(p, sp) for p in prompts]
            run_all(eng, reqs)
            return [list(r.output_tokens) for r in reqs]

        assert run("pallas") == run("gather")

    def test_unknown_impl_rejected(self, cfg, params):
        with pytest.raises(ValueError, match="paged_attn_impl"):
            LLMEngine(cfg, BatchingSpec(
                max_batch_size=2, max_seq_len=64, paged=True, page_size=16,
                paged_attn_impl="flash"), params=params)


# -- the decode kernel walks a row's live pages itself ----------------------------
#
# One call a case: rows [B] over a pool of pages of 16 tokens (heads of 128,
# two KV heads: the strided form of the kernel, as on the chip), ``N`` = 4
# pages a turn. A row is (table row, length, lower or None).

_WALK_MPP = 10
_WALK = {
    # contexts that end on a page's FIRST and LAST token; 1, 4, 5, 8 and 9
    # live pages (a whole and a short last turn); a row of length 0
    "page_edges": [([3], 0, None), ([4, 5, 6, 7], 48, None),
                   ([8, 9, 10, 11], 63, None),
                   ([12, 13, 14, 15, 16], 64, None),
                   (list(range(17, 25)), 127, None),
                   (list(range(25, 34)), 128, None)],
    # dead rows (every id -1, a stale length) between live ones
    "dead_rows": [([], 37, None), ([1, 2, 3], 40, None), ([], 0, None),
                  ([], 159, None), ([4, 5, 6, 7, 8, 9], 90, None),
                  ([], 5, None)],
    # a hole inside a live row's range: in a turn of its own pages, alone
    # in the last turn, the row's first page
    "hole": [([1, -1, 2, 3, 4, 5], 93, None), ([6, 7, 8, 9, -1], 79, None),
             ([-1, 10, 11], 40, None), ([12, 13, -1, -1, 14, -1, 15], 110,
                                        None)],
    # ``lower`` inside the first page, on a page's edge, past whole turns
    "lower": [([1, 2, 3], 40, 5), ([4, 5, 6, 7, 8, 9], 95, 32),
              ([10, 11, 12, 13, 14, 15, 16, 17, 18], 143, 100),
              ([19, 20], 31, 31), ([21, 22, 23], 47, 0)],
    # the window layers' call: a two-page table, its walk one turn
    "window": [([1, 2], 20, 5), ([3, 4], 31, 16), ([5, -1], 9, 0),
               ([6, 7], 16, 1), ([], 0, 0)],
}


# What the kernels over whole-token rows take of it (a latent pool, packed
# K/V rows: no lower bound): 8 pages a turn over one plane, 4 over two, so 8
# and 9 live pages are a whole turn and a short last one of the first too.
_ROW_WALK = {case: _WALK[case] for case in ("page_edges", "dead_rows",
                                            "hole")}


def _walk_rows(rows, mpp):
    import numpy as np

    table = np.full((len(rows), mpp), -1, np.int32)
    for r, (ids, _, _) in enumerate(rows):
        table[r, :len(ids)] = ids
    lengths = np.asarray([ln for _, ln, _ in rows], np.int32)
    bounded = rows[0][2] is not None
    lower = np.asarray([lo for _, _, lo in rows], np.int32) if bounded \
        else None
    return table, lengths, lower


def _idle_pages(rows, pg, pages):
    """[pages] bool: the pages the walk may NOT read, every one but those a
    row maps at or before its length's page."""
    import numpy as np

    used = {i for ids, ln, _ in rows
            for j, i in enumerate(ids) if i >= 0 and j <= ln // pg}
    return np.asarray([i not in used for i in range(pages)])


def _without_holes(table, lengths, lower, pg):
    """The same attention with no hole: a row's unmapped pages inside its
    range are cut out and the positions behind them move up (attention
    knows no position beyond the masks)."""
    import numpy as np

    table, lengths = table.copy(), lengths.copy()
    lower = None if lower is None else lower.copy()
    for r in range(table.shape[0]):
        last = lengths[r] // pg
        keep = [j for j in range(table.shape[1])
                if not (table[r, j] < 0 and j <= last)]
        cut = [j for j in range(last + 1) if table[r, j] < 0]
        if len(cut) == last + 1:        # nothing to attend to
            table[r], lengths[r] = -1, 0
            continue
        if lower is not None:
            assert not any(j * pg < lower[r] for j in cut)
        lengths[r] -= pg * len(cut)
        row = table[r, keep]
        table[r] = -1
        table[r, :len(row)] = row
    return table, lengths, lower


@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("case", sorted(_WALK))
def test_decode_kernel_walks_live_pages(cfg, case, pool, group):
    """``paged_decode_attention`` against ``_decode_attention`` over the
    gathered pages. Every unmapped page and every page no context holds is
    POISONED (NaN): a walk that copied one, or attended to a buffer it did
    not fill, shows it."""
    import dataclasses

    import numpy as np

    from kubeflow_tpu.ops import paged_attention as pa
    from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv
    from kubeflow_tpu.serve.paged import _decode_attention, paged_gather

    pg, kv, d, pages = 16, 2, 128, 36
    rows = _WALK[case]
    mpp = 2 if case == "window" else _WALK_MPP
    assert pa._pages_a_turn(pg * kv * d * 4, _WALK_MPP) == 4
    table, lengths, lower = _walk_rows(rows, mpp)
    rng = np.random.default_rng(len(case))
    dt = jnp.float32 if pool == "int8" else jnp.dtype(pool)
    pk, pv = (jnp.asarray(rng.normal(size=(pages, pg, kv, d)), dt)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(rows), 1, kv * group, d)), dt)
    idle = jnp.asarray(_idle_pages(rows, pg, pages))[:, None, None, None]
    extra, scales = {}, None
    if pool == "int8":
        (pk, sk), (pv, sv) = quantize_kv(pk), quantize_kv(pv)
        scales = (sk, sv)
        extra = {"pool_ks": jnp.where(idle[..., 0], jnp.nan, sk),
                 "pool_vs": jnp.where(idle[..., 0], jnp.nan, sv)}
        poisoned = (pk, pv)
    else:
        poisoned = tuple(jnp.where(idle, jnp.nan, x) for x in (pk, pv))
    got = pa.paged_decode_attention(
        q, *poisoned, jnp.asarray(table), jnp.asarray(lengths),
        lower=None if lower is None else jnp.asarray(lower), **extra)

    if scales is not None:
        pk, pv = (dequantize_kv(x, sc, jnp.float32)
                  for x, sc in zip((pk, pv), scales))
    t2, l2, lo2 = _without_holes(table, lengths, lower, pg)
    c = dataclasses.replace(cfg, n_heads=kv * group, n_kv_heads=kv,
                            head_dim=d)
    want = _decode_attention(
        q, paged_gather(pk, jnp.asarray(t2)), paged_gather(pv, jnp.asarray(t2)),
        jnp.asarray(l2), c, lower=None if lo2 is None else jnp.asarray(lo2))
    nothing = np.asarray((t2 < 0).all(axis=1))      # rows that attend to
    want = jnp.where(nothing[:, None, None, None], 0, want)     # no page
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    # bfloat16: the probabilities are rounded to the pool's type on both
    # sides, before (the kernel) and after (the oracle) they are normed.
    assert float(err) < (3e-2 if pool == "bfloat16" else 2e-5), float(err)


@pytest.mark.parametrize("pool", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_ROW_WALK))
def test_packed_decode_kernel_walks_live_pages(cfg, case, pool):
    """``paged_packed_decode_attention`` (heads of 64, a token's KV heads
    side by side in one row a plane) against ``_decode_attention`` over the
    gathered pages, every page no context holds POISONED as above."""
    import dataclasses

    import numpy as np

    from kubeflow_tpu.ops import paged_attention as pa
    from kubeflow_tpu.serve.paged import _decode_attention, paged_gather

    pg, kv, d, group, pages = 16, 2, 64, 4, 36
    rows = _ROW_WALK[case]
    dt = jnp.dtype(pool)
    assert pa._pages_a_turn(pg * kv * d * dt.itemsize, _WALK_MPP, 2) == 4
    table, lengths, _ = _walk_rows(rows, _WALK_MPP)
    rng = np.random.default_rng(len(case))
    pk, pv = (jnp.asarray(rng.normal(size=(pages, pg, kv * d)), dt)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(len(rows), 1, kv * group, d)), dt)
    idle = jnp.asarray(_idle_pages(rows, pg, pages))[:, None, None]
    got = pa.paged_packed_decode_attention(
        q, jnp.where(idle, jnp.nan, pk), jnp.where(idle, jnp.nan, pv),
        jnp.asarray(table), jnp.asarray(lengths), kv)

    t2, l2, _ = _without_holes(table, lengths, None, pg)
    c = dataclasses.replace(cfg, n_heads=kv * group, n_kv_heads=kv,
                            head_dim=d)
    want = _decode_attention(
        q, *(paged_gather(x.reshape(pages, pg, kv, d), jnp.asarray(t2))
             for x in (pk, pv)), jnp.asarray(l2), c)
    nothing = np.asarray((t2 < 0).all(axis=1))
    want = jnp.where(nothing[:, None, None, None], 0, want)
    assert got.dtype == dt and got.shape == q.shape
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert float(err) < (3e-2 if pool == "bfloat16" else 2e-5), float(err)


# -- the decode step writes the pool in place (flat carry) ---------------------

def _oracle_decode_step(params, cache, tokens, lengths, live, cfg, attn_impl):
    """The decode step in its plain per-layer form, kept here as the
    reference: for each layer slice that layer's ``[P,pg,KV,Dh]`` planes out
    of the pool, write the token's row, attend over the slice, put the slice
    back. Same building blocks and arithmetic as serve/paged.py's step; only
    the pool's residency differs."""
    from kubeflow_tpu.models import layers as L
    from kubeflow_tpu.ops.paged_attention import paged_decode_attention
    from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv
    from kubeflow_tpu.serve.paged import _decode_attention, paged_gather

    dt = cfg.activation_dtype
    table = cache["table"]
    pools = {n: p for n, p in cache.items() if n != "table"}
    num_pages, pg = pools["k"].shape[1:3]
    x = params["embed"].astype(dt)[tokens[:, None]]
    positions = lengths[:, None]
    page_id = table[jnp.arange(tokens.shape[0]),
                    jnp.clip(lengths // pg, 0, table.shape[1] - 1)]
    pidx = jnp.where(live & (page_id >= 0), page_id, num_pages)
    off = lengths % pg
    def one_layer(carry, scan_in):
        x, pools = carry
        bp, layer = scan_in
        h = L.rmsnorm(x, bp["ln1"], cfg)
        q = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wv"].astype(dt))
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        rows = {"k": k[:, 0], "v": v[:, 0]}
        if "ks" in pools:
            rows["k"], rows["ks"] = quantize_kv(k[:, 0])
            rows["v"], rows["vs"] = quantize_kv(v[:, 0])
        slab = {n: pools[n][layer].at[pidx, off].set(r, mode="drop")
                for n, r in rows.items()}
        if attn_impl == "pallas":
            attn = paged_decode_attention(
                q, slab["k"], slab["v"], table, lengths,
                pool_ks=slab.get("ks"), pool_vs=slab.get("vs"))
        else:
            ck = paged_gather(slab["k"], table)
            cv = paged_gather(slab["v"], table)
            if "ks" in slab:
                ck = dequantize_kv(ck, paged_gather(slab["ks"], table), dt)
                cv = dequantize_kv(cv, paged_gather(slab["vs"], table), dt)
            attn = _decode_attention(q, ck, cv, lengths, cfg)
        x = x + jnp.einsum("bshk,hkd->bsd", attn,
                           bp["attn"]["wo"].astype(dt))
        x = x + L.mlp_block(bp["mlp"], L.rmsnorm(x, bp["ln2"], cfg), cfg)
        return (x, {n: pools[n].at[layer].set(slab[n]) for n in pools}), None

    (x, pools), _ = jax.lax.scan(
        one_layer, (x, pools),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = L.rmsnorm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(dt),
                        preferred_element_type=jnp.float32)[:, 0]
    return logits, {**pools, "table": table}


class TestDecodeWritesPoolInPlace:
    """The decode step carries the WHOLE pool, viewed flat [L*P,...],
    through its layer scan and writes a layer's rows at ``layer*P + page``
    (serve/paged.py::_paged_decode_step). It must be bitwise what the
    per-layer form computes, touch no page of another layer, and compile
    to a dispatch that never copies the pool."""

    PG, NP, MPP = 4, 12, 3          # page size, pages a layer, pages a slot

    def _case(self, kv_dtype):
        import numpy as np

        cfg = preset("tiny", vocab_size=64, n_layers=3)
        params = init_decoder_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(7)
        shape = (cfg.n_layers, self.NP, self.PG, cfg.n_kv_heads, cfg.head_dim)
        if kv_dtype == "int8":
            cache = {n: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                     for n in ("k", "v")}
            for n in ("ks", "vs"):
                cache[n] = jnp.asarray(
                    rng.uniform(0.001, 0.02, shape[:-1]), jnp.float32)
        else:
            cache = {n: jnp.asarray(rng.normal(size=shape),
                                    cfg.activation_dtype) for n in ("k", "v")}
        # Page 0 belongs to no slot: under the flat view it is where a
        # dropped write aimed at ``layer*P + P`` would land, one layer up.
        # row 0 plain (its second page); row 1 DEAD on mapped pages; row 2
        # live but its write page is unmapped; row 3 crosses from its first
        # page to its second.
        cache["table"] = jnp.asarray(
            [[3, 5, -1], [7, 2, -1], [9, -1, -1], [4, 11, 8]], jnp.int32)
        lengths = np.array([self.PG, 6, self.PG, self.PG - 2], np.int32)
        live = np.array([True, False, True, True])
        return cfg, params, cache, lengths, live

    @pytest.mark.parametrize("attn_impl", ["gather", "pallas"])
    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_bitwise_equal_to_per_layer_oracle(self, kv_dtype, attn_impl):
        import numpy as np

        from kubeflow_tpu.serve.paged import _paged_decode_step

        cfg, params, cache, lengths, live = self._case(kv_dtype)
        initial = {n: np.asarray(p) for n, p in cache.items()}
        table = initial["table"]
        step = jax.jit(lambda c, t, ln, lv: _paged_decode_step(
            params, c, t, ln, lv, cfg, attn_impl=attn_impl))
        oracle = jax.jit(lambda c, t, ln, lv: _oracle_decode_step(
            params, c, t, ln, lv, cfg, attn_impl))
        # "gather" reads SOME page for an unmapped table entry inside the
        # attended range (the clamp picks which: page 0 of the layer's slab
        # there, of the flat pool here), so row 2's logits are garbage of
        # two kinds; the kernel skips the entry and agrees on every row.
        # No engine dispatches a live row in that state.
        rows = [0, 1, 3] if attn_impl == "gather" else [0, 1, 2, 3]
        ours, theirs = dict(cache), dict(cache)
        written = set()
        rng = np.random.default_rng(11)
        for _ in range(4):
            tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, 4), jnp.int32)
            ln, lv = jnp.asarray(lengths), jnp.asarray(live)
            got, ours = step(ours, tokens, ln, lv)
            want, theirs = oracle(theirs, tokens, ln, lv)
            np.testing.assert_array_equal(np.asarray(got)[rows],
                                          np.asarray(want)[rows])
            assert set(ours) == set(theirs) == set(cache)
            for n in cache:
                assert ours[n].shape == cache[n].shape
                np.testing.assert_array_equal(np.asarray(ours[n]),
                                              np.asarray(theirs[n]))
            for b in np.flatnonzero(live):
                page = table[b, lengths[b] // self.PG]
                if page >= 0:
                    written.add((int(page), int(lengths[b] % self.PG)))
            lengths = np.where(live, lengths + 1, lengths)
        # Row 3 wrote on both sides of its page boundary; rows 1 and 2
        # wrote nothing; every (page, offset) outside the written set is
        # what it was IN EVERY LAYER, page 0 above all.
        assert {p for p, _ in written} == {5, 4, 11}
        for n in ("k", "v", "ks", "vs"):
            if n not in cache:
                continue
            after = np.asarray(ours[n])
            touched = np.zeros(after.shape[:3], bool)
            for page, off in written:
                touched[:, page, off] = True
            changed = (after != initial[n]).reshape(*touched.shape, -1).any(-1)
            assert not (changed & ~touched).any()
            assert changed[:, [5, 4, 11]].any(axis=(1, 2)).all()

    def test_compiled_dispatch_never_copies_the_pool(self):
        """Optimized HLO of the 8-step dispatch with the pool donated, at a
        pool large enough to dominate the program: no ``copy`` of a
        pool-shaped operand, temporaries under ONE plane, both planes
        aliased input to output. (The scanned-slab form read 3 such copies
        and three planes of temporaries here.) float32, because the CPU
        backend widens a bf16 scatter's whole operand to f32 and back: a
        pool-sized artefact of this backend, not of the program."""
        import re

        from kubeflow_tpu.serve.paged import paged_decode_multi

        cfg = preset("tiny", vocab_size=64, dtype="float32",
                     param_dtype="float32")
        params = init_decoder_params(jax.random.PRNGKey(1), cfg)
        slots, pg, mpp, num_pages = 4, 16, 4, 2048
        pool = (cfg.n_layers, num_pages, pg, cfg.n_kv_heads, cfg.head_dim)
        plane = jax.ShapeDtypeStruct(pool, cfg.activation_dtype)
        plane_bytes = plane.size * plane.dtype.itemsize
        i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        f32 = jax.ShapeDtypeStruct((slots,), jnp.float32)
        compiled = jax.jit(
            lambda c, tbl, t, ln, lv, tmp, tk, tp, st, bd, key:
            paged_decode_multi(params, {**c, "table": tbl}, t, ln, lv, tmp,
                               tk, tp, st, bd, key, cfg, 8,
                               sample_mode="greedy", attn_impl="gather"),
            donate_argnums=(0,)).lower(
                {"k": plane, "v": plane},
                jax.ShapeDtypeStruct((slots, mpp), jnp.int32), i32, i32,
                jax.ShapeDtypeStruct((slots,), jnp.bool_), f32, i32, f32,
                i32, i32, jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        dims = "|".join(",".join(map(str, s)) for s in
                        (pool, (pool[0] * pool[1],) + pool[2:], pool[1:]))
        copies = re.findall(
            rf"= \w+\[(?:{dims})\]\S* copy(?:-start)?\(", compiled.as_text())
        assert not copies, copies
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < plane_bytes, (
            mem.temp_size_in_bytes, plane_bytes)
        assert mem.alias_size_in_bytes >= 2 * plane_bytes


# -- the pool's one block over T tokens a row ------------------------------------

POOL_STEP_CASES = {
    # case: (preset, overrides, int8 pool)
    "dense-f32": ("tiny", {}, False),
    "int8-pool": ("tiny", {}, True),
    "sorted-experts": ("tiny-moe", {"moe_impl": "sorted"}, False),
}


@pytest.mark.parametrize("case", sorted(POOL_STEP_CASES))
def test_pool_step_over_t_tokens_is_t_single_steps(case):
    """``paged._pool_forward`` (the builder under the decode step, the
    in-place chunk and the speculative verify) at ``T = 4`` tokens a row
    returns, at column ``t``, the logits that ``t + 1`` single decode steps
    (``T = 1``) return, and leaves the pool rows those steps leave. A dead
    row writes nothing; a row whose tokens run onto an unmapped page writes
    the tokens before it and nothing after. ``paged_verify_step`` is that
    call and the argmax."""
    import numpy as np

    from kubeflow_tpu.serve.paged import (
        _head_logits, _paged_decode_step, _pool_forward, _pool_planes,
    )
    from kubeflow_tpu.serve.spec_decode import paged_verify_step

    name, over, int8 = POOL_STEP_CASES[case]
    cfg = preset(name, vocab_size=64, dtype="float32", param_dtype="float32",
                 **over)
    params = init_decoder_params(jax.random.PRNGKey(2), cfg)
    pg, pages, t = 4, 12, 4
    rng = np.random.default_rng(5)
    shape = (cfg.n_layers, pages, pg, cfg.n_kv_heads, cfg.head_dim)
    if int8:
        cache = {n: jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                 for n in ("k", "v")}
        for n in ("ks", "vs"):
            cache[n] = jnp.asarray(rng.uniform(0.001, 0.02, shape[:-1]),
                                   jnp.float32)
    else:
        cache = {n: jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for n in ("k", "v")}
    # row 0 crosses from its first page into its second; row 1 is DEAD on
    # mapped pages; row 2's third and fourth tokens fall on an unmapped
    # page; row 3 stays inside one page. Page 0 belongs to no row.
    table = jnp.asarray([[3, 5, -1], [7, 2, -1], [9, -1, -1], [4, 11, 8]],
                        jnp.int32)
    start = np.array([pg - 2, 5, pg - 2, pg], np.int32)
    live = np.array([True, False, True, True])
    tokens = rng.integers(1, cfg.vocab_size, (4, t)).astype(np.int32)
    initial = {n: np.asarray(p) for n, p in cache.items()}

    def at_once(c):
        x, flat = _pool_forward(
            params, c, jnp.asarray(tokens), table, jnp.asarray(start),
            jnp.where(jnp.asarray(live), t, 0), cfg, "gather")
        return _head_logits(params, x, cfg), _pool_planes(flat, c)

    got, pool = jax.jit(at_once)(cache)
    step = jax.jit(lambda c, tok, ln: _paged_decode_step(
        params, c, tok, ln, jnp.asarray(live), cfg))
    stepped = {**cache, "table": table}
    whole = [0, 3]                      # live rows, every page mapped
    for i in range(t):
        want, stepped = step(stepped, jnp.asarray(tokens[:, i]),
                             jnp.asarray(start + i))
        rows = whole + ([2] if i < 2 else [])
        np.testing.assert_allclose(np.asarray(got)[rows, i],
                                   np.asarray(want)[rows], atol=2e-4)
    written = {(3, 2), (3, 3), (5, 0), (5, 1), (9, 2), (9, 3),
               (11, 0), (11, 1), (11, 2), (11, 3)}
    for n in cache:
        after = np.asarray(pool[n])
        np.testing.assert_allclose(after, np.asarray(stepped[n]),
                                   atol=1e-5)
        touched = np.zeros(after.shape[:3], bool)
        for page, off in written:
            touched[:, page, off] = True
        changed = (after != initial[n]).reshape(*touched.shape, -1).any(-1)
        assert not (changed & ~touched).any()           # dead, unmapped
        assert changed[:, [3, 5, 9, 11]].any(axis=(1, 2)).all()
    greedy, verified = paged_verify_step(
        params, {**cache, "table": table}, jnp.asarray(tokens),
        jnp.asarray(start), jnp.asarray(live), cfg)
    np.testing.assert_array_equal(
        np.asarray(greedy)[whole], np.asarray(got).argmax(-1)[whole])
    for n in cache:
        np.testing.assert_array_equal(np.asarray(verified[n]),
                                      np.asarray(pool[n]))
