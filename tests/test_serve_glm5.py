"""Latent attention that reads the keys a learned indexer selects (the
``tiny-glm-5`` preset: GLM-5's structure at odd small ranks, ``index_topk``
24 under the tests' contexts), on the CPU: the indexer's scores and the exact
selection against the benchmark's plain reference (a sort a query), sets
position for position; ``decoder_forward``, the engine's chunked prefill and
its decode step through the two planes of the pool ("gather", and "pallas"
with every kernel interpreted) and the mixed chunk-and-step program against
the reference's ONE full forward; the three kernels against their XLA forms;
contexts under, at and over ``index_topk`` and a chunk that straddles it; a
tie at the threshold; both planes under one page id (``copy_pages``, prefix
reuse, preemption); the counters and the spans; the shares of all chips; and
each mechanism that refuses the model by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture, correctness
from benchmark import manifest as mf
from benchmark.weights import make_params
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.ops import paged_attention as PA
from kubeflow_tpu.serve.engine import (
    LLMEngine, SamplingParams, _keys_selected,
)
from kubeflow_tpu.serve.paged import (
    copy_pages, paged_gather, pool_bytes_per_token, pool_planes,
)
from test_serve_chunk_rows import record_spans

CONF = mf.load_json("benchmark/configs/rehearsal-tiny-glm5.json")
REF = architecture.part(CONF, "reference")
TOPK = CONF["index_topk"]


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny-glm-5", dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded tree (stratified router bias, a bias on the
    indexer's key norm), float32."""
    return make_params(CONF, 11, "float32")


def make_engine(cfg, params, **kw):
    spec = dict(max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32, decode_steps=4)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec), params=params)


def run_all(eng, reqs, max_steps=3000):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def greedy(eng, prompt, n):
    req = eng.submit(list(prompt), SamplingParams(max_new_tokens=n,
                                                  temperature=0.0))
    run_all(eng, [req])
    return req.result()


def full_forward_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        lg, _, _ = decoder_forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


def sorted_selection(scores: np.ndarray, k: int) -> np.ndarray:
    """The mask a stable sort a query gives: the reference's rule."""
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        row = scores[idx]
        order = np.argsort(-row, kind="stable")
        out[idx][order[:min(k, int(np.isfinite(row).sum()))]] = True
    return out & np.isfinite(scores)


def causal_scores(rng, b, t, s, first):
    """[B, T, S] scores, ``-inf`` behind each query (query ``i`` of row
    ``r`` at position ``first[r] + i``)."""
    sc = rng.normal(size=(b, t, s)).astype(np.float32)
    pos = np.asarray(first)[:, None] + np.arange(t)[None]
    return np.where(np.arange(s)[None, None] <= pos[:, :, None], sc,
                    -np.inf).astype(np.float32), pos


class TestAgainstTheReference:
    def test_the_seeded_tree_is_the_programs_tree(self, cfg, params):
        want = jax.eval_shape(
            lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
        assert jax.tree.structure(params) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
            assert a.shape == b.shape
        attn = params["layers"]["attn"]
        assert attn["wq_idx"].shape == (3, 2 * 16, 24)
        assert attn["wk_idx"].shape == (3, 64, 16)
        assert attn["w_idx"].shape == (3, 64, 2)
        assert attn["k_idx_norm"].shape == attn["k_idx_bias"].shape == (3, 16)
        assert float(jnp.abs(attn["k_idx_bias"]).max()) > 0
        assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))

    def test_decoder_forward_is_the_references_forward(self, cfg, params):
        toks = correctness.check_tokens(3, 0, 70, 256)
        with jax.default_matmul_precision("highest"):
            got, _, _ = decoder_forward(params, jnp.asarray(toks[None]), cfg)
            want = REF.logits(params, jnp.asarray(toks), CONF)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=5e-5)

    def test_the_selected_sets_are_the_references_position_for_position(
            self, cfg, params):
        """Layer 0's indexer on the embedded tokens, float32: queries under
        (t < 23), at (t = 23) and over ``index_topk`` in one sequence."""
        toks = jnp.asarray(correctness.check_tokens(5, 0, 70, 256))
        with jax.default_matmul_precision("highest"):
            want, ref_scores = REF.selected_sets(params, toks, CONF, 0)
            bp = jax.tree.map(lambda a: a[0], params["dense_layers"])
            x = params["embed"][toks][None]
            pos = jnp.arange(70)[None]
            h = L.rmsnorm(x, bp["ln1"], cfg)
            *_, cq = L.latent_qkv(bp["attn"], h, pos, cfg)
            qi, ki, wi = L.index_qkw(bp["attn"], h, cq, pos, cfg)
            scores = L.index_scores(qi, wi, ki, pos)
        got = np.asarray(L.select_keys(scores, TOPK))[0]
        want = np.asarray(want)
        fin = np.isfinite(np.asarray(ref_scores))
        np.testing.assert_allclose(np.asarray(scores)[0][fin],
                                   np.asarray(ref_scores)[fin], atol=2e-5)
        assert np.array_equal(got, want)
        counts = got.sum(-1)
        assert counts.tolist() == [min(TOPK, t + 1) for t in range(70)]
        assert not got[40:, :].all(axis=0).all()     # a real selection

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_chunked_prefill_then_decode_is_the_references_full_forward(
            self, cfg, params, impl):
        """The benchmark's own drive of the engine's programs: three chunks
        of 32 into the two planes (the first straddles ``index_topk`` = 24),
        then teacher-forced decode steps; logits of the last chunk and of
        every step against the reference's ONE full forward."""
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        toks = correctness.check_tokens(3, 0, 90 + 6, 256)
        got, real = correctness.engine_logits(eng, toks, 90, 6)
        assert real == 90 - 64
        want = correctness.reference_logits(params, toks, CONF,
                                            last=real + 6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)

    @pytest.mark.parametrize("plen", [10, 23, 24, 25])
    def test_contexts_under_at_and_over_the_selection(self, cfg, params,
                                                      plen):
        eng = make_engine(cfg, params, paged_attn_impl="pallas")
        toks = correctness.check_tokens(7, plen, plen + 4, 256)
        got, real = correctness.engine_logits(eng, toks, plen, 4)
        want = correctness.reference_logits(params, toks, CONF,
                                            last=real + 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_served_tokens_are_the_full_forwards(self, cfg, params, impl):
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        assert greedy(eng, prompt, 8) == full_forward_greedy(
            params, cfg, prompt, 8)

    def test_the_mixed_program_selects_for_chunk_rows_and_decode_rows(
            self, cfg, params):
        """Where the kernels are on, a chunk program carries the slots'
        step (``paged_mixed_step``): both groups of rows select."""
        eng = make_engine(cfg, params, paged_attn_impl="pallas",
                          decode_steps=1, prefill_interleave_steps=1)
        assert eng._plan.carries_step
        rng = np.random.default_rng(2)
        prompts = [rng.integers(3, 256, n).tolist() for n in (70, 45, 90)]
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=6,
                                             temperature=0.0))
                for p in prompts]
        run_all(eng, reqs)
        assert [r.result() for r in reqs] == [
            full_forward_greedy(params, cfg, p, 6) for p in prompts]
        assert eng.counters()["mixed_programs_dispatched"] > 0


class TestTheSelection:
    def test_ties_at_the_threshold_go_to_the_lower_position(self):
        rng = np.random.default_rng(0)
        sc, _ = causal_scores(rng, 3, 50, 80, [20, 0, 29])
        sc[0, :, 10:50] = np.where(np.isfinite(sc[0, :, 10:50]), 0.0, -np.inf)
        sc[0, :, 20:30] = np.where(np.isfinite(sc[0, :, 20:30]), -0.0,
                                   -np.inf)        # -0.0 ties with 0.0
        sc[1, 7] = np.where(np.isfinite(sc[1, 7]), 1.5, -np.inf)
        sc[2, :, ::3] = np.where(np.isfinite(sc[2, :, ::3]), 0.25, -np.inf)
        got = np.asarray(L.select_keys(jnp.asarray(sc), TOPK))
        assert np.array_equal(got, sorted_selection(sc, TOPK))
        # every score a tie: the lowest positions
        assert got[1, 7].nonzero()[0].tolist() == list(range(8))
        ref = np.asarray(REF.selected_keys(jnp.asarray(sc[0]), TOPK))
        assert np.array_equal(ref, got[0])

    def test_at_most_topk_visible_keys_are_all_selected(self):
        sc, _ = causal_scores(np.random.default_rng(1), 1, TOPK, 64, [0])
        got = np.asarray(L.select_keys(jnp.asarray(sc), TOPK))
        assert np.array_equal(got, np.isfinite(sc))
        # a table shorter than the selection: nothing to decide
        assert np.array_equal(
            np.asarray(L.select_keys(jnp.asarray(sc[..., :16]), TOPK)),
            np.isfinite(sc[..., :16]))

    @pytest.mark.parametrize("tile,first", [(8, [0, 40, 70]), (1, [5]),
                                            (16, [33])])
    def test_the_kernel_is_the_counting_form(self, tile, first):
        """``dsa_select`` (interpreted) over page-major scores: ties, a
        context that ends inside a page, pages behind the queries never
        read (NaN there)."""
        pg, mpp = 16, 6
        rng = np.random.default_rng(3)
        sc, pos = causal_scores(rng, len(first), tile, mpp * pg, first)
        sc[0, :, 3:30:2] = np.where(np.isfinite(sc[0, :, 3:30:2]), 0.5,
                                    -np.inf)
        want = np.asarray(L.select_keys(jnp.asarray(sc), TOPK))
        live = pos[:, -1] // pg + 1
        pm = sc.reshape(len(first), tile, mpp, pg).swapaxes(1, 2).copy()
        for r, n in enumerate(live):
            pm[r, n:] = np.nan
        got = np.asarray(PA.paged_select_keys(
            jnp.asarray(pm), jnp.asarray(pos[:, -1]), TOPK, interpret=True))
        got = got.swapaxes(1, 2).reshape(len(first), tile, mpp * pg) != 0
        assert np.array_equal(got, want)


class TestKernels:
    PG, DI, HI, MPP = 16, 16, 2, 6

    def _pool(self, rng, pages, held):
        pool = rng.normal(size=(pages, self.PG, self.DI)).astype(np.float32)
        pool[held:] = np.nan            # pages no table names
        return jnp.asarray(pool)

    @pytest.mark.parametrize("t,starts", [(1, [37, 0, 95, 5]),
                                          (32, [0, 48, 17, 64]),
                                          (256, [0, 13])])
    def test_index_scores_kernel_is_the_xla_form(self, t, starts,
                                                 monkeypatch):
        """One query a row (a dead row among them), a chunk in one tile, and
        a chunk in two tiles of 128; every page no context holds is NaN."""
        rng = np.random.default_rng(0)
        mpp = 24 if t == 256 else self.MPP
        held = 60 if t == 256 else 30
        pool = self._pool(rng, held + 10, held)
        b = len(starts)
        table = np.full((b, mpp), -1, np.int32)
        perm, k = rng.permutation(held), 0
        for r in range(b):
            need = (starts[r] + t - 1) // self.PG + 1
            table[r, :need] = perm[k:k + need]
            k += need
        if t == 1:
            table[1] = -1
        q = jnp.asarray(rng.normal(size=(b, t, self.HI, self.DI)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(b, t, self.HI)), jnp.float32)
        start = jnp.asarray(starts, jnp.int32)
        # page-major, a tile a row of the walk; back to [B, T, S] here
        tile = min(t, PA.INDEX_QUERY_TILE)
        pm = np.asarray(PA.paged_index_scores(
            q, w, pool, jnp.asarray(table), start, interpret=True))
        assert pm.shape == (b * (t // tile), mpp, tile, self.PG)
        got = pm.reshape(b, t // tile, mpp, tile, self.PG).swapaxes(
            2, 3).reshape(b, t, mpp * self.PG)
        pos = start[:, None] + jnp.arange(t)[None]
        want = np.asarray(L.index_scores(
            q, w, paged_gather(jnp.nan_to_num(pool), jnp.asarray(table)),
            pos))
        want = np.where(np.repeat(table >= 0, self.PG, axis=1)[:, None],
                        want, -np.inf)
        assert np.array_equal(np.isfinite(got), np.isfinite(want))
        fin = np.isfinite(want)
        np.testing.assert_allclose(got[fin], want[fin], atol=2e-5)

    H, R, ROPE, W = 4, 40, 8, 128

    def _rows(self, shape, seed):
        x = np.random.default_rng(seed).normal(size=(*shape, self.W))
        x[..., self.R + self.ROPE:] = 0.0
        return jnp.asarray(x, jnp.float32)

    @staticmethod
    def _attend(q, rows, mask, scale):
        s = jnp.einsum("...hw,tw->...ht", q, rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("...ht,tw->...hw", p, rows)

    def test_decode_kernel_under_a_selection_is_the_gather_form(self):
        rng = np.random.default_rng(4)
        pool = self._rows((24, self.PG), 0)
        lengths = np.asarray([70, 5, 37, 90], np.int32)
        table = np.full((4, self.MPP), -1, np.int32)
        perm, k = rng.permutation(24), 0
        for r, n in enumerate(lengths // self.PG + 1):
            table[r, :n] = perm[k:k + n]
            k += n
        q = self._rows((4, self.H), 1)
        sc, _ = causal_scores(rng, 4, 1, self.MPP * self.PG, lengths)
        sel = np.asarray(L.select_keys(jnp.asarray(sc), TOPK))[:, 0]
        out = PA.paged_latent_decode_attention(
            q, pool, jnp.asarray(table), jnp.asarray(lengths), sm_scale=0.2,
            selected=jnp.asarray(sel.reshape(4, self.MPP, self.PG)),
            interpret=True)
        rows = paged_gather(pool, jnp.asarray(table))
        for b in range(4):
            want = self._attend(q[b], rows[b], sel[b][None], 0.2)
            np.testing.assert_allclose(np.asarray(out[b]), np.asarray(want),
                                       atol=2e-5)
        assert sel[0].sum() == TOPK and sel[1].sum() == 6

    @pytest.mark.parametrize("start,pages", [(0, 4), (37, 7), (64, 9)])
    def test_chunk_kernel_under_a_selection_is_the_gather_form(self, start,
                                                               pages):
        """32 queries in two tiles of 16 (the mask laid page-major, a tile
        a row), a table that is no multiple of the step's pages."""
        rng = np.random.default_rng(5)
        pool = self._rows((12, self.PG), 0)
        q = self._rows((self.H, 32), 1)
        order = rng.permutation(12)[:pages]
        need = -(-(start + 32) // self.PG)
        table = np.where(np.arange(pages) < need, order, -1).astype(np.int32)
        sc, _ = causal_scores(rng, 1, 32, pages * self.PG, [start])
        sel = np.asarray(L.select_keys(jnp.asarray(sc), TOPK))[0]
        paged = sel.reshape(2, 16, pages, self.PG).swapaxes(1, 2)
        out = PA.paged_latent_chunk_attention(
            q, pool, jnp.asarray(table), jnp.int32(start), sm_scale=0.2,
            selected=jnp.asarray(paged, jnp.int32), interpret=True)
        rows = paged_gather(pool, jnp.asarray(table)[None])[0]
        want = self._attend(jnp.swapaxes(q, 0, 1), rows, sel[:, None], 0.2)
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(out, 0, 1)),
                                   np.asarray(want), atol=2e-5)


class TestTheTwoPlanes:
    def test_the_pool_is_described_once(self, cfg):
        f32 = jnp.dtype("float32")
        assert pool_planes(cfg) == (("ckv", (128,), f32), ("idx", (16,), f32))
        assert pool_bytes_per_token(cfg) == 4 * (128 + 16) * 4
        real = preset("glm-5", n_layers=5, dtype="bfloat16")
        bf16 = jnp.dtype("bfloat16")
        assert pool_planes(real) == (("ckv", (640,), bf16),
                                     ("idx", (128,), bf16))
        assert pool_bytes_per_token(real) == 5 * 1536 == 7680
        # without an indexer the pool is what it was
        assert pool_planes(preset("glm-4.7-flash")) == (
            ("ckv", (640,), bf16),)
        with pytest.raises(ValueError, match="int8 KV over a latent"):
            pool_planes(cfg, True)

    def test_engine_builds_both_planes_and_counts_them(self, cfg, params):
        eng = make_engine(cfg, params, max_pages=12)
        assert {n: a.shape for n, a in eng.cache.items()
                if n != "moe_rows"} == {
            "ckv": (4, 12, 16, 128), "idx": (4, 12, 16, 16)}
        c = eng.counters()
        assert c["kv_bytes_per_token"] == 4 * 144 * 4
        assert c["kv_pool_bytes"] == 12 * 16 * c["kv_bytes_per_token"]
        assert c["index_pool_bytes"] == 4 * 12 * 16 * 16 * 4
        assert 9 * c["index_pool_bytes"] == c["kv_pool_bytes"]
        assert (c["dsa_keys_visible"], c["dsa_keys_selected"]) == (0, 0)
        plain = make_engine(preset("tiny-glm", dtype="float32",
                                   param_dtype="float32"), None)
        assert plain.counters()["index_pool_bytes"] == 0

    def test_copy_pages_carries_both_planes(self, cfg):
        rng = np.random.default_rng(0)
        cache = {n: jnp.asarray(rng.normal(size=(4, 6, 16, *t)), jnp.float32)
                 for n, t, _ in pool_planes(cfg)}
        assert sorted(cache) == ["ckv", "idx"]
        out = copy_pages(cache, jnp.asarray([1, 2]), jnp.asarray([4, -1]))
        for n in cache:
            np.testing.assert_array_equal(np.asarray(out[n][:, 4]),
                                          np.asarray(cache[n][:, 1]))
            np.testing.assert_array_equal(np.asarray(out[n][:, :4]),
                                          np.asarray(cache[n][:, :4]))

    def test_a_chunk_writes_both_rows_at_the_same_index(self, cfg, params):
        eng = make_engine(cfg, params)
        toks = correctness.check_tokens(3, 0, 40, 256)
        correctness.engine_logits(eng, toks, 36, 4)
        ckv, idx = (np.asarray(eng.cache[n]) for n in ("ckv", "idx"))
        wrote = np.abs(ckv).sum(-1) > 0                     # [L, P, pg]
        assert np.array_equal(wrote, np.abs(idx).sum(-1) > 0)
        assert wrote.sum() == 4 * 40

    def test_a_zeroed_index_plane_changes_the_answer(self, cfg, params):
        """The comparison's third control, at the program's side: with the
        indexer's keys gone every score ties and the lowest positions are
        read; the logits move far beyond rounding."""
        toks = correctness.check_tokens(3, 0, 96, 256)
        eng = make_engine(cfg, params)
        sound, _ = correctness.engine_logits(eng, toks, 90, 6)
        blind = make_engine(cfg, params)
        got, _ = correctness.engine_logits(blind, toks, 64, 0)
        blind.cache = {**blind.cache,
                       "idx": jnp.zeros_like(blind.cache["idx"])}
        row = jnp.asarray(np.r_[np.arange(6), -np.ones(2)].astype(np.int32))
        block = np.zeros((1, 32), np.int32)
        block[0, :26] = toks[64:90]
        from kubeflow_tpu.serve.paged import context_bucket
        last, _ = blind._paged_chunk(
            blind.params, blind.cache, jnp.asarray(block), row,
            jnp.int32(64), jnp.int32(26), context_bucket(64, 32, 16, 8))
        err = correctness.position_errors(last[:26], sound[:26])
        assert float(np.median(err)) > 0.05


class TestCountersAndSpans:
    def test_keys_visible_and_selected_are_summed_from_the_positions(
            self, cfg, params, monkeypatch):
        spans = record_spans(monkeypatch)
        eng = make_engine(cfg, params, decode_steps=1,
                          pipelined_decode=False)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        greedy(eng, prompt, 8)
        c = eng.counters()
        # two chunks (32 + 18 queries) and seven steps after the first token
        positions = list(range(50)) + list(range(50, 57))
        assert c["dsa_keys_visible"] == sum(t + 1 for t in positions)
        assert c["dsa_keys_selected"] == sum(min(TOPK, t + 1)
                                             for t in positions)
        assert c["decode_context_tokens"] == sum(range(51, 58))
        chunks = [a for n, a in spans if n == "engine.prefill_dispatch"]
        assert [(a["context"], a["selected"]) for a in chunks] == [
            (32 * 33 // 2, _keys_selected(0, 32, TOPK)),
            (18 * 32 + 18 * 19 // 2, 18 * TOPK)]
        rounds = [a for n, a in spans if n == "engine.decode_dispatch"]
        assert [(a["context"], a["selected"]) for a in rounds] == [
            (t + 1, TOPK) for t in range(50, 57)]

    def test_a_prompt_alone_goes_two_chunks_a_program_and_says_each_rows(
            self, cfg, params, monkeypatch):
        """Over the indexed pool a program's spare row carries the prompt's
        NEXT chunk (ISSUE 56: the row behind finds the latent rows AND the
        index keys of the row in front written): five chunks alone in three
        programs of the one width (a latent pool's one-row program is a
        program a bucket, so such an engine's traffic never takes it), the
        tokens of the full forward and of one prefill at a time, and the
        keys visible and selected counted at each row's OWN start, in the
        spans and in the counters."""
        kw = dict(paged_attn_impl="pallas", max_seq_len=256, decode_steps=1,
                  prefill_interleave_steps=1, enable_prefix_caching=False,
                  pipelined_decode=False)
        eng = make_engine(cfg, params, **kw)
        assert eng._plan.ahead and eng._plan.rows_only and eng._plan.rows == 2
        prompt = np.random.default_rng(5).integers(3, 256, 137).tolist()
        spans = record_spans(monkeypatch)
        got = greedy(eng, prompt, 6)
        assert got == full_forward_greedy(params, cfg, prompt, 6)
        assert got == greedy(make_engine(
            cfg, params, max_concurrent_prefills=1, **kw), prompt, 6)
        c = eng.counters()
        assert [c[f"prefill_{n}"] for n in (
            "programs_dispatched", "chunks_dispatched", "rows_ahead",
            "rows_dead")] == [3, 5, 2, 1]   # the odd last chunk's
        chunks = [a for n, a in spans if n == "engine.prefill_dispatch"][:3]
        assert [(a["pos"], a["chunks"]) for a in chunks] == [
            (0, 2), (64, 2), (128, 1)]

        def seen(pos, real):
            return real * pos + real * (real + 1) // 2

        assert [(a["context"], a["selected"]) for a in chunks] == [
            (seen(0, 32) + seen(32, 32),
             _keys_selected(0, 32, TOPK) + _keys_selected(32, 32, TOPK)),
            (seen(64, 32) + seen(96, 32),
             _keys_selected(64, 32, TOPK) + _keys_selected(96, 32, TOPK)),
            (seen(128, 9), _keys_selected(128, 9, TOPK))]
        positions = list(range(137)) + list(range(137, 142))
        assert c["dsa_keys_visible"] == sum(t + 1 for t in positions)
        assert c["dsa_keys_selected"] == sum(min(TOPK, t + 1)
                                             for t in positions)

    @pytest.mark.parametrize("pos,real,k", [(0, 5, 3), (0, 5, 10), (7, 4, 9),
                                            (10, 4, 3), (2, 6, 4)])
    def test_keys_selected_by_hand(self, pos, real, k):
        assert _keys_selected(pos, real, k) == sum(
            min(k, pos + i + 1) for i in range(real))

    def test_a_model_without_an_indexer_says_nothing(self, monkeypatch):
        spans = record_spans(monkeypatch)
        plain = preset("tiny-glm", dtype="float32", param_dtype="float32")
        eng = make_engine(plain, None)
        greedy(eng, list(range(5, 45)), 3)
        assert all("selected" not in a for _, a in spans)
        c = eng.counters()
        assert (c["dsa_keys_visible"], c["dsa_keys_selected"]) == (0, 0)


class TestPrefixIndexAndPreemption:
    def test_a_prefix_hit_reuses_both_planes_by_page(self, cfg, params):
        """Both rows a token lie under ONE page id: a matched page brings
        its index keys with its latent rows, and the copy-on-write tail
        copies both."""
        rng = np.random.default_rng(4)
        shared = rng.integers(3, 256, 48).tolist()
        a = shared + rng.integers(3, 256, 9).tolist()
        b = shared + rng.integers(3, 256, 13).tolist()
        eng = make_engine(cfg, params)
        first = greedy(eng, a, 6)
        before = eng.kv_tier_stats()
        second = greedy(eng, b, 6)
        after = eng.kv_tier_stats()
        assert after["prefix_hits"] == before["prefix_hits"] + 1
        assert after["tokens_matched"] >= before["tokens_matched"] + 48
        assert first == full_forward_greedy(params, cfg, a, 6)
        assert second == full_forward_greedy(params, cfg, b, 6)
        c = a[:40] + rng.integers(3, 256, 11).tolist()
        assert greedy(eng, c, 6) == full_forward_greedy(params, cfg, c, 6)
        assert eng.kv_tier_stats()["cow_copies"] > after["cow_copies"]

    def test_preempt_and_recompute_equals_the_cold_result(self, cfg, params):
        rng = np.random.default_rng(8)
        prompts = [rng.integers(3, 256, n).tolist() for n in (40, 44, 36)]
        want = [full_forward_greedy(params, cfg, p, 24) for p in prompts]
        eng = make_engine(cfg, params, max_pages=9, max_seq_len=128,
                          enable_prefix_caching=False)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=24,
                                             temperature=0.0))
                for p in prompts]
        run_all(eng, reqs)
        assert eng.metrics.preemptions > 0
        assert [r.result() for r in reqs] == want
        assert eng.kv_pages_in_use() == 0


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(cfg):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer (the
    tiny preset's group; the published one is sixteen of 16): the parts
    they compute, the shared expert counted ONCE, are the uncut layer's
    result."""
    whole = dataclasses.replace(cfg, experts_held=0)
    p, _ = L.init_moe(jax.random.PRNGKey(3), whole)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    want, _ = L.moe_block(p, x, whole)
    shared = L.mlp_block(p["shared"], x, whole)
    parts, held = [], 0
    for chip in range(4):
        own_cfg = dataclasses.replace(whole, experts_held=4,
                                      expert_offset=4 * chip)
        own = {**p, **{n: p[n][4 * chip:4 * chip + 4]
                       for n in L.EXPERT_LEAVES}}
        out, _, rows = L.moe_block(own, x, own_cfg, rows_out=True)
        assert int(rows[0]) == 2 * 24 * 4
        held += int(rows[1])
        parts.append(out - shared)
    assert held == 2 * 24 * 4
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-5)


class TestRefusals:
    @pytest.mark.parametrize("kw, names", [
        (dict(kv_cache_dtype="int8"), "int8 KV"),
        (dict(role="prefill"), "handoff export/adopt"),
        (dict(role="decode"), "handoff export/adopt"),
        (dict(host_kv_pages=8), "host tier's wire format"),
        (dict(host_kv_pages=8, remote_kv_root="/tmp/never"),
         "host tier's wire format"),
        (dict(speculative={"mode": "ngram", "k": 2}), "speculative verify"),
        (dict(lora={"max_adapters": 2, "rank": 4}), "LoRA targets"),
        (dict(quantize="int8"), "weight quantization"),
    ])
    def test_each_mechanism_refuses_the_indexed_pool_by_name(
            self, cfg, params, kw, names):
        with pytest.raises(ValueError, match="an indexer whose key a token "
                                             "lives in the page pool") as err:
            make_engine(cfg, params, **kw)
        assert names in str(err.value)

    def test_a_mesh_is_refused_by_name(self, cfg, params):
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("model",))
        with pytest.raises(ValueError, match="indexer") as err:
            LLMEngine(cfg, BatchingSpec(
                max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32), params=params, mesh=mesh)
        assert "a mesh (tensor-parallel serving)" in str(err.value)

    @pytest.mark.parametrize("chunk, refused", [
        (64, False), (128, False), (192, True), (256, False)])
    def test_a_chunk_that_is_no_whole_index_tiles_is_refused_by_name(
            self, cfg, params, chunk, refused):
        """``paged_index_scores`` takes a chunk of more than
        ``INDEX_QUERY_TILE`` queries as whole tiles: the engine says so when
        it is built, not at the first prefill's trace."""
        kw = dict(page_size=64, max_seq_len=256,
                  chunked_prefill_tokens=chunk)
        if not refused:
            assert make_engine(cfg, params, **kw).chunk_size == chunk
            return
        with pytest.raises(ValueError, match="indexer") as err:
            make_engine(cfg, params, **kw)
        assert f"chunked_prefill_tokens={chunk}" in str(err.value)
        assert "whole number of the indexer's tiles of 128" in str(err.value)

    def test_an_indexer_needs_latent_attention(self):
        with pytest.raises(ValueError, match="LATENT"):
            preset("tiny", index_topk=8, index_heads=2, index_head_dim=16)
        with pytest.raises(ValueError, match="index_heads > 0"):
            preset("tiny-glm", index_topk=8)

    def test_a_contiguous_cache_is_refused_as_for_every_latent_model(
            self, cfg, params):
        bp = jax.tree.map(lambda a: a[0], params["dense_layers"]["attn"])
        with pytest.raises(NotImplementedError, match="page pool"):
            L.latent_attention_block(
                bp, jnp.zeros((1, 4, 64)), jnp.arange(4)[None], cfg,
                kv_cache={"len": 0})
