"""A model with a latent (MLA) cache, sigmoid-routed experts beside a shared
one and a leading dense layer (the ``tiny-glm`` preset: GLM-4.7-Flash's
structure at odd small ranks), on the CPU: the expanded block and the whole
model against the benchmark's plain reference, chunked prefill and absorbed
decode through the paged latent pool against one full forward, the router's
choice and weights, drop-free experts, the prefix index and preemption over
latent pages, and each mechanism that refuses the model by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture
from benchmark import manifest as mf
from benchmark.weights import make_params
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import (
    decoder_forward, decoder_loss, init_decoder_params, layer_groups,
)
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    _paged_decode_step, copy_pages, paged_gather, pool_bytes_per_token,
    pool_planes,
)
from test_serve_paged import _ROW_WALK, _WALK_MPP, _idle_pages, _walk_rows

CONF = mf.load_json("benchmark/configs/rehearsal-tiny-glm.json")
REF = architecture.part(CONF, "reference")


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny-glm", dtype="float32", param_dtype="float32",
                  max_seq_len=256)


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded tree (non-zero correction bias), float32."""
    return make_params(CONF, 11, "float32")


def make_engine(cfg, params, **kw):
    spec = dict(max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32, decode_steps=4)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec), params=params)


def run_all(eng, reqs, max_steps=800):
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


class TestAgainstTheReference:
    def test_the_seeded_tree_is_the_programs_tree(self, cfg, params):
        shapes = jax.eval_shape(
            lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
        assert jax.tree.structure(shapes) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
            assert a.shape == b.shape
        assert [g[0] for g in layer_groups(cfg)] == ["dense_layers", "layers"]
        assert float(jnp.abs(params["layers"]["mlp"]["router_bias"]).min()) > 0

    def test_expanded_attention_block(self, cfg, params):
        a = jax.tree.map(lambda x: x[1], params["layers"]["attn"])
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 48, cfg.hidden))
        pos = jnp.arange(48)[None]
        with jax.default_matmul_precision("highest"):
            got, cache = L.attention_block(a, x, pos, cfg)
            want = REF.latent_attention(a, x[0], pos[0], CONF, 16, lambda v: v)
        assert cache is None
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=2e-6)

    def test_logits_loss_and_gradient(self, cfg, params):
        toks = np.random.default_rng(5).integers(3, 256, 65).astype(np.int32)
        with jax.default_matmul_precision("highest"):
            got, _, _ = decoder_forward(params, jnp.asarray(toks[None, :-1]),
                                        cfg)
            want = REF.logits(params, jnp.asarray(toks[:-1]), CONF)
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                       atol=2e-5)
            (loss, _), g = jax.value_and_grad(
                lambda p: decoder_loss(p, jnp.asarray(toks[None]), cfg),
                has_aux=True)(params)
            nll, g_ref = jax.value_and_grad(
                lambda p: REF.sequence_nll(p, jnp.asarray(toks), CONF))(params)
        assert float(loss) == pytest.approx(float(nll) / 64, rel=1e-5)
        paths = jax.tree_util.tree_flatten_with_path(g)[0]
        for (path, a), b in zip(paths, jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b) / 64, atol=3e-6,
                err_msg=jax.tree_util.keystr(path))
        # every kind of leaf is trained: both groups, the shared expert
        for leaf in (g["dense_layers"]["mlp"]["down"],
                     g["layers"]["mlp"]["shared"]["up"],
                     g["layers"]["attn"]["wkvb"], g["layers"]["mlp"]["gate"]):
            assert float(jnp.abs(leaf).max()) > 0

    def test_scan_and_list_of_blocks_agree(self, cfg, params):
        unrolled = dataclasses.replace(cfg, scan_layers=False)
        pu = {**params, **{
            name: [jax.tree.map(lambda a, i=i: a[i], params[name])
                   for i in range(g.n_layers)]
            for name, g, _ in layer_groups(cfg)}}
        toks = jnp.asarray(np.random.default_rng(2).integers(3, 256, (2, 12)))
        a, _, _ = decoder_forward(params, toks, cfg)
        b, _, _ = decoder_forward(pu, toks, unrolled)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


class TestRouter:
    def _p(self, scores, bias):
        e = len(scores)
        logits = np.log(np.asarray(scores) / (1 - np.asarray(scores)))
        return ({"router": jnp.eye(e, dtype=jnp.float32),
                 "router_bias": jnp.asarray(bias, jnp.float32)},
                jnp.asarray(logits[None], jnp.float32))

    def test_bias_changes_the_choice_and_not_the_weight(self):
        cfg = preset("tiny-glm", num_experts=4, hidden=4, router_scale=1.8)
        scores = [0.9, 0.8, 0.7, 0.1]
        p, x = self._p(scores, [0.0, 0.0, 0.5, 0.0])
        _, idx, w = L.route(p, x, cfg)
        assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]   # 1.2 and 0.9
        by = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(w[0])))
        assert by[2] == pytest.approx(1.8 * 0.7 / 1.6, rel=1e-5)
        assert by[0] == pytest.approx(1.8 * 0.9 / 1.6, rel=1e-5)
        p0, _ = self._p(scores, [0.0] * 4)
        _, idx0, w0 = L.route(p0, x, cfg)
        assert sorted(np.asarray(idx0[0]).tolist()) == [0, 1]
        assert float(w0.sum()) == pytest.approx(1.8, rel=1e-5)

    def test_weights_without_normalisation(self):
        cfg = preset("tiny-glm", num_experts=4, hidden=4, router_scale=1.0,
                     router_norm_topk=False)
        p, x = self._p([0.9, 0.8, 0.7, 0.1], [0.0] * 4)
        _, _, w = L.route(p, x, cfg)
        assert sorted(np.asarray(w[0]).tolist()) == pytest.approx([0.8, 0.9])

    def test_the_seeded_bias_changes_some_choices(self, cfg, params):
        mlp = jax.tree.map(lambda x: x[0], params["layers"]["mlp"])
        x = jax.random.normal(jax.random.PRNGKey(9), (256, cfg.hidden))
        _, with_b, _ = L.route(mlp, x, cfg)
        _, without, _ = L.route(
            {**mlp, "router_bias": jnp.zeros_like(mlp["router_bias"])}, x, cfg)
        changed = np.mean(np.sort(np.asarray(with_b)) !=
                          np.sort(np.asarray(without)))
        assert 0.0 < changed < 0.5

    def test_a_softmax_router_is_mixtrals(self):
        cfg = preset("tiny-moe", dtype="float32")
        p = {"router": jax.random.normal(jax.random.PRNGKey(0), (64, 4))}
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
        logits, idx, w = L.route(p, x, cfg)
        top, want = jax.lax.top_k(x @ p["router"], 2)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
        np.testing.assert_allclose(np.asarray(w),
                                   np.asarray(jax.nn.softmax(top, -1)),
                                   rtol=1e-5)


class TestNoTokenIsDropped:
    def test_every_token_to_one_expert(self, cfg, params):
        """Every token chooses experts 0 and 1: a capacity would overflow
        (the dispatch path at its default factor drops most rows); the
        sorted path computes them all and equals the dense oracle."""
        mlp = jax.tree.map(lambda x: x[0], params["layers"]["mlp"])
        mlp = {**mlp, "router_bias": jnp.asarray(
            [10.0, 5.0] + [0.0] * 6, jnp.float32)}
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.hidden))
        _, idx, _ = L.route(mlp, x[0], cfg)
        assert set(np.asarray(idx).ravel().tolist()) == {0, 1}
        out = {impl: L.moe_block(mlp, x, dataclasses.replace(
            cfg, moe_impl=impl))[0] for impl in ("sorted", "dense",
                                                 "dispatch")}
        np.testing.assert_allclose(np.asarray(out["sorted"]),
                                   np.asarray(out["dense"]), atol=2e-5)
        assert float(jnp.abs(out["dispatch"] - out["dense"]).max()) > 1e-2

    def test_the_shared_expert_is_added_to_every_token(self, cfg, params):
        mlp = jax.tree.map(lambda x: x[0], params["layers"]["mlp"])
        x = jax.random.normal(jax.random.PRNGKey(6), (1, 8, cfg.hidden))
        with_shared, _ = L.moe_block(mlp, x, cfg)
        routed, _ = L.moe_block(mlp, x, dataclasses.replace(
            cfg, shared_experts=0))
        np.testing.assert_allclose(
            np.asarray(with_shared - routed),
            np.asarray(L.mlp_block(mlp["shared"], x, cfg)), atol=1e-5)

    def test_the_grouped_matmul_kernel_is_ragged_dot(self, cfg, params):
        """Whole 128-row tiles go through the Pallas grouped matmul when the
        fused kernels are on (interpret mode here), with the layer's
        experts addressed inside the whole stack; fewer rows, or kernels
        off, through ``ragged_dot``: the same numbers and gradients."""
        on = dataclasses.replace(cfg, fused_kernels="on")
        off = dataclasses.replace(cfg, fused_kernels="off")
        rest, whole = L.split_expert_stack(params["layers"], cfg)
        assert set(whole) == set(L.EXPERT_LEAVES) and "gate" not in rest["mlp"]
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.hidden))
        layer = jax.tree.map(lambda a: a[2], params["layers"]["mlp"])
        want, _ = L.moe_block(layer, x, off)
        got, _ = L.moe_block(jax.tree.map(lambda a: a[2], rest["mlp"]), x, on,
                             expert_stack=(whole, jnp.int32(2)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        few, _ = L.moe_block(layer, x[:, :5], on)      # 10 rows: ragged_dot
        np.testing.assert_allclose(np.asarray(few), np.asarray(want[:, :5]),
                                   atol=2e-5)
        g_on = jax.grad(lambda m: L.moe_block(m, x, on)[0].sum())(layer)
        g_off = jax.grad(lambda m: L.moe_block(m, x, off)[0].sum())(layer)
        for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        assert L.split_expert_stack(params["dense_layers"], dataclasses.replace(
            cfg, num_experts=0)) == (params["dense_layers"], None)

    def test_sorted_inside_a_pipeline_stage_is_refused(self, cfg, params):
        mlp = jax.tree.map(lambda x: x[0], params["layers"]["mlp"])
        with pytest.raises(NotImplementedError, match="sorted"):
            L.moe_block(mlp, jnp.zeros((1, 4, cfg.hidden)), cfg,
                        expert_axis="expert")


class TestLatentPool:
    def test_the_pool_is_described_once(self, cfg):
        # one row a token a layer: 40 latent + 8 rotary values, padded with
        # zeros to whole 128-value lanes
        assert pool_planes(cfg) == (("ckv", (128,), jnp.dtype("float32")),)
        assert pool_bytes_per_token(cfg) == 4 * 128 * 4
        real = preset("glm-4.7-flash", n_layers=7, dtype="bfloat16")
        assert pool_planes(real) == (("ckv", (640,), jnp.dtype("bfloat16")),)
        assert pool_bytes_per_token(real) == 7 * 1280     # 1152 of content
        kv = preset("tiny")
        assert [p[0] for p in pool_planes(kv)] == ["k", "v"]
        assert [p[0] for p in pool_planes(kv, True)] == ["k", "v", "ks", "vs"]
        assert pool_bytes_per_token(kv) == 2 * 2 * 2 * 16 * 2
        with pytest.raises(ValueError, match="int8 KV over a latent"):
            pool_planes(cfg, True)

    def test_engine_builds_the_pool_and_counts_it(self, cfg, params):
        eng = make_engine(cfg, params, max_pages=12)
        assert {n: a.shape for n, a in eng.cache.items()} == {
            "ckv": (4, 12, 16, 128)}
        c = eng.counters()
        assert c["kv_bytes_per_token"] == 4 * 128 * 4
        assert c["kv_pool_bytes"] == 12 * 16 * c["kv_bytes_per_token"]
        assert c["decode_context_tokens"] == 0
        assert eng.kv_pool_density()["pool_bytes"] == c["kv_pool_bytes"]

    def test_copy_pages_walks_every_plane(self, cfg):
        rng = np.random.default_rng(0)
        cache = {n: jnp.asarray(rng.normal(size=(4, 6, 16, *t)), jnp.float32)
                 for n, t, _ in pool_planes(cfg)}
        out = copy_pages(cache, jnp.asarray([1, 2]), jnp.asarray([4, -1]))
        for n in cache:
            np.testing.assert_array_equal(np.asarray(out[n][:, 4]),
                                          np.asarray(cache[n][:, 1]))
            np.testing.assert_array_equal(np.asarray(out[n][:, :4]),
                                          np.asarray(cache[n][:, :4]))

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_chunked_prefill_then_absorbed_decode_is_one_full_forward(
            self, cfg, params, impl):
        """The benchmark's own drive of the engine's programs: three chunks
        into the latent pool, then teacher-forced decode steps; logits of
        the last chunk and of every step against ONE full forward."""
        from benchmark import correctness

        eng = make_engine(cfg, params, paged_attn_impl=impl)
        toks = correctness.check_tokens(3, 0, 90 + 6, 256)
        got, real = correctness.engine_logits(eng, toks, 90, 6)
        assert real == 90 - 64
        with jax.default_matmul_precision("highest"):
            want, _, _ = decoder_forward(params, jnp.asarray(toks[None]), cfg)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want[0, 64:96]), atol=3e-5)

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_served_tokens_are_the_full_forwards(self, cfg, params, impl):
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        assert greedy(eng, prompt, 8) == full_forward_greedy(
            params, cfg, prompt, 8)

    def test_the_context_counter_counts_the_rows_a_step_attends_to(
            self, cfg, params):
        eng = make_engine(cfg, params, decode_steps=1, pipelined_decode=False)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        greedy(eng, prompt, 8)
        c = eng.counters()
        # seven steps after the first token (the prefill's last logits
        # gave that one), attending to rows 0..50, 0..51, ...
        assert c["decode_steps_dispatched"] == 7
        assert c["decode_context_tokens"] == sum(range(51, 58))

    def test_the_decode_step_holds_no_per_head_key_or_value_of_the_context(
            self, cfg, params):
        """No intermediate of the step has both the context's length and a
        per-head key or value width: the context is only ever touched as
        latent rows (the absorbed form)."""
        slots, mpp, pg = 2, 8, 16
        cache = {n: jnp.zeros((4, 16, pg, *t), dt)
                 for n, t, dt in pool_planes(cfg)}
        cache["table"] = jnp.zeros((slots, mpp), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda c, t, ln, lv: _paged_decode_step(
            params, c, t, ln, lv, cfg, attn_impl="gather"))(
                cache, jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool))
        ctx = mpp * pg
        per_head = {cfg.qk_nope_dim, cfg.v_head_dim,
                    cfg.qk_nope_dim + cfg.v_head_dim,
                    cfg.qk_nope_dim + cfg.qk_rope_dim}
        shapes = set()

        def walk(j):
            for eqn in j.eqns:
                for v in eqn.outvars:
                    shapes.add(tuple(v.aval.shape))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert any(ctx in s for s in shapes)          # the scores are there
        bad = [s for s in shapes if ctx in s and cfg.n_heads in s
               and s[-1] in per_head]
        assert not bad, bad


class TestLatentKernels:
    """Both kernels (interpret mode) against the kernel-free sums over the
    gathered rows, at widths where a row is 40 + 8 values padded to 128."""

    H, R, ROPE, W, PG, PAGES = 4, 40, 8, 128, 16, 12

    def _pool(self, dtype, pages=PAGES):
        rng = np.random.default_rng(0)
        rows = rng.normal(size=(pages, self.PG, self.W))
        rows[..., self.R + self.ROPE:] = 0.0
        return jnp.asarray(rows, dtype)

    def _queries(self, shape, dtype):
        q = np.random.default_rng(1).normal(size=(*shape, self.W))
        q[..., self.R + self.ROPE:] = 0.0
        return jnp.asarray(q, dtype)

    @staticmethod
    def _attend(q, rows, mask, scale):
        s = jnp.einsum("...hw,tw->...ht", q, rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("...ht,tw->...hw", p, rows.astype(jnp.float32))

    # row 0 three pages; row 1 one partial page; row 2 has an unmapped page
    # inside its range, which the kernel skips; beside them the walk's own
    # cases (tests/test_serve_paged.py: a context that ends on a page's first
    # and last token, a whole and a short last turn, length 0, dead rows
    # between live ones, holes)
    ROWS = {"three_rows": [([3, 7, 1], 40, None), ([9], 5, None),
                           ([2, -1, 5], 37, None)], **_ROW_WALK}

    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                            (jnp.bfloat16, 0.06)])
    @pytest.mark.parametrize("case", sorted(ROWS))
    def test_decode_kernel_matches_the_gather_form(self, case, dtype, atol):
        """Every page no context holds is POISONED (NaN): a walk that copied
        one, or attended to a buffer it did not fill, shows it (a latent
        page's rows are keys AND values: ``0 x NaN`` is NaN)."""
        from kubeflow_tpu.ops import paged_attention as pa

        rows = self.ROWS[case]
        mpp = 4 if case == "three_rows" else _WALK_MPP
        assert pa._pages_a_turn(
            self.PG * self.W * jnp.dtype(dtype).itemsize, mpp, 1) \
            == min(8, mpp)
        table, lengths, _ = _walk_rows(rows, mpp)
        pool = self._pool(dtype, 36)
        q = self._queries((len(rows), self.H), dtype)
        idle = _idle_pages(rows, self.PG, 36)[:, None, None]
        table, lengths = jnp.asarray(table), jnp.asarray(lengths)
        out = pa.paged_latent_decode_attention(
            q, jnp.where(idle, jnp.nan, pool), table, lengths, sm_scale=0.2,
            interpret=True)
        assert out.dtype == dtype and out.shape == q.shape
        gathered = paged_gather(pool, table)               # [B, S, W]
        pos = jnp.arange(gathered.shape[1])[None, :]
        mask = (pos <= lengths[:, None]) & jnp.repeat(table >= 0, self.PG, 1)
        for b in range(len(rows)):
            want = self._attend(q[b], gathered[b], mask[b][None], 0.2)
            if not bool(mask[b].any()):     # a row that attends to no page
                want = jnp.zeros_like(want)
            np.testing.assert_allclose(np.asarray(out[b], np.float32),
                                       np.asarray(want), atol=atol)

    @pytest.mark.parametrize("start,pages", [(0, 4), (37, 7), (64, 9)])
    def test_chunk_kernel_matches_the_gather_form(self, start, pages):
        """A chunk of 32 queries from ``start`` over its slot's pages (the
        table longer than the context: blocks behind the chunk are skipped,
        a table that is no multiple of the step's pages is padded)."""
        from kubeflow_tpu.ops.paged_attention import (
            paged_latent_chunk_attention,
        )

        pool = self._pool(jnp.float32)
        q = self._queries((self.H, 32), jnp.float32)
        order = np.random.default_rng(2).permutation(self.PAGES)[:pages]
        need = -(-(start + 32) // self.PG)
        table = np.where(np.arange(pages) < need, order, -1).astype(np.int32)
        out = paged_latent_chunk_attention(
            q, pool, jnp.asarray(table), jnp.int32(start), sm_scale=0.2,
            interpret=True)
        rows = paged_gather(pool, jnp.asarray(table)[None])[0]   # [T, W]
        q_pos = start + jnp.arange(32)
        mask = jnp.arange(rows.shape[0])[None, :] <= q_pos[:, None]
        want = self._attend(jnp.swapaxes(q, 0, 1), rows, mask[:, None], 0.2)
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(out, 0, 1)),
                                   np.asarray(want), atol=2e-5)


class TestPrefixIndexAndPreemption:
    def test_a_prefix_hit_equals_the_cold_result(self, cfg, params):
        rng = np.random.default_rng(4)
        shared = rng.integers(3, 256, 48).tolist()
        a = shared + rng.integers(3, 256, 9).tolist()
        b = shared + rng.integers(3, 256, 13).tolist()
        eng = make_engine(cfg, params)
        first = greedy(eng, a, 6)
        before = eng.kv_tier_stats()
        second = greedy(eng, b, 6)
        after = eng.kv_tier_stats()                      # b reused a's pages
        assert after["prefix_hits"] == before["prefix_hits"] + 1
        assert after["tokens_matched"] >= before["tokens_matched"] + 48
        assert first == full_forward_greedy(params, cfg, a, 6)
        assert second == full_forward_greedy(params, cfg, b, 6)
        cold = make_engine(cfg, params, enable_prefix_caching=False)
        assert greedy(cold, b, 6) == second
        # a copy-on-write inside a shared page: a prompt that diverges
        # mid-page resumes from a private copy of the partial page
        c = a[:40] + rng.integers(3, 256, 11).tolist()
        assert greedy(eng, c, 6) == full_forward_greedy(params, cfg, c, 6)
        assert eng.kv_tier_stats()["cow_copies"] > after["cow_copies"]

    def test_preempt_and_recompute_equals_the_cold_result(self, cfg, params):
        rng = np.random.default_rng(8)
        prompts = [rng.integers(3, 256, n).tolist() for n in (40, 44, 36)]
        want = [full_forward_greedy(params, cfg, p, 24) for p in prompts]
        # 9 pages of 16: three requests growing to 60-68 tokens need 12-15
        eng = make_engine(cfg, params, max_pages=9, max_seq_len=128,
                          enable_prefix_caching=False)
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=24,
                                             temperature=0.0))
                for p in prompts]
        run_all(eng, reqs, max_steps=3000)
        assert eng.metrics.preemptions > 0
        assert [r.result() for r in reqs] == want
        assert eng.kv_pages_in_use() == 0


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
    def test_each_mechanism_refuses_the_model_by_name(self, cfg, params, kw,
                                                      names):
        with pytest.raises(ValueError, match="latent") as err:
            make_engine(cfg, params, **kw)
        assert names in str(err.value)

    def test_handoff_at_the_door(self, cfg, params):
        eng = make_engine(cfg, params)
        with pytest.raises(ValueError, match="latent"):
            eng.submit([5, 6, 7], handoff=True)

    def test_leading_dense_layers_without_a_latent_cache(self):
        """Layers of more than one kind over per-head K and V serve through
        the same pool; what walks ONE stack of layers refuses them."""
        cfg = preset("tiny-moe", n_layers=3, leading_dense_layers=1,
                     dtype="float32", param_dtype="float32")
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        assert set(params) >= {"dense_layers", "layers"}
        eng = make_engine(cfg, params)
        prompt = list(range(5, 45))
        assert greedy(eng, prompt, 5) == full_forward_greedy(
            params, cfg, prompt, 5)
        with pytest.raises(ValueError, match="leading dense layers"):
            make_engine(cfg, params, speculative={"mode": "ngram", "k": 2})

    def test_pipeline_parallel_refuses_two_kinds_of_layer(self, cfg):
        with pytest.raises(ValueError, match="leading_dense_layers"):
            layer_groups(dataclasses.replace(cfg, num_experts=0))
