"""LongCat-Flash's block through the page pool (the ``tiny-longcat-flash``
preset), on the CPU: the engine's chunked prefill and its decode step
("gather", and "pallas" with the latent kernels interpreted) against the
benchmark's plain reference's ONE full forward, logits compared: the one-row
program, the two-row program with a prompt's rows ahead, and the mixed
program with decode rows beside a chunk; the cached row holding the SCALED
latent; served tokens against the full recompute with prefix reuse by page
and preemption; the three row counts in ``counters()`` against a count by
hand and on the dispatch spans; the pool's running sums an entry longer; and
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
from kubeflow_tpu.models.decoder import decoder_forward
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    MOE_ROWS, engine_pool_shapes, mixed_step_rows,
    paged_chunk_prefill, pool_bytes_per_token,
)
from test_serve_chunk_rows import record_spans

CONF = mf.load_json("benchmark/configs/rehearsal-tiny-longcat.json")
REF = architecture.part(CONF, "reference")


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny-longcat-flash", dtype="float32",
                  param_dtype="float32")


@pytest.fixture(scope="module")
def params():
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


def routed_by_hand(params, cfg, tokens) -> np.ndarray:
    """[routed, held, zero] over both expert layers for ``tokens`` [S] in one
    forward pass, from the reference's own routing."""
    c, counts = CONF, np.zeros(3, np.int64)
    from benchmark.reference import F32, rmsnorm

    s = len(tokens)
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    stack = params["layers"]
    for layer in range(c["num_layers"]):
        pair = {k: jax.tree.map(lambda w: w[2 * layer:2 * layer + 2], v)
                for k, v in stack.items() if k != "moe"}
        pair["moe"] = jax.tree.map(lambda w: w[layer], stack["moe"])
        first = jax.tree.map(lambda w: w[0],
                             {k: v for k, v in pair.items() if k != "moe"})
        a = x + REF.latent_attention(
            first["attn"], rmsnorm(x, first["ln1"], 1e-5), positions, c, s,
            lambda v: v)
        h = rmsnorm(a, first["ln2"], 1e-5)
        weight = np.asarray(REF.routing(pair["moe"], h, c, lambda v: v))
        chosen = weight > 0
        counts += [chosen.sum(), chosen[:, :4].sum(), chosen[:, 16:].sum()]
        x = REF.published_layer(pair, x, positions, c, s, lambda v: v)
    return counts


# -- the programs against the reference's one full forward ----------------------------

class TestAgainstTheReference:
    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_chunked_prefill_then_decode_is_the_references_full_forward(
            self, cfg, params, impl):
        """The benchmark's own drive of the engine's programs: three chunks
        of 32 through the one-row program into the pool's four attention
        layers, then teacher-forced decode steps; logits of the last chunk
        and of every step against the reference's ONE full forward."""
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        toks = correctness.check_tokens(3, 0, 90 + 6, 256)
        got, real = correctness.engine_logits(eng, toks, 90, 6)
        assert real == 90 - 64
        want = correctness.reference_logits(params, toks, CONF,
                                            last=real + 6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_two_rows_with_a_row_ahead_are_the_references(self, cfg, params,
                                                          impl):
        """The program over rows with a prompt's NEXT chunk in its second
        row (the engine's rows ahead): the row behind finds, in each of the
        four attention layers, the latent rows the row in front wrote in
        the same layer, and the pair's pending expert result is a row's own.
        Each row's last position against the reference's full forward."""
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        toks = correctness.check_tokens(9, 0, 64, 256)
        row = np.full((eng._mpp,), -1, np.int32)
        row[:4] = np.arange(4)
        logits, cache = paged_chunk_prefill(
            params, eng.cache, jnp.asarray(toks.reshape(2, 32)),
            jnp.asarray(np.stack([row, row])), jnp.asarray([0, 32]),
            jnp.asarray([32, 32]), cfg, context_pages=eng._mpp,
            paged_attn_impl=eng.paged_attn_impl, logits_at="last",
            wanted=jnp.asarray([True, True]))
        want = correctness.reference_logits(params, toks, CONF, last=33)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[0]), atol=5e-5)
        np.testing.assert_allclose(np.asarray(logits[1]),
                                   np.asarray(want[-1]), atol=5e-5)

    def test_the_mixed_program_carries_decode_rows_beside_a_chunk(
            self, cfg, params):
        """Where the kernels are on, a chunk program carries the slots'
        step (``paged_mixed_step``): the dense MLP and the experts run over
        the chunk's and the decode rows' tokens together, and what each
        group started joins its own stream a block later."""
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

    def test_a_prompt_alone_goes_two_chunks_a_program(self, cfg, params):
        kw = dict(paged_attn_impl="pallas", max_seq_len=256, decode_steps=1,
                  prefill_interleave_steps=1, enable_prefix_caching=False,
                  pipelined_decode=False)
        eng = make_engine(cfg, params, **kw)
        assert eng._plan.ahead and eng._plan.rows_only and eng._plan.rows == 2
        prompt = np.random.default_rng(5).integers(3, 256, 137).tolist()
        got = greedy(eng, prompt, 6)
        assert got == full_forward_greedy(params, cfg, prompt, 6)
        c = eng.counters()
        assert [c[f"prefill_{n}"] for n in (
            "programs_dispatched", "chunks_dispatched", "rows_ahead",
            "rows_dead")] == [3, 5, 2, 1]

    @pytest.mark.parametrize("impl", ["gather", "pallas"])
    def test_served_tokens_are_the_full_forwards(self, cfg, params, impl):
        eng = make_engine(cfg, params, paged_attn_impl=impl)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        assert greedy(eng, prompt, 8) == full_forward_greedy(
            params, cfg, prompt, 8)

    def test_mixed_rows_are_whole_tiles_at_the_cells_sizes(self):
        big = preset("longcat-flash-omni")
        assert mixed_step_rows(big, 1024, 48) == 64
        assert (1024 + 64) * 12 % L.GROUPED_TILE_ROWS == 0


# -- the pool ---------------------------------------------------------------------------

class TestThePool:
    def test_a_token_holds_a_row_in_each_of_a_layers_two_attentions(
            self, cfg, params):
        assert pool_bytes_per_token(cfg) == 4 * 128 * 4      # float32 here
        eng = make_engine(cfg, params)
        shapes = engine_pool_shapes(eng._cfg_decode, 4, eng._num_pages, 16)
        assert shapes["ckv"][0] == (4, eng._num_pages, 16, 128)
        assert shapes[MOE_ROWS][0] == (3,)            # routed, held, zero
        assert eng.cache[MOE_ROWS].shape == (3,)
        glm = preset("tiny-glm-5")
        assert engine_pool_shapes(glm, 4, 8, 16)[MOE_ROWS][0] == (2,)

    def test_the_cached_row_holds_the_scaled_latent(self, cfg, params):
        eng = make_engine(cfg, params)
        toks = correctness.check_tokens(4, 0, 20 + 1, 256)
        correctness.engine_logits(eng, toks, 20, 1)
        a = jax.tree.map(lambda w: w[0], params["layers"]["attn"])
        x = params["embed"][jnp.asarray(toks[:20])][None]
        h = L.rmsnorm(x, params["layers"]["ln1"][0], cfg)
        *_, row, _ = L.latent_qkv(a, h, jnp.arange(20)[None], cfg)
        plain = dataclasses.replace(cfg, latent_rank_scale=False)
        *_, row0, _ = L.latent_qkv(a, h, jnp.arange(20)[None], plain)
        held = np.asarray(eng.cache["ckv"][0, 0, :16])
        np.testing.assert_allclose(held, np.asarray(row[0, :16]), atol=1e-5)
        np.testing.assert_allclose(
            held[:, :40], (64 / 40) ** 0.5 * np.asarray(row0[0, :16, :40]),
            rtol=1e-4, atol=1e-5)

    def test_prefix_reuse_by_page_is_taken(self, cfg, params):
        """A latent pool's pages are shared read-only by page id and an
        expert layer keeps nothing: a matched page's rows are what the
        second prompt would have written."""
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


# -- counters and spans -----------------------------------------------------------------

class TestCountersAndSpans:
    def test_the_three_row_counts_are_a_count_by_hand(self, cfg, params,
                                                      monkeypatch):
        """One prompt of 50 (two chunks) and seven decode steps behind its
        first token: every token passes both expert layers once, so the
        window's rows are the reference's own routing of the 57 positions,
        summed."""
        eng = make_engine(cfg, params, decode_steps=1, max_batch_size=1,
                          pipelined_decode=False,
                          enable_prefix_caching=False)
        assert [eng.counters()[f"expert_rows_{n}"]
                for n in ("routed", "held", "zero")] == [0, 0, 0]
        # (the first fetch reads what the constructor's warm-up runs routed)
        greedy(eng, list(range(3, 23)), 3)
        before = eng.counters()
        spans = record_spans(monkeypatch)
        prompt = np.random.default_rng(1).integers(3, 256, 50).tolist()
        out = greedy(eng, prompt, 8)
        after = eng.counters()
        c = {n: after[f"expert_rows_{n}"] - before[f"expert_rows_{n}"]
             for n in ("routed", "held", "zero")}
        # the last sampled token is never fed back
        routed, held, zero = routed_by_hand(params, cfg, prompt + out[:-1])
        pad = 64 - 50                 # the last chunk's pad rows route too
        assert c["routed"] == routed + 2 * 4 * pad == 2 * 4 * (57 + pad)
        assert 0 <= c["held"] - held <= 2 * 4 * pad
        assert 0 <= c["zero"] - zero <= 2 * 4 * pad
        assert 0 < c["held"] and 0 < c["zero"]
        assert c["held"] + c["zero"] < c["routed"]
        rounds = [a for n, a in spans if n == "engine.decode_dispatch"]
        chunks = [a for n, a in spans if n == "engine.prefill_dispatch"]
        for a in rounds + chunks:
            assert {"rows_routed", "rows_held", "rows_zero"} <= set(a)
        # a dispatch says what the LAST FETCH read: the rows of the programs
        # between the two fetches in front of it (a step of one slot routes
        # its token's four choices in both expert layers)
        assert [a["rows_routed"] for a in rounds[-3:]] == [2 * 4] * 3
        assert all(a["rows_held"] + a["rows_zero"] <= a["rows_routed"]
                   for a in rounds + chunks)
        assert [a["context"] for a in chunks] == [
            32 * 33 // 2, 18 * 32 + 18 * 19 // 2]

    def test_a_model_that_holds_every_expert_says_nothing_of_rows(
            self, monkeypatch):
        spans = record_spans(monkeypatch)
        cfg = preset("tiny-moe", dtype="float32", param_dtype="float32")
        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, paged=True, page_size=16,
            chunked_prefill_tokens=16))
        greedy(eng, list(range(3, 23)), 3)
        c = eng.counters()
        assert (c["expert_rows_routed"], c["expert_rows_held"],
                c["expert_rows_zero"]) == (0, 0, 0)
        assert spans and not any("rows_zero" in a for _, a in spans)


# -- what refuses the model by name ---------------------------------------------------------

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
        (dict(moe_decode_impl="zero_drop"), "zero experts are the sorted"),
        (dict(moe_prefill_impl="dispatch"), "zero experts are the sorted"),
    ])
    def test_each_mechanism_refuses_the_block_by_name(self, cfg, params, kw,
                                                      names):
        with pytest.raises(ValueError, match="an expert layer on a shortcut "
                                             "beside the dense MLPs") as err:
            make_engine(cfg, params, **kw)
        assert names in str(err.value)
        assert "8 zero experts" in str(err.value)

    def test_a_mesh_is_refused_by_name(self, cfg, params):
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("model",))
        with pytest.raises(ValueError, match="shortcut") as err:
            LLMEngine(cfg, BatchingSpec(
                max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
                chunked_prefill_tokens=32), params=params, mesh=mesh)
        assert "a mesh (tensor-parallel serving)" in str(err.value)

    def test_a_contiguous_cache_is_refused_as_for_every_latent_model(
            self, cfg, params):
        bp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
        with pytest.raises(NotImplementedError, match="page pool"):
            L.latent_attention_block(
                bp, jnp.zeros((1, 4, 64)), jnp.arange(4)[None], cfg,
                kv_cache={"len": 0})
