"""The chunks of several in-flight prefills in ONE program (ISSUE 29), on the
CPU: the program over rows (a, b) against the one-row program on a and on b
in turn (pool rows and logits), a row's independence of its neighbour, the
per-row capacity of the dispatch expert layer, and the engine's side: when
the program is built, that it is warm from construction on, what a pass
dispatches and counts, and that a stalled prefill holds nobody back. And the
chunk program of a per-head pool as the chip runs it (ISSUE 36): rows written
in place and attended through ``paged_chunk_attention`` (interpreted here),
against the gathered form, and the kernel alone against plain attention. And
the program's last-position form (ISSUE 41): ``[B,V]`` logits, the head at each
row's last valid position, against the all-position form's row there, over
every kind of pool; the engine's program over rows takes it. And the engine
that sends ONE chunk a program (ISSUE 52): a lone chunk through the program
over rows at one row, the head at one position or under the untaken branch of
a ``cond``, the ``[C,V]`` program left to callers outside the engine."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.device import CompileCounter
from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.obs import profiler
from kubeflow_tpu.serve.chunk_programs import (
    RIDGE_ROWS, chunk_rows_per_weight,
)
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.lora import AdapterSpec, init_adapter_weights
from kubeflow_tpu.ops.attention import multi_head_attention
from kubeflow_tpu.ops.paged_attention import (
    CHUNK_PAGES_PER_STEP, CHUNK_QUERY_TILE, chunk_attention_supported,
    paged_chunk_attention,
)
from kubeflow_tpu.serve.paged import (
    _chunk_in_place, context_bucket, engine_pool_shapes, paged_chunk_prefill,
    pool_shapes, ring_pages,
)

PAGE, CHUNK, MPP, POOL = 16, 32, 8, 14
VOCAB = 256


def _config(kind: str):
    if kind == "dense":
        return preset("tiny", dtype="float32", param_dtype="float32",
                      max_seq_len=1024)
    if kind == "dispatch":
        # Mixtral-like: capacity buffers at the published factor.
        return preset("tiny-moe", dtype="float32", param_dtype="float32",
                      capacity_factor=1.25, max_seq_len=1024)
    if kind == "patterned":
        # LFM2-like: conv layers beside attention, state in the pool
        return preset("tiny-lfm2", dtype="float32", param_dtype="float32",
                      max_seq_len=1024)
    if kind == "parallel":
        return _parallel_config()
    return preset("tiny-glm", dtype="float32", param_dtype="float32",
                  max_seq_len=1024)


KINDS = ("dense", "dispatch", "latent", "patterned")
EXPERT_KINDS = KINDS[1:]


@functools.lru_cache(maxsize=None)
def _model(kind: str):
    cfg = _config(kind)
    return kind, cfg, init_decoder_params(jax.random.PRNGKey(3), cfg)


@pytest.fixture(scope="module", params=KINDS)
def model(request):
    return _model(request.param)


def _tokens(seed: int, n: int) -> np.ndarray:
    """Prompt tokens from FEW ids, so that a chunk's tokens crowd the same
    experts and a capacity of 1.25 x the even share overflows."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, VOCAB, 3)
    return ids[rng.choice(3, n, p=[0.8, 0.1, 0.1])].astype(np.int32)


def _empty_pool(cfg):
    shapes = (engine_pool_shapes(cfg, 3, POOL, PAGE)
              if cfg.layers_of("window") else pool_shapes(cfg, POOL, PAGE))
    return {name: jnp.zeros(shape, dt) for name, (shape, dt) in shapes.items()}


def _rows_program(cfg, impl="gather", logits_at="all"):
    """``program(..., ncp[, wanted])``: ``wanted`` with "last" only."""
    return jax.jit(
        lambda p, c, t, tr, st, vl, ncp, wanted=None: paged_chunk_prefill(
            p, c, t, tr, st, vl, cfg, context_pages=ncp,
            paged_attn_impl=impl, logits_at=logits_at, wanted=wanted),
        static_argnums=(6,))


def _one(program, params, cache, tokens, table_row, start, valid):
    """The one-row program on one prompt's chunk: ([C,V], cache)."""
    block = np.zeros((1, CHUNK), np.int32)
    block[0, :valid] = tokens[start:start + valid]
    logits, cache = program(
        params, cache, jnp.asarray(block), jnp.asarray(table_row[None]),
        jnp.asarray([start], jnp.int32), jnp.asarray([valid], jnp.int32),
        context_bucket(start, CHUNK, PAGE, MPP))
    return logits[0], cache


def _two(program, params, cache, rows, ctx=None, wanted=None):
    """The two-row program; a row is (tokens, table_row, start, valid) or
    None for a dead one. ``ctx``: the static context bucket (the largest
    live row's unless given). ``wanted``: a bool a row, where given."""
    block = np.zeros((2, CHUNK), np.int32)
    table = np.full((2, MPP), -1, np.int32)
    start, valid = np.zeros((2,), np.int32), np.zeros((2,), np.int32)
    for r, row in enumerate(rows):
        if row is None:
            continue
        toks, table[r], start[r], valid[r] = row
        block[r, :valid[r]] = toks[start[r]:start[r] + valid[r]]
    ctx = ctx or max(context_bucket(int(start[r]), CHUNK, PAGE, MPP)
                     for r, row in enumerate(rows) if row is not None)
    more = () if wanted is None else (jnp.asarray(wanted),)
    return program(params, cache, jnp.asarray(block), jnp.asarray(table),
                   jnp.asarray(start), jnp.asarray(valid), ctx, *more)


@functools.lru_cache(maxsize=None)
def _chunks(kind: str):
    """Prompts a (24 tokens prefilled: its next chunk starts MID-PAGE, whole)
    and b (64 prefilled, its next chunk 19 tokens long, another context
    bucket), their tables, and the pool as those prefixes left it."""
    _, cfg, params = _model(kind)
    program = _rows_program(cfg)
    a, b = _tokens(1, 24 + CHUNK), _tokens(2, 64 + 19)
    row_a = np.asarray([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
    row_b = np.asarray([4, 5, 6, 7, 8, 9, -1, -1], np.int32)
    cache = _empty_pool(cfg)
    _, cache = _one(program, params, cache, a, row_a, 0, 24)
    _, cache = _one(program, params, cache, b, row_b, 0, CHUNK)
    _, cache = _one(program, params, cache, b, row_b, CHUNK, CHUNK)
    return program, cache, (a, row_a, 24, CHUNK), (b, row_b, 64, 19)


@pytest.fixture(scope="module")
def chunks(model):
    return _chunks(model[0])


class TestRowsProgram:
    def test_rows_equal_the_one_row_program_in_turn(self, model, chunks):
        _, _, params = model
        program, cache, ra, rb = chunks
        la, after = _one(program, params, cache, *ra)
        lb, after = _one(program, params, after, *rb)
        both, got = _two(program, params, cache, (ra, rb))
        assert both.shape == (2, CHUNK, VOCAB)
        np.testing.assert_allclose(both[0], la, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(both[1, :19], lb[:19], rtol=2e-5,
                                   atol=2e-5)
        for name in after:
            np.testing.assert_allclose(got[name], after[name], rtol=2e-5,
                                       atol=2e-5, err_msg=name)
        # ... and they wrote: a's 32 rows from position 24 on (pages 1-3),
        # b's 19 from 64 on (pages 8-9), nothing behind b's valid length.
        rows = next(n for n in got if n != "conv")      # a plane a token
        plane = np.asarray(got[rows])
        before = np.asarray(cache[rows])
        assert np.any(plane[:, 1, 8:] != before[:, 1, 8:])
        assert np.any(plane[:, 8] != before[:, 8])
        np.testing.assert_array_equal(plane[:, 9, 3:], before[:, 9, 3:])
        np.testing.assert_array_equal(plane[:, 10:], before[:, 10:])

    def test_a_row_does_not_depend_on_its_neighbour(self, model, chunks):
        """Row a beside b, beside another prompt in b's pages, beside a dead
        row, in one program (one context bucket serves all rows): the same
        logits and the same pool rows, to the bit."""
        _, _, params = model
        program, cache, ra, rb = chunks
        other = (_tokens(9, 64 + CHUNK), rb[1], 64, CHUNK)
        want, want_pool = _two(program, params, cache, (ra, rb))
        for neighbour in (other, None):
            got, pool = _two(program, params, cache, (ra, neighbour), ctx=8)
            np.testing.assert_array_equal(got[0], want[0])
            for name in pool:
                np.testing.assert_array_equal(pool[name][:, :4],
                                              want_pool[name][:, :4])
        # ... in either position, and a dead row writes nothing.
        got, pool = _two(program, params, cache, (None, ra), ctx=8)
        np.testing.assert_array_equal(got[1], want[0])
        for name in pool:
            np.testing.assert_array_equal(pool[name][:, 4:],
                                          cache[name][:, 4:])

    def test_the_traffic_overflows_the_dispatch_capacity(self):
        """The comparisons above have teeth for the capacity path only if
        (token, choice) pairs really drop at 1.25: the same chunk with
        nothing able to drop gives other logits."""
        _, cfg, params = _model("dispatch")
        program, cache, ra, _ = _chunks("dispatch")
        ample = _rows_program(dataclasses.replace(
            cfg, capacity_factor=float(cfg.num_experts)))
        tight, _ = _one(program, params, cache, *ra)
        loose, _ = _one(ample, params, cache, *ra)
        assert float(jnp.max(jnp.abs(tight - loose))) > 1e-3


# -- the chunk of a per-head pool, in place ---------------------------------------

# (heads, KV heads, chunk, start, tokens the slot holds, table length, type)
KERNEL_CASES = {
    "start-0-inside-one-page": (8, 2, 16, 0, 16, 4, jnp.float32),
    "page-aligned-one-whole-step": (8, 2, 32, 32, 64, 4, jnp.float32),
    "mid-page-across-two-steps": (8, 2, 32, 40, 72, 12, jnp.float32),
    "long-context-skipped-pages-unmapped": (8, 2, 32, 200, 232, 20,
                                            jnp.float32),
    "group-of-one": (2, 2, 32, 40, 72, 7, jnp.float32),
    "bfloat16-two-heads-a-word": (8, 4, 32, 40, 72, 12, jnp.bfloat16),
    "short-valid-length": (8, 2, 32, 24, 24 + 19, 8, jnp.float32),
    "two-query-tiles": (8, 2, 2 * CHUNK_QUERY_TILE, 40,
                        40 + 2 * CHUNK_QUERY_TILE, 24, jnp.float32),
    "dead-row": (8, 2, 32, 0, 0, 8, jnp.float32),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_chunk_kernel_matches_plain_attention(case):
    """``paged_chunk_attention`` (interpreted) against
    ``multi_head_attention(impl="xla")`` over the slot's gathered rows in
    float32: the rows the slot holds are mapped in a shuffled order, the
    table's tail is unmapped, and only queries at positions the slot holds
    are compared (a padded query's output is discarded). A dead row (an
    unmapped table) attends to nothing and emits zeros."""
    h, kv, c, start, held, mpp, dt = KERNEL_CASES[case]
    assert chunk_attention_supported(kv, 128, dt)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    pool_k = jax.random.normal(keys[0], (40, PAGE, kv, 128), dt)
    pool_v = jax.random.normal(keys[1], (40, PAGE, kv, 128), dt)
    q = jax.random.normal(keys[2], (h, c, 128), dt)
    pages = -(-held // PAGE)
    assert held <= start + c and pages <= mpp
    if case != "dead-row":
        assert mpp > CHUNK_PAGES_PER_STEP or pages <= CHUNK_PAGES_PER_STEP
    table = np.full((mpp,), -1, np.int32)
    table[:pages] = np.random.default_rng(len(case)).permutation(40)[:pages]
    out = paged_chunk_attention(q, pool_k, pool_v, jnp.asarray(table),
                                jnp.int32(start), interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    valid = held - start
    if valid <= 0:
        np.testing.assert_array_equal(np.asarray(out, np.float32), 0.0)
        return
    rows = [pool[jnp.clip(jnp.asarray(table), 0)].reshape(
        1, -1, kv, 128).astype(jnp.float32) for pool in (pool_k, pool_v)]
    want = multi_head_attention(
        jnp.swapaxes(q, 0, 1)[None].astype(jnp.float32), *rows, causal=True,
        q_offset=start, impl="xla")[0]
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(out, 0, 1), np.float32)[:valid],
        np.asarray(want)[:valid], rtol=tol, atol=tol)


def test_planes_the_chunk_kernel_does_not_take():
    # int8 pools, heads that are not one 128-value row, a lone bf16 head
    assert not chunk_attention_supported(8, 128, jnp.int8)
    assert not chunk_attention_supported(8, 64, jnp.bfloat16)
    assert not chunk_attention_supported(1, 128, jnp.bfloat16)
    assert chunk_attention_supported(1, 128, jnp.float32)
    with pytest.raises(ValueError, match="paged_chunk_attention"):
        paged_chunk_attention(
            jnp.zeros((4, 16, 64)), jnp.zeros((4, PAGE, 2, 64)),
            jnp.zeros((4, PAGE, 2, 64)), jnp.zeros((4,), jnp.int32),
            jnp.int32(0), interpret=True)


IN_PLACE_KINDS = ("dense", "dispatch", "dense-bfloat16")


@functools.lru_cache(maxsize=None)
def _wide_model(kind: str):
    """The tiny presets with heads of 128, which the kernel takes."""
    dt = "bfloat16" if kind.endswith("bfloat16") else "float32"
    over = dict(head_dim=128, dtype=dt, param_dtype=dt, max_seq_len=1024)
    cfg = (preset("tiny-moe", capacity_factor=1.25, **over)
           if kind == "dispatch" else preset("tiny", **over))
    return cfg, init_decoder_params(jax.random.PRNGKey(3), cfg)


def _assert_same(kind, got, want, what):
    tol = 3e-2 if kind.endswith("bfloat16") else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol, err_msg=what)


class TestChunkInPlace:
    """The "pallas" arm of the chunk program over a per-head pool (rows
    written in place, ``paged_chunk_attention`` over the pages where they
    lie) against the "gather" arm: the logits of every valid position and
    every plane of the pool, chunk after chunk, each arm on its own pool."""

    @pytest.mark.parametrize("kind", IN_PLACE_KINDS)
    def test_which_form_a_program_takes(self, kind):
        cfg, params = _wide_model(kind)
        cache = _empty_pool(cfg)
        assert _chunk_in_place(cache, cfg, None, "pallas")
        assert not _chunk_in_place(cache, cfg, None, "gather")
        assert not _chunk_in_place(cache, cfg, {"targets": {}}, "pallas")
        args = (params, cache, jnp.zeros((1, CHUNK), jnp.int32),
                jnp.zeros((1, MPP), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32))
        for impl, sites in (("pallas", 1), ("gather", 0)):
            text = str(jax.make_jaxpr(
                lambda *a, impl=impl: paged_chunk_prefill(
                    *a, cfg, context_pages=4, paged_attn_impl=impl))(*args))
            # one call site a scanned layer group a row
            assert text.count("name=paged_chunk_attention") == sites, impl

    def test_every_row_of_every_bucket_traces_the_kernel_once(
            self, monkeypatch):
        """A kernel's body is traced anew wherever ``pallas_call`` is
        reached, and a trace of this one costs a chip's host seconds; the
        table goes to it whole, so a row of any program is the same call,
        traced once (``setup_s``: PERF.md, PR 36)."""
        from kubeflow_tpu.ops import paged_attention as pa

        cfg, params = _wide_model("dense")
        traced = []
        real = pa._chunk_kernel
        monkeypatch.setattr(
            pa, "_chunk_kernel",
            lambda *a, **k: (traced.append(1), real(*a, **k))[1])
        pa._chunk_attention_call.clear_cache()
        for rows, ctx in ((1, 2), (1, 4), (2, MPP)):
            args = (params, _empty_pool(cfg),
                    jnp.zeros((rows, CHUNK), jnp.int32),
                    jnp.zeros((rows, MPP), jnp.int32),
                    jnp.zeros((rows,), jnp.int32),
                    jnp.zeros((rows,), jnp.int32))
            text = str(jax.make_jaxpr(
                lambda *a, ctx=ctx: paged_chunk_prefill(
                    *a, cfg, context_pages=ctx,
                    paged_attn_impl="pallas"))(*args))
            assert text.count("name=paged_chunk_attention") == rows
        pa._chunk_attention_call.clear_cache()
        assert len(traced) == 1

    def test_other_pools_stay_on_the_gathered_form(self):
        for kind in ("dense", "dispatch", "patterned"):    # heads of 16
            _, cfg, _ = _model(kind)
            assert not _chunk_in_place(_empty_pool(cfg), cfg, None, "pallas")
        cfg, _ = _wide_model("dense")
        int8 = {name: jnp.zeros(shape, dt) for name, (shape, dt) in
                pool_shapes(cfg, POOL, PAGE, kv_quant=True).items()}
        assert not _chunk_in_place(int8, cfg, None, "pallas")
        _, glm, _ = _model("latent")
        assert _chunk_in_place(_empty_pool(glm), glm, None, "gather")

    @pytest.mark.parametrize("kind", IN_PLACE_KINDS)
    def test_one_row_two_chunks_in_sequence(self, kind):
        cfg, params = _wide_model(kind)
        a = _tokens(1, CHUNK + 19)
        row = np.asarray([3, 0, 2, 1, -1, -1, -1, -1], np.int32)
        pools = {impl: _empty_pool(cfg) for impl in ("gather", "pallas")}
        for start, valid in ((0, CHUNK), (CHUNK, 19)):
            logits = {}
            for impl in pools:
                logits[impl], pools[impl] = _one(
                    _rows_program(cfg, impl), params, pools[impl], a, row,
                    start, valid)
            _assert_same(kind, logits["pallas"][:valid],
                         logits["gather"][:valid], f"logits at {start}")
            for name in pools["gather"]:
                _assert_same(kind, pools["pallas"][name],
                             pools["gather"][name], f"{name} at {start}")
        assert np.any(np.asarray(pools["pallas"]["k"], np.float32)[:, 1])

    @pytest.mark.parametrize("kind", IN_PLACE_KINDS)
    def test_two_rows_at_unlike_starts_then_one_dead(self, kind):
        """Row a resumes MID-PAGE (24 tokens held), row b at 64 with a short
        chunk; the next pass carries a's last 9 tokens beside a dead row."""
        cfg, params = _wide_model(kind)
        a, b = _tokens(1, 24 + CHUNK + 9), _tokens(2, 64 + 19)
        row_a = np.asarray([0, 1, 2, 3, 10, -1, -1, -1], np.int32)
        row_b = np.asarray([4, 5, 6, 7, 8, 9, -1, -1], np.int32)
        pools = {}
        for impl in ("gather", "pallas"):
            one = _rows_program(cfg, impl)
            _, pool = _one(one, params, _empty_pool(cfg), a, row_a, 0, 24)
            _, pool = _one(one, params, pool, b, row_b, 0, CHUNK)
            _, pools[impl] = _one(one, params, pool, b, row_b, CHUNK, CHUNK)
        passes = (((a, row_a, 24, CHUNK), (b, row_b, 64, 19)),
                  ((a, row_a, 24 + CHUNK, 9), None))
        for n, rows in enumerate(passes):
            logits = {}
            for impl in pools:
                logits[impl], pools[impl] = _two(
                    _rows_program(cfg, impl), params, pools[impl], rows,
                    ctx=MPP)
            for r, row in enumerate(rows):
                if row is not None:
                    _assert_same(kind, logits["pallas"][r, :row[3]],
                                 logits["gather"][r, :row[3]],
                                 f"pass {n} row {r}")
            for name in pools["gather"]:
                _assert_same(kind, pools["pallas"][name],
                             pools["gather"][name], f"{name} after pass {n}")

    def test_the_engine_runs_every_bucket_as_one_program(self):
        """The in-place form of a per-head pool does not read its context
        bucket, so the engine's one-row program is ONE compiled program
        whatever bucket a call names (the names ``program_kernels`` records
        stay a bucket's); the gathered form keeps a program a bucket."""
        cfg, params = _wide_model("dense")
        row = jnp.asarray([3, 0, 2, 1, -1, -1, -1, -1], jnp.int32)
        block = jnp.asarray(_tokens(6, CHUNK)[None])
        programs = {}
        for impl in ("pallas", "gather"):
            eng = _engine(cfg, params, max_len=MPP * PAGE,
                          paged_attn_impl=impl)
            one = getattr(eng._paged_chunk, "jitted", eng._paged_chunk)
            # an engine that sends chunks ahead ran it under every
            # bucket's name when it was built (``ChunkPrograms.warm``): ONE
            # program
            assert one._cache_size() == (1 if eng._plan.ahead else 0)
            for start, bucket in ((0, 2), (CHUNK, 4)):
                logits, eng.cache = eng._paged_chunk(
                    eng.params, eng.cache, block, row, jnp.int32(start),
                    jnp.int32(CHUNK), bucket)
                assert logits.shape == (CHUNK, cfg.vocab_size)
            programs[impl] = one._cache_size()
        assert programs == {"pallas": 1, "gather": 2}

    def test_the_engine_serves_the_same_tokens_on_either_arm(self):
        cfg, params = _wide_model("dispatch")
        prompts = [_tokens(4, 3 * CHUNK - 5), _tokens(5, 2 * CHUNK - 9)]
        eng = _engine(cfg, params, paged_attn_impl="pallas")
        assert eng._plan.rows == 2
        assert _chunk_in_place(eng.cache, cfg, None, eng.paged_attn_impl)
        assert _greedy(eng, prompts) == _greedy(
            _engine(cfg, params, paged_attn_impl="gather"), prompts)


class TestCapacityPerRow:
    """``layers._moe_dispatch``: capacity and claiming order within a row."""

    @pytest.fixture(scope="class")
    def layer(self):
        cfg = _config("dispatch")
        p, _ = L.init_moe(jax.random.PRNGKey(5), cfg)
        # Two rows that crowd expert 0: few distinct token vectors each.
        base = jax.random.normal(jax.random.PRNGKey(6), (2, 3, cfg.hidden))
        pick = jax.random.choice(jax.random.PRNGKey(7), 3, (2, CHUNK),
                                 p=jnp.asarray([0.85, 0.1, 0.05]))
        x = jnp.take_along_axis(base, pick[..., None], axis=1)
        x = x + 0.01 * jax.random.normal(jax.random.PRNGKey(8), x.shape)
        return cfg, p, x, jnp.asarray([CHUNK, 19], jnp.int32)

    def test_rows_together_equal_each_row_alone(self, layer):
        cfg, p, x, valid = layer
        both, _ = L.moe_block(p, x, cfg, valid_len=valid,
                              capacity_per_row=True)
        for r in range(2):
            alone, _ = L.moe_block(p, x[r:r + 1], cfg,
                                   valid_len=valid[r:r + 1])
            n = int(valid[r])
            np.testing.assert_allclose(both[r, :n], alone[0, :n], rtol=1e-6,
                                       atol=1e-6)

    def test_one_capacity_over_the_block_couples_the_rows(self, layer):
        """What the serving chunk must NOT use: with capacity per dispatch
        batch (training's), a row's drops depend on its neighbour."""
        cfg, p, x, valid = layer
        pooled, _ = L.moe_block(p, x, cfg, valid_len=valid)
        alone, _ = L.moe_block(p, x[:1], cfg, valid_len=valid[:1])
        assert float(jnp.max(jnp.abs(pooled[0] - alone[0]))) > 1e-3

    def test_at_one_row_both_are_the_same_computation(self, layer):
        cfg, p, x, valid = layer
        per_row, aux_row = L.moe_block(p, x[:1], cfg, valid_len=valid[:1],
                                       capacity_per_row=True)
        per_batch, aux = L.moe_block(p, x[:1], cfg, valid_len=valid[:1])
        np.testing.assert_array_equal(per_row, per_batch)
        assert float(aux_row) == float(aux)


# -- the head at each row's last valid position -----------------------------------

# kind -> (config, the engine's "gather" | "pallas"): this file's kinds, a
# per-head pool in place, packed rows beside conv layers with the tied head
# in bfloat16 (the longanswer cell's way), window layers over a ring in
# either form.
LAST_KINDS = {
    **{kind: (lambda kind=kind: _config(kind), "gather") for kind in KINDS},
    "dense-in-place": (lambda: _wide_model("dense")[0], "pallas"),
    "packed-beside-conv-bfloat16": (
        lambda: preset("tiny-lfm2", max_seq_len=1024), "gather"),
    "window": (lambda: _window_config(), "gather"),
    "window-in-place": (lambda: _window_config(head_dim=128), "pallas"),
    # attention and an SSD mixer side by side (the assistant cell's way): K
    # and V rows a token and a state and a convolution's rows a sequence
    "parallel": (lambda: _parallel_config(), "gather"),
    "parallel-in-place": (
        lambda: _parallel_config(n_heads=2, n_kv_heads=1, head_dim=128),
        "pallas"),
}


def _parallel_config(**over):
    return preset("tiny-falconh1", dtype="float32", param_dtype="float32",
                  max_seq_len=1024, **over)


def _window_config(**over):
    """test_serve_exaone.py's small model (a leading dense window layer,
    then window, window, global, window; a quarter of the experts held)
    with the ring an engine of this file's sizes would set."""
    cfg = preset("tiny-exaone", dtype="float32", param_dtype="float32",
                 max_seq_len=1024, **over)
    return dataclasses.replace(
        cfg, window_ring_pages=ring_pages(cfg, CHUNK, PAGE, MPP))


@functools.lru_cache(maxsize=None)
def _last_and_all(kind: str):
    """Both forms of the two-row program, each on its own pool, over two
    passes: row a resumes MID-PAGE (24 tokens held) with a FULL chunk beside
    row b at 64 with a SHORT last chunk of 19; then a's last 9 tokens beside
    a DEAD row. Returns the config and, a pass, (rows, {form: logits},
    {form: pool})."""
    make, impl = LAST_KINDS[kind]
    cfg = make()
    params = init_decoder_params(jax.random.PRNGKey(3), cfg)
    a, b = _tokens(1, 24 + CHUNK + 9), _tokens(2, 64 + 19)
    row_a = np.asarray([0, 1, 2, 3, 10, -1, -1, -1], np.int32)
    row_b = np.asarray([4, 5, 6, 7, 8, 9, -1, -1], np.int32)
    one = _rows_program(cfg, impl)
    _, pool = _one(one, params, _empty_pool(cfg), a, row_a, 0, 24)
    _, pool = _one(one, params, pool, b, row_b, 0, CHUNK)
    _, pool = _one(one, params, pool, b, row_b, CHUNK, CHUNK)
    pools = {"all": pool, "last": pool}
    out = []
    for rows in (((a, row_a, 24, CHUNK), (b, row_b, 64, 19)),
                 ((a, row_a, 24 + CHUNK, 9), None)):
        logits = {}
        for form in pools:
            logits[form], pools[form] = _two(
                _rows_program(cfg, impl, form), params, pools[form], rows,
                ctx=MPP)
        out.append((rows, logits, dict(pools)))
    return cfg, out


@pytest.mark.parametrize("kind", sorted(LAST_KINDS))
class TestLastPosition:
    def test_a_row_is_the_all_position_forms_row_there(self, kind):
        """``[B,V]``: row ``r`` is the all-position form's
        ``logits[r, valid_len[r] - 1]``: the same greedy token, and values
        within one unit in the last place of a bfloat16 (the matmul's
        inputs on the chip) of the largest logit; the number is printed."""
        cfg, passes = _last_and_all(kind)
        worst = 0.0
        for rows, logits, _ in passes:
            assert logits["all"].shape == (2, CHUNK, cfg.vocab_size)
            assert logits["last"].shape == (2, cfg.vocab_size)
            assert logits["last"].dtype == jnp.float32
            for r, row in enumerate(rows):
                if row is None:
                    continue
                want = np.asarray(logits["all"][r, row[3] - 1])
                got = np.asarray(logits["last"][r])
                assert int(got.argmax()) == int(want.argmax())
                worst = max(worst, float(np.abs(got - want).max())
                            / float(np.abs(want).max()))
        print(f"{kind}: largest |last - all| over the largest logit "
              f"{worst:.3g}")
        assert worst <= 2.0 ** -8

    def test_the_pool_is_written_alike(self, kind):
        _, passes = _last_and_all(kind)
        for n, (_, _, pools) in enumerate(passes):
            for name in pools["all"]:
                np.testing.assert_array_equal(
                    np.asarray(pools["last"][name], np.float32),
                    np.asarray(pools["all"][name], np.float32),
                    err_msg=f"{name} after pass {n}")

    def test_a_dead_row_writes_nothing(self, kind):
        """The second pass's dead row beside a's last tokens: b's pages and
        every unmapped page stand as the first pass left them; two dead
        rows leave the whole pool as it was and return finite rows."""
        cfg, passes = _last_and_all(kind)
        before, after = passes[0][2]["last"], passes[1][2]["last"]
        for name in (n for n in after if after[n].ndim > 1):
            np.testing.assert_array_equal(
                np.asarray(after[name], np.float32)[:, 5:10],
                np.asarray(before[name], np.float32)[:, 5:10], err_msg=name)
        impl = LAST_KINDS[kind][1]
        params = init_decoder_params(jax.random.PRNGKey(3), cfg)
        logits, pool = _two(_rows_program(cfg, impl, "last"), params, after,
                            (None, None), ctx=MPP)
        assert logits.shape == (2, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        for name in (n for n in pool if pool[n].ndim > 1):
            np.testing.assert_array_equal(
                np.asarray(pool[name], np.float32),
                np.asarray(after[name], np.float32), err_msg=name)


    def test_with_no_row_wanted_the_head_is_not_run(self, kind):
        """``wanted``: where it names any row every row's logits come back
        as without it, to the bit; where it names none, zeros: the head's
        ONE matrix product lies under a conditional; the pool is written
        alike whatever it names."""
        cfg, passes = _last_and_all(kind)
        impl = LAST_KINDS[kind][1]
        params = init_decoder_params(jax.random.PRNGKey(3), cfg)
        program = _rows_program(cfg, impl, "last")
        rows, logits, pools = passes[1]         # a's last tokens, a dead row
        before = passes[0][2]["last"]
        for wanted in ((True, False), (False, True), (False, False)):
            got, pool = _two(program, params, before, rows, ctx=MPP,
                             wanted=wanted)
            if any(wanted):
                np.testing.assert_array_equal(got, logits["last"])
            else:
                np.testing.assert_array_equal(got, 0.0)
            for name in pool:
                np.testing.assert_array_equal(
                    np.asarray(pool[name], np.float32),
                    np.asarray(pools["last"][name], np.float32), err_msg=name)
        args = (params, before, jnp.zeros((2, CHUNK), jnp.int32),
                jnp.zeros((2, MPP), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32))
        traced = jax.make_jaxpr(lambda *a: paged_chunk_prefill(
            *a[:6], cfg, context_pages=MPP, paged_attn_impl=impl,
            logits_at="last", wanted=a[6]))(*args, jnp.zeros((2,), bool))
        assert _head_products(traced.jaxpr, cfg.vocab_size) == [True]


def _head_products(jaxpr, vocab: int, under_cond: bool = False,
                   rows: int = 2) -> list:
    """For every matrix product in ``jaxpr`` whose result is ``[rows, 1,
    V]`` (the head at one position a row): whether it lies under a
    ``cond``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" \
                and eqn.outvars[0].aval.shape == (rows, 1, vocab):
            found.append(under_cond)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _head_products(
                        sub, vocab,
                        under_cond or eqn.primitive.name == "cond", rows)
    return found


def test_an_unknown_form_is_refused():
    _, cfg, params = _model("dense")
    with pytest.raises(ValueError, match="logits_at"):
        _two(_rows_program(cfg, logits_at="first"), params, _empty_pool(cfg),
             (None, None), ctx=MPP)
    with pytest.raises(ValueError, match="wanted"):
        _two(_rows_program(cfg), params, _empty_pool(cfg), (None, None),
             ctx=MPP, wanted=(True, False))


# -- the engine ------------------------------------------------------------------

def _engine(cfg, params, *, chunk=CHUNK, max_len=256, **kw):
    spec = dict(max_batch_size=4, max_seq_len=max_len, paged=True,
                page_size=PAGE, chunked_prefill_tokens=chunk,
                enable_prefix_caching=False, max_concurrent_prefills=2)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec), params=params)


def _run(eng, reqs, max_steps=800):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def _greedy(eng, prompts, n=4):
    sp = SamplingParams(max_new_tokens=n, temperature=0.0)
    reqs = [eng.submit(list(map(int, p)), sp) for p in prompts]
    _run(eng, reqs)
    return [list(r.output_tokens) for r in reqs]


def _chunks_per_program(eng) -> float:
    c = eng.counters()
    return c["prefill_chunks_dispatched"] / c["prefill_programs_dispatched"]


class TestTheRule:
    def test_the_ridge_is_the_chips(self):
        # v5e: 197 TFLOP/s over 819 GB/s, a bf16 parameter 2 FLOPs a row
        # and 2 bytes: 240 rows, the next whole tile.
        assert 197e12 / 819e9 < RIDGE_ROWS == 256

    @pytest.mark.parametrize("name,overrides,chunk,rows", [
        ("llama3-8b", {}, 512, 512),
        ("mixtral-8x7b", {}, 512, 128),
        ("glm-4.7-flash", {}, 512, 32),
        ("mixtral-8x7b", {"moe_impl": "dense"}, 512, 512),
    ])
    def test_rows_one_weight_sees_in_a_chunk(self, name, overrides, chunk,
                                             rows):
        cfg = preset(name, **overrides)
        assert chunk_rows_per_weight(cfg, chunk) == rows
        assert (rows < RIDGE_ROWS) == (name != "llama3-8b"
                                       and not overrides)


def record_spans(patch) -> list:
    """The engine's host spans from here on, as (name, attrs): what its
    phases would write into a capture (obs/profiler.py), with none taken."""
    seen = []

    class Span(contextlib.nullcontext):
        def __init__(self, name, **attrs):
            super().__init__(self)
            seen.append((name, attrs))
            self.set_metadata = attrs.update

    patch.setattr(profiler, "hot_span", Span)
    patch.setattr(profiler, "active", lambda: True)
    return seen


class TestEngineBatchesChunks:
    def test_two_prompts_together_as_each_alone(self, model):
        kind, cfg, params = model
        # A dense model's chunk at the ridge: one chunk a program.
        chunk = 256 if kind == "dense" else CHUNK
        max_len = 1024 if kind == "dense" else 256
        n = 3 * chunk
        prompts = [_tokens(4, n - 5), _tokens(5, n - chunk - 9)]
        eng = _engine(cfg, params, chunk=chunk, max_len=max_len)
        assert set(eng.counters()) >= {"prefill_programs_dispatched",
                                       "prefill_chunks_dispatched",
                                       "prefill_tokens_dispatched"}
        assert eng.counters()["prefill_programs_dispatched"] == 0
        together = _greedy(eng, prompts)
        alone = [_greedy(_engine(cfg, params, chunk=chunk, max_len=max_len,
                                 max_concurrent_prefills=1), [p])[0]
                 for p in prompts]
        assert together == alone
        c = eng.counters()
        assert c["prefill_tokens_dispatched"] == sum(map(len, prompts))
        assert c["prefill_chunks_dispatched"] == 3 + 2
        if kind == "dense":
            assert eng._plan.rows == 1 and eng._plan.lone_at_last
            assert _chunks_per_program(eng) == 1
        else:
            assert eng._plan.rows == 2
            # two passes carry both prompts' chunks, the third a's last
            assert c["prefill_programs_dispatched"] == 3
            assert _chunks_per_program(eng) > 1

    @pytest.mark.parametrize("kind", KINDS)
    def test_no_compile_after_construction(self, kind):
        """The program over rows is compiled and run when the engine is
        built: its first dispatch compiles nothing, nor does the first pass
        that carries two prompts' chunks."""
        _, cfg, params = _model(kind)
        eng = _engine(cfg, params)
        compiles = CompileCounter()
        compiles.start()
        logits, eng.cache = eng._programs.rows(
            eng.params, eng.cache,
            *map(jnp.asarray, eng._programs.pack([], 2)), eng._mpp)
        assert logits.shape == (2, cfg.vocab_size)
        jax.block_until_ready(logits[1])
        sp = SamplingParams(max_new_tokens=2, temperature=0.0)
        for seed in (4, 5):
            eng.submit(list(map(int, _tokens(seed, 60))), sp)
        eng._admit()
        assert eng.counters()["prefill_chunks_dispatched"] == 2
        assert compiles.stop() == 0, compiles.names

    @pytest.mark.parametrize("kind", KINDS)
    def test_the_programs_over_rows_and_those_with_an_end_are_counted(
            self, kind):
        """Prompts of three chunks and of two: two passes carry a chunk of
        each (the program over rows; b ends in the second), the third a's
        last chunk alone (the one-row program). A dense model at its ridge
        sends one chunk a program: five programs of one row (none of them a
        program over SEVERAL rows), two with an end."""
        _, cfg, params = _model(kind)
        chunk = 256 if kind == "dense" else CHUNK
        eng = _engine(cfg, params, chunk=chunk,
                      max_len=1024 if kind == "dense" else 256)
        c = eng.counters()
        assert (c["prefill_row_programs_dispatched"],
                c["prefill_programs_with_end"]) == (0, 0)
        _greedy(eng, [_tokens(4, 3 * chunk - 5), _tokens(5, 2 * chunk - 9)])
        c = eng.counters()
        assert (c["prefill_programs_dispatched"],
                c["prefill_row_programs_dispatched"],
                c["prefill_programs_with_end"]) == (
                    (5, 0, 2) if kind == "dense" else (3, 2, 2))

    @pytest.mark.parametrize("kind", KINDS)
    def test_the_positions_the_head_ran_at_are_counted(self, kind):
        """The same two prompts. Where the engine sends two chunks a
        program: none in the first pass's program (no row ends its prompt),
        its two rows in the second's (b ends), and a's last chunk alone
        takes the ``[C,V]`` program, the head at all ``C``. A dense model at
        its ridge sends every chunk alone through the program over rows:
        one position in each of the two programs that end a prompt."""
        _, cfg, params = _model(kind)
        chunk = 256 if kind == "dense" else CHUNK
        eng = _engine(cfg, params, chunk=chunk,
                      max_len=1024 if kind == "dense" else 256)
        assert eng.counters()["prefill_head_positions"] == 0
        _greedy(eng, [_tokens(4, 3 * chunk - 5), _tokens(5, 2 * chunk - 9)])
        assert eng.counters()["prefill_head_positions"] == (
            2 if kind == "dense" else 0 + 2 + chunk)

    def test_a_small_dense_chunk_batches_too(self):
        """The rule reads rows, not a model's kind: a dense model at 32
        tokens a chunk is as weights-bound as an expert layer."""
        _, cfg, params = _model("dense")
        eng = _engine(cfg, params)
        assert eng._plan.rows == 2
        prompts = [_tokens(4, 91), _tokens(5, 50)]
        together = _greedy(eng, prompts)
        alone = [_greedy(_engine(cfg, params, max_concurrent_prefills=1),
                         [p])[0] for p in prompts]
        assert together == alone
        assert _chunks_per_program(eng) > 1

    @pytest.mark.parametrize("kind", EXPERT_KINDS)
    def test_a_stalled_prefill_does_not_hold_back_the_other(self, kind,
                                                            monkeypatch):
        _, cfg, params = _model(kind)
        prompts = [_tokens(4, 90), _tokens(5, 75)]
        want = _greedy(_engine(cfg, params), prompts)
        eng = _engine(cfg, params)
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        reqs = [eng.submit(list(map(int, p)), sp) for p in prompts]
        eng._admit()                      # both admitted, one pass together
        a, b = eng._chunkings
        assert (a.pos, b.pos) == (CHUNK, CHUNK)
        assert eng.counters()["prefill_programs_dispatched"] == 1
        # No page for a's next chunk in this pass.
        ensure = eng._ensure_pages
        monkeypatch.setattr(
            eng, "_ensure_pages",
            lambda slot, upto: slot != a.slot and ensure(slot, upto))
        assert eng._advance_chunked() == 1
        assert (a.pos, a.stalls, b.pos, b.stalls) == (CHUNK, 1, 2 * CHUNK, 0)
        c = eng.counters()
        assert (c["prefill_programs_dispatched"],
                c["prefill_chunks_dispatched"]) == (2, 3)
        monkeypatch.setattr(eng, "_ensure_pages", ensure)
        _run(eng, reqs)
        assert [list(r.output_tokens) for r in reqs] == want

    @pytest.mark.parametrize("kind", KINDS)
    def test_a_finished_prefill_hands_its_lane_on_within_the_pass(self, kind):
        """Short prompts do not queue behind a decode round for one of the
        two lanes: four of them are prefilled by ONE admit pass, two
        programs of two chunks."""
        _, cfg, params = _model(kind)
        eng = _engine(cfg, params)
        sp = SamplingParams(max_new_tokens=3, temperature=0.0)
        reqs = [eng.submit(list(map(int, _tokens(s, 20 + s))), sp)
                for s in range(4)]
        eng._admit()
        assert not eng._chunkings and all(len(r.output_tokens) == 1
                                          for r in reqs)
        c = eng.counters()
        assert (c["prefill_programs_dispatched"],
                c["prefill_chunks_dispatched"]) == (2, 4)
        _run(eng, reqs)
        assert [list(r.output_tokens) for r in reqs] == [
            _greedy(_engine(cfg, params, max_concurrent_prefills=1),
                    [r.prompt_tokens])[0][:3] for r in reqs]

    def test_the_dispatch_span_carries_its_chunks(self, monkeypatch):
        _, cfg, params = _model("dispatch")
        eng = _engine(cfg, params)
        seen = record_spans(monkeypatch)
        _greedy(eng, [_tokens(4, 70), _tokens(5, 40)])
        chunks = [attrs["chunks"] for name, attrs in seen
                  if name == "engine.prefill_dispatch"]
        assert chunks == [2, 2, 1]


# -- an engine that sends one chunk a program: a lone chunk at one row -------------

ADAPTER = "tenant-a"
# name -> (this file's kind of model, what the engine is built with): engines
# that send ONE chunk a program and carry no step in their chunk programs.
ONE_CHUNK = {
    "dense-over-the-ridge": ("dense", dict(chunk=256, max_len=1024)),
    "parallel": ("parallel", dict(max_concurrent_prefills=1)),
    "lora": ("dense", dict(
        max_concurrent_prefills=1,
        lora=LoRASpec(max_adapters=2, rank=4, targets=("wq", "wv")))),
    "speculative": ("dense", dict(
        max_concurrent_prefills=1,
        speculative=SpeculativeSpec(mode="ngram", k=4))),
    "int8-pool": ("dense", dict(max_concurrent_prefills=1,
                                kv_cache_dtype="int8")),
}


def _one_chunk_engine(name: str, at_last: bool = True):
    """The engine of ``ONE_CHUNK[name]`` and a count of the calls of its two
    chunk programs; ``at_last`` False: as such an engine dispatched before
    ISSUE 52, every lone chunk through the ``[C,V]`` program."""
    kind, options = ONE_CHUNK[name]
    _, cfg, params = _model(kind)
    eng = _engine(cfg, params, **options)
    assert eng._plan.rows == 1 and not eng._plan.carries_step \
        and eng._plan.lone_at_last
    if name == "lora":
        eng._lora.register(AdapterSpec(
            ADAPTER, rank=4, alpha=8.0, weights=init_adapter_weights(
                jax.random.PRNGKey(11), cfg, 4, ("wq", "wv"))))
    eng._plan = dataclasses.replace(eng._plan, lone_at_last=at_last)
    calls = {}
    for which in ("lone", "rows"):
        def counted(*args, _program=getattr(eng._programs, which),
                    _which=which):
            calls[_which] = calls.get(_which, 0) + 1
            return _program(*args)
        setattr(eng._programs, which, counted)
    return eng, calls


def _stream(eng, prompt, n, **submit):
    req = eng.submit(list(map(int, prompt)), SamplingParams(
        max_new_tokens=n, temperature=0.0), **submit)
    _run(eng, [req])
    return list(req.output_tokens)


def _by_hand(eng, prompt, n: int) -> list:
    """Greedy tokens from the engine's ``[C,V]`` program ALONE, driven as a
    caller outside the engine drives it: for every token the whole sequence
    is prefilled again from position 0 into the pool's first pages and the
    token read at ``logits[real - 1]`` of its last chunk."""
    C = eng.chunk_size
    row = jnp.arange(eng._mpp, dtype=jnp.int32)
    toks, out = list(map(int, prompt)), []
    for _ in range(n):
        for pos in range(0, len(toks), C):
            real = min(C, len(toks) - pos)
            block = np.zeros((1, C), np.int32)
            block[0, :real] = toks[pos:pos + real]
            logits, eng.cache = eng._paged_chunk(
                eng.params, eng.cache, jnp.asarray(block), row,
                jnp.int32(pos), jnp.int32(real),
                context_bucket(pos, C, eng.page_size, eng._mpp))
        out.append(int(jnp.argmax(logits[real - 1])))
        toks.append(out[-1])
    return out


class TestALoneChunkAtOneRow:
    @pytest.mark.parametrize("name", sorted(ONE_CHUNK))
    def test_traffic_never_takes_the_all_position_program(self, name):
        """A prompt of two chunks (and, beside it, an adapter's): every
        chunk goes through the program over rows, none through the
        ``[C,V]`` program, and the tokens are those the same engine gave
        when every chunk took the ``[C,V]`` program."""
        eng, calls = _one_chunk_engine(name)
        prompts = [(_tokens(4, 2 * eng.chunk_size - 7), {})]
        if name == "lora":
            prompts.append((_tokens(5, eng.chunk_size + 3),
                            dict(adapter=ADAPTER)))
        got = [_stream(eng, p, 6, **kw) for p, kw in prompts]
        assert calls == {"rows": 2 * len(prompts)}
        c = eng.counters()
        assert (c["prefill_programs_dispatched"],
                c["prefill_row_programs_dispatched"],
                c["prefill_programs_with_end"],
                c["prefill_head_positions"]) == (
                    2 * len(prompts), 0, len(prompts), len(prompts))
        before, calls = _one_chunk_engine(name, at_last=False)
        assert got == [_stream(before, p, 6, **kw) for p, kw in prompts]
        assert calls == {"lone": 2 * len(prompts)}
        assert before.counters()["prefill_head_positions"] \
            == 2 * len(prompts) * eng.chunk_size
        if name == "lora":
            assert got[1] != _stream(eng, prompts[1][0], 6)

    @pytest.mark.parametrize("name", ["dense-over-the-ridge", "parallel"])
    def test_streams_are_the_all_position_programs_driven_by_hand(self,
                                                                  name):
        eng, calls = _one_chunk_engine(name)
        prompts = [_tokens(4, 2 * eng.chunk_size - 7),
                   _tokens(5, eng.chunk_size // 2)]
        got = [_stream(eng, p, 4) for p in prompts]
        assert "lone" not in calls
        by_hand, _ = _one_chunk_engine(name)
        assert got == [_by_hand(by_hand, p, 4) for p in prompts]

    @pytest.mark.parametrize("kind", ["dense", "parallel"])
    def test_the_two_programs_of_an_engine_agree(self, kind):
        """The engine's own two programs, each on its own pool, over a
        prompt of three chunks: 24 tokens, a FULL chunk that starts
        MID-PAGE, a last one of 9. The program over rows at one row returns
        zeros while the prompt goes on and, at its end, the ``[C,V]``
        program's row ``real - 1``; every plane of the pool (K and V rows a
        token; a parallel layer's SSD state and its convolution's rows a
        sequence) is the same to the bit after every chunk."""
        _, cfg, params = _model(kind)
        tokens = _tokens(1, 24 + CHUNK + 9)
        row = np.asarray([0, 1, 2, 3, 10, -1, -1, -1], np.int32)
        engines = {form: _engine(cfg, params, max_concurrent_prefills=1,
                                 max_len=MPP * PAGE)
                   for form in ("all", "last")}
        for start, real in ((0, 24), (24, CHUNK), (24 + CHUNK, 9)):
            block = np.zeros((1, CHUNK), np.int32)
            block[0, :real] = tokens[start:start + real]
            bucket = context_bucket(start, CHUNK, PAGE, MPP)
            ends = start + real == len(tokens)
            eng = engines["all"]
            every, eng.cache = eng._paged_chunk(
                eng.params, eng.cache, jnp.asarray(block), jnp.asarray(row),
                jnp.int32(start), jnp.int32(real), bucket)
            assert every.shape == (CHUNK, cfg.vocab_size)
            eng = engines["last"]
            last, eng.cache = eng._programs.rows(
                eng.params, eng.cache, *map(jnp.asarray, eng._programs.pack(
                    [(tokens[start:start + real], row, start, ends)], 1)),
                bucket)
            assert last.shape == (1, cfg.vocab_size)
            if ends:
                want = np.asarray(every[real - 1])
                assert int(last[0].argmax()) == int(want.argmax())
                assert float(np.abs(np.asarray(last[0]) - want).max()) \
                    <= 2.0 ** -8 * float(np.abs(want).max())
            else:
                np.testing.assert_array_equal(last, 0.0)
            pools = {form: e.cache for form, e in engines.items()}
            assert set(pools["last"]) == set(pools["all"]) \
                and (kind != "parallel" or {"k", "v", "ssd_state",
                                            "ssd_conv"} <= set(pools["all"]))
            for plane in pools["all"]:
                np.testing.assert_array_equal(
                    pools["last"][plane], pools["all"][plane],
                    err_msg=f"{plane} after the chunk at {start}")

    @pytest.mark.parametrize("kind", ["dense", "parallel"])
    def test_the_head_of_the_traffic_program_lies_under_one_cond(self, kind):
        """What traffic runs holds ONE matrix product of the head, ``[1, 1,
        V]``, under a conditional, and no value of the ``[C,V]`` program's
        result's shape anywhere in its lowered text (which that program's
        own text holds)."""
        _, cfg, params = _model(kind)
        eng = _engine(cfg, params, max_concurrent_prefills=1)
        args = (eng.params, eng.cache, jnp.zeros((1, CHUNK), jnp.int32),
                jnp.zeros((1, eng._mpp), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
        traced = jax.make_jaxpr(
            lambda *a: eng._programs.rows(*a, eng._mpp))(
                *args, jnp.zeros((1,), bool))
        assert _head_products(traced.jaxpr, cfg.vocab_size, rows=1) == [True]
        all_positions = f"{CHUNK}x{cfg.vocab_size}xf32"
        assert all_positions not in eng._programs.rows.lower(
            *args, jnp.zeros((1,), bool), eng._mpp).as_text()
        assert all_positions in eng._paged_chunk.lower(
            *args[:3], jnp.zeros((eng._mpp,), jnp.int32), jnp.int32(0),
            jnp.int32(0), eng._mpp).as_text()
