"""The chunks of several in-flight prefills in ONE program (ISSUE 29), on the
CPU: the program over rows (a, b) against the one-row program on a and on b
in turn (pool rows and logits), a row's independence of its neighbour, the
per-row capacity of the dispatch expert layer, and the engine's side: when
the program is built, that it is warm from construction on, what a pass
dispatches and counts, and that a stalled prefill holds nobody back."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.device import CompileCounter
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve import engine as engine_mod
from kubeflow_tpu.serve.engine import (
    RIDGE_ROWS, LLMEngine, SamplingParams, chunk_rows_per_weight,
)
from kubeflow_tpu.serve.paged import (
    context_bucket, paged_chunk_prefill, pool_shapes,
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
    return {name: jnp.zeros(shape, dt)
            for name, (shape, dt) in pool_shapes(cfg, POOL, PAGE).items()}


def _rows_program(cfg):
    return jax.jit(
        lambda p, c, t, tr, st, vl, ncp: paged_chunk_prefill(
            p, c, t, tr, st, vl, cfg, context_pages=ncp),
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


def _two(program, params, cache, rows, ctx=None):
    """The two-row program; a row is (tokens, table_row, start, valid) or
    None for a dead one. ``ctx``: the static context bucket (the largest
    live row's unless given)."""
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
    return program(params, cache, jnp.asarray(block), jnp.asarray(table),
                   jnp.asarray(start), jnp.asarray(valid), ctx)


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


class TestEngineBatchesChunks:
    def test_two_prompts_together_as_each_alone(self, model):
        kind, cfg, params = model
        # A dense model's chunk at the ridge: no program over rows is built.
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
            assert eng._chunk_rows == 1 and not hasattr(eng, "_paged_chunks")
            assert _chunks_per_program(eng) == 1
        else:
            assert eng._chunk_rows == 2
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
        logits, eng.cache = eng._paged_chunks(
            eng.params, eng.cache, jnp.zeros((2, CHUNK), jnp.int32),
            jnp.full((2, eng._mpp), -1, jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), eng._mpp)
        jax.block_until_ready(logits[1, CHUNK - 1])
        sp = SamplingParams(max_new_tokens=2, temperature=0.0)
        for seed in (4, 5):
            eng.submit(list(map(int, _tokens(seed, 60))), sp)
        eng._admit()
        assert eng.counters()["prefill_chunks_dispatched"] == 2
        assert compiles.stop() == 0, compiles.names

    def test_a_small_dense_chunk_batches_too(self):
        """The rule reads rows, not a model's kind: a dense model at 32
        tokens a chunk is as weights-bound as an expert layer."""
        _, cfg, params = _model("dense")
        eng = _engine(cfg, params)
        assert eng._chunk_rows == 2
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
        seen = []

        @contextlib.contextmanager
        def span(name, **attrs):
            seen.append((name, attrs))
            yield

        eng = _engine(cfg, params)
        monkeypatch.setattr(engine_mod, "hot_span", span)
        _greedy(eng, [_tokens(4, 70), _tokens(5, 40)])
        chunks = [attrs["chunks"] for name, attrs in seen
                  if name == "engine.prefill_dispatch"]
        assert chunks == [2, 2, 1]
