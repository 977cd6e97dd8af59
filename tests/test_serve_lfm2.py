"""A patterned stack (LFM2: gated short-convolution layers with one attention
layer in four, a leading dense conv layer, per-head q/k norms, K/V heads
packed into one pool row) on the normal path, at the tiny preset on the CPU:
the conv operator in pieces, the stack's groups, the pool's planes by kind,
the chunk program and the decode step against the full forward, and through
the engine: preemption, whole-page prefix reuse, the refused options and the
counters."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import (
    decoder_forward, decoder_loss, decoder_param_specs, init_decoder_params,
    layer_groups,
)
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    _paged_decode_step, copy_pages, paged_chunk_prefill,
    pool_bytes_per_token, pool_planes, pool_shapes, state_bytes_per_page,
    state_planes,
)

PAGE, CHUNK, MPP, POOL = 8, 16, 8, 20
CFG = preset("tiny-lfm2", dtype="float32", param_dtype="float32")
PARAMS = init_decoder_params(jax.random.PRNGKey(7), CFG)


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, n).astype(
        np.int32)


def _empty_pool(cfg=CFG):
    return {n: jnp.zeros(shape, dt)
            for n, (shape, dt) in pool_shapes(cfg, POOL, PAGE).items()}


def _prefill(cache, tokens, row, plen, chunk=CHUNK, start=0):
    """``tokens[start:plen]`` through the one-row chunk program; returns
    (the logits of every position [plen - start, V], the cache)."""
    out = []
    for pos in range(start, plen, chunk):
        real = min(chunk, plen - pos)
        block = np.zeros((1, chunk), np.int32)
        block[0, :real] = tokens[pos:pos + real]
        logits, cache = paged_chunk_prefill(
            PARAMS, cache, jnp.asarray(block), jnp.asarray(row)[None],
            jnp.asarray([pos], jnp.int32), jnp.asarray([real], jnp.int32),
            CFG, context_pages=MPP)
        out.append(logits[0, :real])
    return jnp.concatenate(out), cache


def _decode(cache, tokens, row, start, n, impl="gather"):
    table = np.full((2, MPP), -1, np.int32)
    table[0] = row
    out = []
    for i in range(start, start + n):
        tok, lens = np.zeros((2,), np.int32), np.zeros((2,), np.int32)
        tok[0], lens[0] = tokens[i], i
        logits, cache = _paged_decode_step(
            PARAMS, {**cache, "table": jnp.asarray(table)}, jnp.asarray(tok),
            jnp.asarray(lens), jnp.asarray([True, False]), CFG,
            attn_impl=impl)
        cache.pop("table")
        out.append(logits[0])
    return jnp.stack(out), cache


def _full(tokens):
    return decoder_forward(PARAMS, jnp.asarray(tokens)[None], CFG)[0][0]


# -- the conv operator -------------------------------------------------------------

@pytest.mark.parametrize("cuts", [(), (1,), (5, 6), (3, 11, 12, 20), (23,)])
def test_conv_in_pieces_with_the_tail_carried_is_conv_in_one_piece(cuts):
    p, _ = L.init_conv(jax.random.PRNGKey(1), CFG)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, CFG.hidden))
    whole, zs = L.conv_block(p, x, CFG)
    assert zs.shape == (2, CFG.conv_taps - 1 + 24, CFG.hidden)
    np.testing.assert_array_equal(zs[:, :CFG.conv_taps - 1], 0)
    tail, parts = None, []
    for a, b in zip((0, *cuts), (*cuts, 24)):
        out, zs = L.conv_block(p, x[:, a:b], CFG, tail)
        tail = zs[:, -(CFG.conv_taps - 1):]
        parts.append(out)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole,
                               rtol=1e-5, atol=1e-5)


def test_conv_is_causal_and_rows_do_not_mix():
    p, _ = L.init_conv(jax.random.PRNGKey(1), CFG)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, CFG.hidden))
    out, _ = L.conv_block(p, x, CFG)
    later = x.at[0, 7:].set(0.0)            # row 0's future; row 1 untouched
    moved, _ = L.conv_block(p, later, CFG)
    np.testing.assert_array_equal(moved[0, :7], out[0, :7])
    np.testing.assert_array_equal(moved[1], out[1])
    assert float(jnp.abs(moved[0, 7:] - out[0, 7:]).max()) > 0


# -- the stack ---------------------------------------------------------------------

def test_groups_are_whole_periods_of_the_pattern():
    groups = [(n, g.layer_kinds, g.n_layers, first)
              for n, g, first in layer_groups(CFG)]
    assert groups == [
        ("dense_layers", ("conv",), 1, 0),
        ("layers", ("attention", "conv", "conv", "conv"), 8, 1)]
    assert not layer_groups(CFG)[0][1].is_moe
    published = [(n, g.layer_kinds, g.n_layers, first)
                 for n, g, first in layer_groups(preset("lfm2-24b-a2b"))]
    assert published == [
        ("dense_layers", ("conv",), 2, 0),
        ("layers", ("attention", "conv", "conv", "conv"), 36, 2),
        ("layers_rest", ("attention", "conv"), 2, 38)]
    assert preset("lfm2-24b-a2b").kinds.count("attention") == 10
    plain = preset("tiny")
    assert layer_groups(plain) == [("layers", plain, 0)]
    with pytest.raises(ValueError, match="unknown layer kinds"):
        preset("tiny", layer_kinds=("conv", "mamba"))


def test_the_tree_stacks_an_operator_over_the_layers_of_its_kind():
    layers = PARAMS["layers"]
    assert layers["attn"]["wq"].shape[0] == 2
    assert layers["attn"]["q_norm"].shape == (2, CFG.head_dim)
    assert layers["conv"]["win"].shape == (6, CFG.hidden, 3, CFG.hidden)
    assert layers["ln1"].shape[0] == layers["mlp"]["router"].shape[0] == 8
    assert set(PARAMS["dense_layers"]) == {"conv", "mlp", "ln1", "ln2"}
    assert jax.tree.structure(jax.tree.map(lambda a: 0, PARAMS)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, decoder_param_specs(CFG),
            is_leaf=lambda s: isinstance(s, tuple)))
    assert sum(x.size for x in jax.tree.leaves(PARAMS)) == CFG.num_params()


def test_scanned_and_looped_stacks_agree_and_the_loss_has_gradients():
    looped_cfg = dataclasses.replace(CFG, scan_layers=False)
    looped = init_decoder_params(jax.random.PRNGKey(7), looped_cfg)
    tokens = _tokens(1, 26).reshape(2, 13)
    a = decoder_forward(PARAMS, jnp.asarray(tokens), CFG)[0]
    b = decoder_forward(looped, jnp.asarray(tokens), looped_cfg)[0]
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    grads = jax.grad(lambda p: decoder_loss(p, jnp.asarray(tokens), CFG)[0])(
        PARAMS)
    for leaf in (grads["layers"]["conv"]["taps"],
                 grads["layers"]["attn"]["q_norm"],
                 grads["dense_layers"]["conv"]["win"]):
        assert float(jnp.abs(leaf).sum()) > 0


# -- the pool ----------------------------------------------------------------------

def test_the_pool_holds_each_kind_of_layer_its_own_planes():
    assert pool_planes(CFG) == (
        ("k", (CFG.n_kv_heads * CFG.head_dim,), jnp.dtype("float32")),
        ("v", (CFG.n_kv_heads * CFG.head_dim,), jnp.dtype("float32")))
    assert state_planes(CFG) == (
        ("conv", (CFG.conv_taps - 1, CFG.hidden), jnp.dtype("float32")),)
    assert state_planes(preset("tiny")) == ()
    shapes = {n: s for n, (s, _) in pool_shapes(CFG, POOL, PAGE).items()}
    assert shapes == {"k": (2, POOL, PAGE, 32), "v": (2, POOL, PAGE, 32),
                      "conv": (7, POOL, 2, 64)}
    assert pool_bytes_per_token(CFG) == 2 * 2 * 32 * 4
    assert state_bytes_per_page(CFG) == 7 * 2 * 64 * 4
    real = preset("lfm2-24b-a2b", n_layers=9, leading_dense_layers=1,
                  layer_kinds=CFG.layer_kinds, dtype="bfloat16")
    assert pool_bytes_per_token(real) == 4096
    assert state_bytes_per_page(real) == 56 * 1024
    plain = preset("tiny")
    assert {n: s for n, (s, _) in pool_shapes(plain, 5, 16).items()} == {
        "k": (2, 5, 16, 2, 16), "v": (2, 5, 16, 2, 16)}
    with pytest.raises(ValueError, match="packed"):
        pool_planes(CFG, kv_quant=True)


def test_copy_pages_takes_a_pages_state_with_its_rows():
    cache = {n: jax.random.normal(jax.random.PRNGKey(i), p.shape)
             for i, (n, p) in enumerate(_empty_pool().items())}
    out = copy_pages(cache, jnp.asarray([3, 4]), jnp.asarray([9, -1]))
    for n in cache:
        np.testing.assert_array_equal(out[n][:, 9], cache[n][:, 3])
        np.testing.assert_array_equal(out[n][:, 4], cache[n][:, 4])


# -- the programs against the full forward ------------------------------------------

@pytest.mark.parametrize("plen,chunk", [(29, 16), (16, 16), (37, 8), (5, 16)])
def test_chunked_prefill_is_the_full_forward(plen, chunk):
    tokens = _tokens(plen, plen)
    row = np.asarray([7, 3, 9, 1, 4, -1, -1, -1], np.int32)
    logits, _ = _prefill(_empty_pool(), tokens, row, plen, chunk)
    np.testing.assert_allclose(logits, _full(tokens), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("plen", [29, 16, 1])
def test_decode_after_prefill_is_the_full_forward(impl, plen):
    tokens = _tokens(100 + plen, plen + 12)
    row = np.asarray([7, 3, 9, 1, 4, 12, -1, -1], np.int32)
    _, cache = _prefill(_empty_pool(), tokens, row, plen)
    logits, _ = _decode(cache, tokens, row, plen, 12, impl)
    np.testing.assert_allclose(logits, _full(tokens)[plen:], rtol=3e-4,
                               atol=3e-4)


def test_a_chunk_leaves_each_page_the_state_it_ends_in():
    """Pages 0 and 1 are filled (their ends' states), page 2 holds the last
    valid token's; a later sequence that maps the two whole pages and
    prefills on from position 16 reads exactly the logits of prefilling all
    of it, and the unmapped and dead rows wrote nothing."""
    tokens = _tokens(3, 30)
    row = np.asarray([5, 6, 7, 8, -1, -1, -1, -1], np.int32)
    empty = _empty_pool()
    alone, cache = _prefill(empty, tokens, row, 21)
    touched = np.flatnonzero(np.abs(np.asarray(cache["conv"])).sum(
        axis=(0, 2, 3)))
    assert touched.tolist() == [5, 6, 7]
    other = np.concatenate([tokens[:16], _tokens(4, 14)])
    shared_row = np.asarray([5, 6, 10, 11, -1, -1, -1, -1], np.int32)
    resumed, _ = _prefill(cache, other, shared_row, 30, start=16)
    whole, _ = _prefill(empty, other, np.asarray(
        [12, 13, 14, 15, -1, -1, -1, -1], np.int32), 30)
    np.testing.assert_allclose(resumed, whole[16:], rtol=3e-4, atol=3e-4)


def test_dead_rows_and_padding_write_no_state():
    tokens = _tokens(5, 10)
    block = np.zeros((2, CHUNK), np.int32)
    block[0, :10] = tokens
    table = np.full((2, MPP), -1, np.int32)
    table[0, :2] = [2, 3]
    _, cache = paged_chunk_prefill(
        PARAMS, _empty_pool(), jnp.asarray(block), jnp.asarray(table),
        jnp.zeros((2,), jnp.int32), jnp.asarray([10, 0], jnp.int32), CFG,
        context_pages=MPP)
    for name, axes in (("conv", (0, 2, 3)), ("k", (0, 2, 3))):
        touched = np.flatnonzero(np.abs(np.asarray(cache[name])).sum(
            axis=axes))
        assert touched.tolist() == [2, 3], name
    assert float(jnp.abs(cache["k"][:, 3, 2:]).sum()) == 0   # past valid_len


# -- through the engine -------------------------------------------------------------

def _engine(**kw):
    spec = dict(max_batch_size=4, max_seq_len=128, page_size=16,
                chunked_prefill_tokens=32, max_pages=32, decode_steps=4)
    return LLMEngine(CFG, BatchingSpec(**{**spec, **kw}), params=PARAMS,
                     seed=0)


@functools.lru_cache(maxsize=None)
def _greedy(prompt: tuple, n: int) -> list:
    """``n`` greedy tokens behind ``prompt`` by full recompute: ONE program
    at a fixed length (the stack is causal, so what lies behind a position
    cannot move its logits), once a prompt for all the cases."""
    forward = _padded_forward()
    tokens = list(prompt)
    for _ in range(n):
        block = np.zeros((96,), np.int32)
        block[:len(tokens)] = tokens
        tokens.append(int(jnp.argmax(forward(jnp.asarray(block))[
            len(tokens) - 1])))
    return tokens[len(prompt):]


@functools.lru_cache(maxsize=None)
def _padded_forward():
    return jax.jit(lambda t: decoder_forward(PARAMS, t[None], CFG)[0][0])


def _serve(engine, prompts, n=10):
    reqs = [engine.submit(p, SamplingParams(max_new_tokens=n,
                                            temperature=0.0))
            for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    return [r.result(1) for r in reqs]


PROMPTS = [[int(t) for t in _tokens(40, 40)] + [int(t) for t in _tokens(s, n)]
           for s, n in ((41, 10), (42, 21), (43, 30), (44, 3), (45, 5))]


@pytest.mark.parametrize("case,kw", [
    ("plain", dict(enable_prefix_caching=False)),
    ("radix-whole-pages", dict()),
    ("flat-whole-pages", dict(prefix_index="flat")),
    ("preempted", dict(max_pages=9)),
    ("one-row-programs", dict(max_concurrent_prefills=1)),
])
def test_engine_tokens_are_the_full_recomputes(case, kw):
    engine = _engine(**kw)
    got = _serve(engine, PROMPTS)
    assert got == [_greedy(tuple(p), 10) for p in PROMPTS]
    counters = engine.counters()
    if case == "preempted":
        assert counters["preemptions"] > 0
    if case.endswith("whole-pages"):
        stats = engine._allocator.stats
        assert stats["prefix_hits"] >= 2
        # 40 shared tokens: two whole pages of 16 are reused, never 2.5
        assert engine.metrics.snapshot().get("prefix_tokens_reused", 32) \
            % 16 == 0
    assert counters["state_tail_writes"] > 0
    engine._allocator.assert_quiescent()


def test_a_prefix_match_over_conv_layers_ends_at_a_page_boundary():
    engine = _engine()
    _serve(engine, [PROMPTS[0]], n=2)
    req = engine.submit(PROMPTS[1], SamplingParams(max_new_tokens=1))
    pages, covered = engine._kv_match(req)
    assert covered == 32 and len(pages) == 2        # 40 shared: not 40
    engine._allocator.free(pages)
    req.cancel()


def test_counters_exist_from_construction_and_only_grow():
    engine = _engine()
    before = engine.counters()
    shapes = pool_shapes(CFG, 32, 16)
    state = int(np.prod(shapes["conv"][0])) * 4
    assert before["state_pool_bytes"] == state
    assert before["kv_pool_bytes"] == state + 2 * int(
        np.prod(shapes["k"][0])) * 4
    assert before["kv_bytes_per_token"] == pool_bytes_per_token(CFG)
    assert before["state_tail_writes"] == 0
    _serve(engine, PROMPTS[:2], n=3)
    after = engine.counters()
    assert set(after) == set(before)
    assert all(after[k] >= before[k] for k in before)
    # 50 and 61 prompt tokens over pages of 16, prefilled side by side
    # (neither finds the other's pages yet): 4 + 4 page-end tails
    assert after["state_tail_writes"] == 4 + 4
    plain = LLMEngine(preset("tiny"), BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=16,
        chunked_prefill_tokens=16))
    assert plain.counters()["state_pool_bytes"] == 0
    assert plain.counters()["state_tail_writes"] == 0


REFUSED = {
    "int8 KV": dict(kv_cache_dtype="int8"),
    "handoff": dict(role="prefill"),
    "host tier": dict(host_kv_pages=8),
    "speculative": dict(speculative=SpeculativeSpec(mode="ngram")),
    "LoRA": dict(lora=LoRASpec(max_adapters=2)),
    "weight quantization": dict(quantize="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_an_option_that_does_not_take_this_model_is_refused_by_name(what):
    with pytest.raises(ValueError) as err:
        _engine(**REFUSED[what])
    message = str(err.value)
    assert "convolution layers whose state lives in the page pool" in message
    assert "K/V heads packed into one pool row" in message
    assert "leading dense layers" in message
    assert what in message


def test_a_mesh_is_refused_by_name():
    from kubeflow_tpu.runtime.mesh import build_mesh

    mesh = build_mesh({"model": 2}, jax.devices()[:2])
    with pytest.raises(ValueError, match="a mesh"):
        LLMEngine(CFG, BatchingSpec(max_batch_size=2, max_seq_len=64,
                                    page_size=16, chunked_prefill_tokens=16),
                  params=PARAMS, mesh=mesh)
