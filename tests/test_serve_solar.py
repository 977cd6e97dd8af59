"""Gated delta-rule linear-attention layers (KDA) whose state a SEQUENCE lives
in the page pool, beside gated global attention without position and an
expert layer that holds a share of its experts (Solar-Open2's structure), on
the normal path at the tiny preset on the CPU: the chunked operator against
the token-by-token recurrence (several block counts, a start state, ragged
lengths, decays that underflow a block), both kernels interpreted against the
XLA forms, the stack's tree and counts, the pool's planes by kind and the
entry found at ``table_row[0]``, the chunk programs (gathered, and in place
at heads of 128) and the decode step against the full forward, two sequences
interleaved, the shares of all chips adding up to the uncut layer, and
through the engine: tokens against the full recompute, preemption, the
counters, the ``/metrics`` family and the refused options by name."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import DecoderConfig, preset
from kubeflow_tpu.models.decoder import (
    LINEAR_PLANES, decoder_forward, decoder_param_specs, init_decoder_params,
    layer_groups, plane_kind,
)
from kubeflow_tpu.ops import kda
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    MOE_ROWS, _paged_decode_step, copy_pages, engine_pool_shapes,
    own_first_pages, paged_chunk_prefill, pool_bytes_per_token, pool_shapes,
    sequence_planes, state_bytes_per_sequence,
)

PAGE, CHUNK, MPP, SLOTS = 8, 16, 16, 3
BASE = preset("tiny-solar", dtype="float32", param_dtype="float32")
PARAMS = init_decoder_params(jax.random.PRNGKey(11), BASE)


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


# -- the operator ------------------------------------------------------------------

def _operands(seed, b, s, h, dk, strong=False):
    """Seeded q (normalised, scaled), k (normalised), v, log-decays a channel
    (down to -33 a token where ``strong``: a block of 64 then loses e^-2000)
    and beta in (0, 2), with a start state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, dk), minval=-7.0,
                                    maxval=3.5 if strong else 0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return (q, k, jax.random.normal(ks[2], (b, s, h, dk)), g, beta,
            jax.random.normal(ks[5], (b, h, dk, dk)))


def _token_by_token(q, k, v, g, beta, state):
    """The recurrence itself, a ``lax.scan`` over positions."""
    def one(s, xs):
        o, s = kda.kda_step_xla(*xs, s)
        return s, o

    state, o = jax.lax.scan(
        one, state, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), state


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("s,block,sub,strong", [
    (64, 64, 16, False),        # one block of four sub-blocks
    (128, 64, 16, True),        # two blocks, decays that underflow a block
    (48, 16, 16, False),        # three blocks of one sub-block
    (100, 64, 16, True),        # a ragged length: padded to whole blocks
    (32, 32, 8, False),
    (7, 64, 16, True),          # shorter than a sub-block
])
def test_the_chunked_form_is_the_recurrence(impl, s, block, sub, strong):
    """From a start state to an end state, the XLA form and the kernel
    (interpreted), against the token-by-token scan."""
    args = _operands(s, 2, s, 3, 16, strong)
    want_o, want_s = _token_by_token(*args)
    o, state = kda.kda_chunk(*args, impl=impl, block=block, sub=sub)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=1e-4, atol=5e-5)
    if strong and s >= block:   # a channel lost more than float32 holds
        assert float(jnp.min(jnp.sum(args[3][:, :min(s, block)], 1))) < -104


def test_a_block_whose_decay_underflows_stays_finite_and_exact():
    """Every channel of every head loses e^-50 a token: ``k / e^G`` would be
    inf after two tokens; the differences are at most 1 and the result is the
    recurrence's (the state is forgotten at once, each token reads itself)."""
    q, k, v, g, beta, s0 = _operands(3, 1, 64, 2, 16)
    g = jnp.full_like(g, -50.0)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
    o, state = kda.kda_chunk(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(state, want_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        o, (beta * jnp.sum(q * k, -1))[..., None] * v, rtol=1e-4, atol=1e-6)


def test_padding_leaves_the_state_as_it_was():
    """beta = 0 and g = 0 behind a row's valid length: the end state is the
    one after the valid positions, so no program needs a second form."""
    q, k, v, g, beta, s0 = _operands(4, 2, 32, 2, 16)
    valid = jnp.arange(32)[None, :] < jnp.asarray([[20], [32]])
    _, want = kda.kda_chunk(q[:1, :20], k[:1, :20], v[:1, :20], g[:1, :20],
                            beta[:1, :20], s0[:1])
    _, got = kda.kda_chunk(q, k, v, jnp.where(valid[..., None, None], g, 0),
                           jnp.where(valid[..., None], beta, 0), s0)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)


def _chunk_case(s, block, sub, strong):
    return _operands(s, 2, s, 3, 16, strong)[:5], block, sub


def _underflow_case():
    q, k, v, g, beta, _ = _operands(3, 1, 64, 2, 16)
    return (q, k, v, jnp.full_like(g, -50.0), beta), 64, 16


def _padded_rows_case():
    q, k, v, g, beta, _ = _operands(4, 2, 128, 2, 16, True)
    valid = jnp.arange(128)[None, :] < jnp.asarray([[20], [97]])
    return (q, k, v, jnp.where(valid[..., None, None], g, 0),
            jnp.where(valid[..., None], beta, 0)), 64, 16


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _chunk_case(64, 64, 16, False), id="one-block"),
    pytest.param(lambda: _chunk_case(128, 64, 16, True), id="underflowing"),
    pytest.param(lambda: _chunk_case(48, 16, 16, False), id="three-blocks"),
    pytest.param(lambda: _chunk_case(100, 64, 16, True), id="ragged"),
    pytest.param(lambda: _chunk_case(32, 32, 8, False), id="block32-sub8"),
    pytest.param(lambda: _chunk_case(7, 64, 16, True), id="short"),
    pytest.param(lambda: _chunk_case(512, 64, 16, True), id="a-chunk-of-512"),
    pytest.param(_underflow_case, id="g-minus-50"),
    pytest.param(_padded_rows_case, id="padding-rows"),
])
def test_the_operands_kernel_is_block_operands(case):
    """``kda_operands`` (interpreted) against ``block_operands``: each of the
    six arrays the scan takes, on the chunked form's cases, where every
    channel loses e^-50 a token and behind a row's valid length."""
    args, block, sub = case()
    # the chunked form's own tolerances; its underflow test's where every
    # term off the diagonal is exactly zero
    tol = dict(rtol=1e-5, atol=1e-6) if case is _underflow_case \
        else dict(rtol=1e-4, atol=5e-5)
    args, block, sub = kda.whole_blocks(*args, block, sub)
    want = kda.block_operands(*(jnp.swapaxes(x, 1, 2) for x in args),
                              block, sub)
    got = kda.kda_operands(*args, block=block, sub=sub, interpret=True)
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].shape == want[name].shape, name
        assert bool(jnp.isfinite(got[name]).all()), name
        np.testing.assert_allclose(got[name], want[name], err_msg=name,
                                   **tol)


def test_the_step_kernel_moves_live_rows_state_in_place_and_no_other():
    b, h, dk = 5, 4, 16
    q, k, v, g, beta, _ = _operands(1, b, 1, h, dk)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    plane = jax.random.normal(jax.random.PRNGKey(2), (12, h, dk, dk))
    idx = jnp.asarray([7, 3, 12, 9, 0])
    fresh = jnp.asarray([False, True, False, False, False])
    live = jnp.asarray([True, True, False, True, False])
    want_o, want_p = kda.kda_step(q, k, v, g, beta, plane, idx, fresh, live)
    got_o, got_p = kda.kda_step(q, k, v, g, beta, plane, idx, fresh, live,
                                impl="pallas")
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    touched = np.flatnonzero(np.abs(np.asarray(got_p - plane)).reshape(
        12, -1).max(1) > 0)
    assert touched.tolist() == [3, 7, 9]           # the live rows' entries
    assert float(jnp.abs(got_o[jnp.asarray([2, 4])]).max()) == 0.0
    # a fresh row starts from zeros whatever its entry held
    zero_o, _ = kda.kda_step_xla(q[1:2], k[1:2], v[1:2], g[1:2], beta[1:2],
                                 jnp.zeros((1, h, dk, dk)))
    np.testing.assert_allclose(got_o[1], zero_o[0], rtol=1e-5, atol=1e-6)
    # no live row at all: nothing moves
    none_o, none_p = kda.kda_step(q, k, v, g, beta, plane, idx, fresh,
                                  live & False, impl="pallas")
    assert float(jnp.abs(none_p - plane).max()) == 0.0
    assert float(jnp.abs(none_o).max()) == 0.0


# -- the stack ---------------------------------------------------------------------

def test_groups_the_tree_and_the_counts():
    assert BASE.kinds == ("attention", "linear", "linear", "linear") * 2
    (name, gcfg, first), = layer_groups(BASE)
    assert (name, first, gcfg.n_layers, gcfg.period) == (
        "layers", 0, 8, ("attention", "linear", "linear", "linear"))
    stack = PARAMS["layers"]
    assert stack["attn"]["wq"].shape == (2, 64, 4, 16)
    assert stack["attn"]["wgate"].shape == (2, 64, 4, 16)
    assert stack["linear"]["wq"].shape == (6, 64, 4, 16)
    assert stack["linear"]["conv_k"].shape == (6, 4, 4, 16)
    assert stack["linear"]["wf1"].shape == (6, 64, 8)
    assert stack["linear"]["a_log"].shape == (6, 4)
    assert stack["mlp"]["gate"].shape == (8, 4, 64, 48)     # 4 of 16 held
    assert stack["mlp"]["router"].shape == (8, 64, 16)
    assert sum(x.size for x in jax.tree.leaves(PARAMS)) == BASE.num_params()
    specs = decoder_param_specs(BASE)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, PARAMS)) \
        == jax.tree.structure(jax.tree.map(
            lambda _: 0, specs, is_leaf=lambda s: isinstance(s, tuple)))
    # the decay starts where the FLA initialisation puts it
    a = np.exp(np.asarray(stack["linear"]["a_log"]))
    step = np.asarray(jax.nn.softplus(stack["linear"]["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01
    # the published widths: a KDA mixer 137.7 M, the gated GQA mixer 109.1 M
    full = preset("solar-open2-250b")
    assert full._linear_params() == 137_732_288
    assert full._attn_params() == 109_051_904
    with pytest.raises(ValueError, match="linear layers need"):
        DecoderConfig(layer_kinds=("linear",))


def test_a_global_layer_carries_no_position_and_its_output_is_gated():
    """No window layer in the stack and ``rope_window_only``: nothing
    rotates (order reaches attention through the linear layers alone); the
    gate is elementwise on the attention output."""
    a = jax.tree.map(lambda x: x[0], PARAMS["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 64))
    pos = jnp.arange(9)[None]
    out, _ = L.attention_block(a, x, pos, BASE)
    moved, _ = L.attention_block(a, x, pos + 1000, BASE)
    np.testing.assert_allclose(out, moved, rtol=1e-6, atol=1e-6)
    ungated, _ = L.attention_block(
        {n: w for n, w in a.items() if n != "wgate"}, x, pos, BASE)
    assert float(jnp.abs(out - ungated).max()) > 1e-2
    attn = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 4, 16))
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", x, a["wgate"]))
    np.testing.assert_allclose(L.gate_attention(a, x, attn, BASE),
                               attn * gate, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        L.gate_attention(a, x, jnp.swapaxes(attn, 1, 2), BASE, heads_axis=1),
        jnp.swapaxes(attn * gate, 1, 2), rtol=1e-6, atol=1e-6)


def test_the_layer_is_the_equations_token_by_token():
    """``kda_block`` over a sequence against the equations written out a
    token at a time in numpy-like steps (convolution by hand, L2 norms,
    gates), from zeros."""
    p = jax.tree.map(lambda x: x[0], PARAMS["layers"]["linear"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 21, 64))
    out, (mat, tails) = L.kda_block(p, x, BASE)
    h, dk, taps = 4, 16, 4
    proj = {n: jnp.einsum("sd,dhk->shk", x[0], p["w" + n]) for n in "qkv"}
    state, want = jnp.zeros((h, dk, dk)), []
    for t in range(21):
        c = {}
        for n in "qkv":
            rows = [proj[n][t - j] if t - j >= 0 else jnp.zeros((h, dk))
                    for j in range(taps)]       # rows[0]: the current one
            c[n] = jax.nn.silu(sum(p["conv_" + n][taps - 1 - j] * rows[j]
                                   for j in range(taps)))
        q = c["q"] / jnp.sqrt(jnp.sum(c["q"] ** 2, -1, keepdims=True) + 1e-6)
        k = c["k"] / jnp.sqrt(jnp.sum(c["k"] ** 2, -1, keepdims=True) + 1e-6)
        q = q * dk ** -0.5
        f = jnp.einsum("r,rhk->hk", x[0, t] @ p["wf1"], p["wf2"])
        a = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                    * jax.nn.softplus(f + p["dt_bias"]))
        beta = 2 * jax.nn.sigmoid(x[0, t] @ p["wb"])
        state = a[..., None] * state
        state = state - (beta[:, None] * k)[..., None] * jnp.einsum(
            "hk,hkv->hv", k, state)[:, None, :] \
            + (beta[:, None] * k)[..., None] * c["v"][:, None, :]
        o = jnp.einsum("hk,hkv->hv", q, state)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + BASE.norm_eps) * p["o_norm"]
        gate = jax.nn.sigmoid(jnp.einsum(
            "r,rhk->hk", x[0, t] @ p["wg1"], p["wg2"]))
        want.append(jnp.einsum("hk,hkd->d", o * gate, p["wo"]))
    np.testing.assert_allclose(out[0], jnp.stack(want), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(mat[0], state, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        tails[0].reshape(3, 3, h, dk),
        jnp.stack([proj[n][-3:] for n in "qkv"]), rtol=1e-6, atol=1e-6)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer: the parts
    they compute, the shared expert counted ONCE, are the uncut layer's
    result; each routes over all 16."""
    whole = dataclasses.replace(BASE, experts_held=0)
    p, _ = L.init_moe(jax.random.PRNGKey(3), whole)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    want, _ = L.moe_block(p, x, whole)
    shared = L.mlp_block(p["shared"], x, whole)
    parts, held = [], 0
    for chip in range(4):
        cfg = dataclasses.replace(whole, experts_held=4,
                                  expert_offset=4 * chip)
        own = {**p, **{n: p[n][4 * chip:4 * chip + 4]
                       for n in L.EXPERT_LEAVES}}
        out, _, rows = L.moe_block(own, x, cfg, rows_out=True)
        assert int(rows[0]) == 2 * 24 * 4
        held += int(rows[1])
        parts.append(out - shared)
    assert held == 2 * 24 * 4           # every routed row is held somewhere
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-5)


# -- the pool ----------------------------------------------------------------------

def _empty_pool(cfg=BASE):
    return {n: jnp.zeros(shape, dt) for n, (shape, dt) in
            engine_pool_shapes(cfg, SLOTS, SLOTS * MPP, PAGE).items()}


def test_the_pool_holds_a_sequences_state_in_planes_of_its_own():
    assert [n for n, _, _ in sequence_planes(BASE)] == list(LINEAR_PLANES)
    assert all(plane_kind(n) == "linear" for n in LINEAR_PLANES)
    assert sequence_planes(preset("tiny")) == ()
    assert own_first_pages(BASE) == 1 and own_first_pages(preset("tiny")) == 0
    both = dataclasses.replace(
        preset("tiny-exaone"), window_ring_pages=5, linear_heads=4,
        linear_head_dim=16, linear_gate_rank=8,
        layer_kinds=("window", "linear", "attention", "window", "linear"))
    assert own_first_pages(both) == 5       # one range serves both kinds
    shapes = {n: s for n, (s, _) in
              engine_pool_shapes(BASE, SLOTS, 48, PAGE).items()}
    assert shapes == {"k": (2, 48, PAGE, 2, 16), "v": (2, 48, PAGE, 2, 16),
                      "kda_state": (6, SLOTS, 4, 16, 16),
                      "kda_conv": (6, SLOTS, 9, 64), MOE_ROWS: (2,)}
    dtypes = {n: dt for n, (_, dt) in pool_shapes(BASE, 48, PAGE).items()}
    assert dtypes["kda_state"] == jnp.float32       # whatever the activations
    assert pool_shapes(dataclasses.replace(BASE, dtype="bfloat16"), 48,
                       PAGE)["kda_conv"][1] == jnp.bfloat16
    # a token keeps rows in the two attention layers only; a sequence its
    # state in the six linear ones, whatever its length
    assert pool_bytes_per_token(BASE) == 2 * 2 * 2 * 16 * 4
    assert state_bytes_per_sequence(BASE) == 6 * (4 * 16 * 16 + 9 * 64) * 4
    full = dataclasses.replace(preset("solar-open2-250b"), n_layers=4)
    assert state_bytes_per_sequence(full) == 3 * (
        64 * 128 * 128 * 4 + 9 * 8192 * 2) == 13_025_280
    assert pool_bytes_per_token(full) == 4096


@functools.lru_cache(maxsize=None)
def _programs(cfg, impl, params_key=11):
    params = PARAMS if cfg is BASE else init_decoder_params(
        jax.random.PRNGKey(params_key), cfg)
    chunk = jax.jit(lambda c, t, rows, st, vl: paged_chunk_prefill(
        params, c, t, rows, st, vl, cfg, context_pages=MPP,
        paged_attn_impl=impl))
    step = jax.jit(lambda c, table, t, ln, lv: _paged_decode_step(
        params, {**c, "table": table}, t, ln, lv, cfg, attn_impl=impl))
    return params, chunk, step


def _full(cfg, params, tokens):
    return decoder_forward(params, jnp.asarray(tokens)[None], cfg)[0][0]


def _prefill(cfg, cache, tokens, row, plen, impl="gather", start=0):
    out = []
    for pos in range(start, plen, CHUNK):
        real = min(CHUNK, plen - pos)
        block = np.zeros((1, CHUNK), np.int32)
        block[0, :real] = tokens[pos:pos + real]
        logits, cache = _programs(cfg, impl)[1](
            cache, jnp.asarray(block), jnp.asarray(row)[None],
            jnp.asarray([pos], jnp.int32), jnp.asarray([real], jnp.int32))
        out.append(logits[0, :real])
    return jnp.concatenate(out), cache


def _decode(cfg, cache, tokens, row, plen, n, impl="gather", slot=1):
    table = np.full((SLOTS, MPP), -1, np.int32)
    table[slot] = row
    live = jnp.asarray(np.arange(SLOTS) == slot)
    out = []
    for i in range(n):
        tok = np.zeros((SLOTS,), np.int32)
        lens = np.zeros((SLOTS,), np.int32)
        tok[slot], lens[slot] = tokens[plen + i], plen + i
        logits, cache = _programs(cfg, impl)[2](
            cache, jnp.asarray(table), jnp.asarray(tok), jnp.asarray(lens),
            live)
        cache.pop("table")
        out.append(logits[slot])
    return jnp.stack(out), cache


def _row(first: int, pages: int = MPP) -> np.ndarray:
    """A page-table row whose first page (the sequence's state entry) is
    ``first`` and whose other pages come from above the entries' ids."""
    row = np.full((MPP,), -1, np.int32)
    row[:pages] = [first] + list(range(SLOTS + first * MPP,
                                       SLOTS + first * MPP + pages - 1))
    return row


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("plen", [13, 64, 101])
def test_chunked_prefill_then_decode_is_the_full_forward(impl, plen):
    """Logits through the pool, the state carried chunk to chunk and step to
    step at ``table_row[0]``, over a dirty pool (whatever an entry held
    before a sequence's start is not read); "pallas": the step kernel
    interpreted."""
    tokens = _tokens(plen, plen + 10)
    want = _full(BASE, PARAMS, tokens)
    dirty = {n: (jnp.full_like(a, 3.0) if n in LINEAR_PLANES else a)
             for n, a in _empty_pool().items()}
    row = _row(2)
    got, cache = _prefill(BASE, dirty, tokens, row, plen, impl)
    np.testing.assert_allclose(got, want[:plen], rtol=3e-4, atol=3e-4)
    got, cache = _decode(BASE, cache, tokens, row, plen, 10, impl)
    np.testing.assert_allclose(got, want[plen:], rtol=3e-4, atol=3e-4)
    # entries 0 and 1 were nobody's: untouched
    for n in LINEAR_PLANES:
        assert float(jnp.abs(cache[n][:, :2] - 3.0).max()) == 0.0


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_the_end_state_is_the_recurrences_and_a_bfloat16_plane_would_not_be(
        impl):
    """The number the STATE decides (the benchmark's ``correct`` compares
    logits, which bfloat16 activations blur ten times more than a rounded
    state does): a sequence's entry after 101 tokens through the chunk
    programs, seven chunks that each carry the state on, against the entry
    the same tokens leave when every one goes through the decode step from
    length 0, which is the recurrence token by token. Float32 holds them
    together to 1e-5 of the state's norm (1.3e-6 read); the same entry
    rounded to bfloat16 once, what a plane of half the bytes would hold,
    stands a thousand times farther off (1.7e-3)."""
    tokens, row = _tokens(23, 101), _row(1)
    _, chunked = _prefill(BASE, _empty_pool(), tokens, row, 101, impl)
    _, stepped = _decode(BASE, _empty_pool(), tokens, row, 0, 101, impl)

    def apart(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    want = stepped["kda_state"][:, 1]
    assert float(jnp.linalg.norm(want)) > 1.0
    assert apart(chunked["kda_state"][:, 1], want) < 1e-5
    assert apart(chunked["kda_conv"][:, 1], stepped["kda_conv"][:, 1]) < 1e-5
    rounded = chunked["kda_state"][:, 1].astype(jnp.bfloat16)
    assert apart(rounded.astype(jnp.float32), want) > 1e-3


def test_the_state_is_found_through_the_harnesss_arange_row():
    """``benchmark/correctness.py::engine_logits`` hands the programs ONE
    row ``arange(pages)`` and no slot: the entry is 0, and a second sequence
    through the same row starts from zeros again."""
    for seed in (1, 2):
        tokens = _tokens(seed, 40)
        row = np.full((MPP,), -1, np.int32)
        row[:6] = np.arange(6)
        cache = _empty_pool() if seed == 1 else cache   # noqa: F821
        got, cache = _prefill(BASE, cache, tokens, row, 36)
        dec, cache = _decode(BASE, cache, tokens, row, 36, 4, slot=0)
        want = _full(BASE, PARAMS, tokens)
        np.testing.assert_allclose(jnp.concatenate([got, dec]), want,
                                   rtol=3e-4, atol=3e-4)


def test_two_sequences_interleaved_chunk_by_chunk_keep_their_own_states():
    """A's chunk, B's chunk, A's next ...: one program a chunk, then both in
    the two-row program with a dead row beside them; every sequence reads
    the logits it reads alone."""
    ta, tb = _tokens(7, 48), _tokens(8, 48)
    ra, rb = _row(0, 7), _row(2, 7)
    cache, got = _empty_pool(), {"a": [], "b": []}
    _, chunk, _ = _programs(BASE, "gather")
    for pos in (0, 16):
        for name, toks, row in (("a", ta, ra), ("b", tb, rb)):
            lg, cache = chunk(cache, jnp.asarray(toks[None, pos:pos + 16]),
                              jnp.asarray(row)[None],
                              jnp.asarray([pos], jnp.int32),
                              jnp.asarray([16], jnp.int32))
            got[name].append(lg[0])
    # the third chunks together: rows (A, dead, B), B's ragged (12 valid)
    block = np.zeros((3, 16), np.int32)
    block[0], block[2, :12] = ta[32:48], tb[32:44]
    rows = np.full((3, MPP), -1, np.int32)
    rows[0], rows[2] = ra, rb
    before = cache
    lg, cache = chunk(cache, jnp.asarray(block), jnp.asarray(rows),
                      jnp.asarray([32, 0, 32], jnp.int32),
                      jnp.asarray([16, 0, 12], jnp.int32))
    got["a"].append(lg[0])
    got["b"].append(lg[2, :12])
    np.testing.assert_allclose(jnp.concatenate(got["a"]),
                               _full(BASE, PARAMS, ta), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(jnp.concatenate(got["b"]),
                               _full(BASE, PARAMS, tb[:44]), rtol=3e-4,
                               atol=3e-4)
    # the dead row wrote no entry (entry 1 is nobody's)
    for n in LINEAR_PLANES:
        np.testing.assert_array_equal(cache[n][:, 1], before[n][:, 1])
    # B decodes on from its ragged chunk's state
    dec, _ = _decode(BASE, cache, tb, rb, 44, 4, slot=2)
    np.testing.assert_allclose(dec, _full(BASE, PARAMS, tb)[44:], rtol=3e-4,
                               atol=3e-4)


def test_the_in_place_chunk_program_at_heads_of_128():
    """Heads of 128 take the chunk program built in place (the pool flat
    through the layer scans, the attention kernel over the pages, the KDA
    kernel over the blocks): against the gathered form and the full
    forward."""
    cfg = dataclasses.replace(
        BASE, n_layers=4, n_heads=2, n_kv_heads=1, head_dim=128,
        linear_heads=2, linear_head_dim=128, linear_gate_rank=16)
    params = _programs(cfg, "pallas")[0]
    tokens = _tokens(21, 40)
    want = _full(cfg, params, tokens)
    row = _row(1, 6)
    for impl in ("pallas", "gather"):
        got, cache = _prefill(cfg, _empty_pool(cfg), tokens, row, 37, impl)
        np.testing.assert_allclose(got, want[:37], rtol=2e-3, atol=2e-3)
        dec, _ = _decode(cfg, cache, tokens, row, 37, 3, impl)
        np.testing.assert_allclose(dec, want[37:], rtol=2e-3, atol=2e-3)
    from kubeflow_tpu.serve.paged import _chunk_in_place

    assert _chunk_in_place(_empty_pool(cfg), cfg, None, "pallas")
    assert not _chunk_in_place(_empty_pool(cfg), cfg, None, "gather")
    assert not _chunk_in_place(_empty_pool(), BASE, None, "pallas")


def test_a_first_pages_copy_carries_the_entry_and_no_other_copy_does():
    cache = {n: jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
             for i, (n, a) in enumerate(_empty_pool().items())
             if n != MOE_ROWS}
    out = copy_pages(cache, jnp.asarray([1, 5, 2]), jnp.asarray([0, 9, 7]))
    for n in LINEAR_PLANES:
        np.testing.assert_array_equal(out[n][:, 0], cache[n][:, 1])
        np.testing.assert_array_equal(out[n][:, 1:], cache[n][:, 1:])
    np.testing.assert_array_equal(out["k"][:, 9], cache["k"][:, 5])
    np.testing.assert_array_equal(out["k"][:, 7], cache["k"][:, 2])


# -- through the engine ------------------------------------------------------------

def _engine(**kw):
    spec = dict(max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                decode_steps=4, max_concurrent_prefills=2)
    return LLMEngine(BASE, BatchingSpec(**{**spec, **kw}), params=PARAMS)


@functools.lru_cache(maxsize=None)
def _full_padded():
    return jax.jit(lambda t: decoder_forward(PARAMS, t[None], BASE)[0][0])


def _greedy(prompt, n):
    toks, out = list(prompt), []
    for _ in range(n):
        padded = np.zeros((PAGE * MPP,), np.int32)
        padded[:len(toks)] = toks
        t = int(jnp.argmax(_full_padded()(jnp.asarray(padded))[len(toks) - 1]))
        out.append(t)
        toks.append(t)
    return out


def _serve(engine, prompts, n):
    reqs = [engine.submit([int(t) for t in p], SamplingParams(
        temperature=0.0, max_new_tokens=n)) for p in prompts]
    for _ in range(4000):
        if all(r.done.is_set() for r in reqs):
            break
        engine.step()
    return reqs


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_engine_tokens_are_the_full_recomputes(impl):
    """Four prompts on three slots: the two-row program, chunks interleaved
    with decode rounds, a slot (and its entry) handed to a second sequence."""
    engine = _engine(paged_attn_impl=impl)
    assert engine._plan.rows == 2
    prompts = [_tokens(31, 75), _tokens(32, 5), _tokens(33, 50),
               _tokens(34, 21)]
    reqs = _serve(engine, prompts, 12)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 12)
    engine._allocator.assert_quiescent()
    # every sequence's first page came from the entries' ids
    assert engine._ring == 1 and engine._window_pages == SLOTS
    assert engine._allocator.available(ring=True) == SLOTS


def test_a_preempted_sequence_starts_its_state_again_from_zeros():
    """A pool too small for three growing contexts: the youngest gives its
    pages back, prefills again from position 0 (its entry, whatever it
    holds, is not read) and every request reads the full recompute's
    tokens."""
    engine = _engine(max_pages=24)
    prompts = [_tokens(41, 60), _tokens(42, 62), _tokens(43, 58)]
    reqs = _serve(engine, prompts, 30)
    counters = engine.counters()
    assert counters["preemptions"] >= 1
    assert counters["state_sequences_started"] \
        == 3 + counters["preemptions"]
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 30)
    engine._allocator.assert_quiescent()


def test_counters_exist_from_construction_and_count_the_states_started():
    engine = _engine()
    before = engine.counters()
    sequence = SLOTS * state_bytes_per_sequence(BASE)
    assert before["kv_sequence_pool_bytes"] == sequence
    assert before["kv_token_pool_bytes"] == SLOTS * MPP * PAGE \
        * pool_bytes_per_token(BASE)
    assert before["kv_pool_bytes"] == before["kv_sequence_pool_bytes"] \
        + before["kv_token_pool_bytes"]
    assert before["kv_window_pages_a_sequence"] == 0
    assert before["state_sequences_started"] == 0
    _serve(engine, [_tokens(51, 40)], 9)       # three chunks, nine tokens
    after = engine.counters()
    assert set(after) == set(before)
    assert after["state_sequences_started"] == 1
    assert after["prefill_chunks_dispatched"] == 3
    # a stack without linear layers reads 0 everywhere
    plain = LLMEngine(preset("tiny"), BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=PAGE,
        chunked_prefill_tokens=CHUNK)).counters()
    assert plain["kv_sequence_pool_bytes"] == 0
    assert plain["state_sequences_started"] == 0
    assert plain["kv_token_pool_bytes"] == plain["kv_pool_bytes"]


def test_the_metrics_family_of_the_pool_carries_the_sequence_planes():
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.serve.server import ModelServer

    engine = _engine()
    server = ModelServer("m", engine)
    _serve(engine, [_tokens(52, 20)], 3)
    values = {(name, labels.get("planes") or labels.get("op")): v
              for name, labels, v in parse_exposition(server.metrics_text())
              if labels.get("model") == "m"}
    counters = engine.counters()
    assert values[("kftpu_engine_kv_pool_bytes", "sequence")] \
        == counters["kv_sequence_pool_bytes"]
    assert values[("kftpu_engine_kv_pool_bytes", "token")] \
        == counters["kv_token_pool_bytes"]
    assert values[("kftpu_engine_sequence_states_started_total", None)] == 1


REFUSED = [
    ("prefix reuse over linear-attention layers",
     {"enable_prefix_caching": True}),
    ("speculative verify", {"speculative": SpeculativeSpec(mode="ngram")}),
    ("int8 KV", {"kv_cache_dtype": "int8"}),
    ("handoff export/adopt", {"role": "prefill"}),
    ("the host tier's wire format", {"host_kv_pages": 8}),
    ("the host tier's wire format",
     {"host_kv_pages": 8, "remote_kv_root": "/tmp/none"}),
    ("LoRA targets", {"lora": None}),
    ("quantize=int8", {"quantize": "int8"}),
]


@pytest.mark.parametrize("what,kw", REFUSED)
def test_an_option_that_does_not_take_this_model_is_refused_by_name(what, kw):
    if "lora" in kw:
        from kubeflow_tpu.core.serving import LoRASpec

        kw = {"lora": LoRASpec(max_adapters=2)}
    with pytest.raises(ValueError, match="linear-attention layers") as err:
        _engine(**kw)
    assert what in str(err.value)
    assert "state a sequence lives in the page pool" in str(err.value)


def test_a_mesh_is_refused_by_name():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("model",))
    with pytest.raises(ValueError, match="linear-attention layers") as err:
        LLMEngine(BASE, BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=PAGE,
            chunked_prefill_tokens=CHUNK, enable_prefix_caching=False),
            params=PARAMS, mesh=mesh)
    assert "a mesh" in str(err.value)
