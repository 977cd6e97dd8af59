"""Mamba-1 selective-scan layers whose state a SEQUENCE lives in the page
pool, differential attention over window rings, ONE full-attention layer
whose pages the cross layers read, gated memory units, and a stateless tail
that runs only where logits are read (Phi-4-mini-flash's structure), on the
normal path at the tiny preset on the CPU: the scan's kernel interpreted
against its XLA form and the token-by-token recurrence, the padded-query
form of differential attention against two softmaxes, the stack's groups,
tree and counts, the pool's planes and the allocator's first-page ids, the
programs (gathered, and in place at pairs of 128) against the full forward
and against the benchmark's plain reference, the state an entry ends in, the
tail skipped exactly, and through the engine: tokens against the full
recompute, preemption, the counters and the refused options by name."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import PRESETS, preset
from kubeflow_tpu.models.decoder import (
    SSM_PLANES, decoder_forward, decoder_param_specs, init_decoder_params,
    layer_groups, plane_kind,
)
from kubeflow_tpu.ops import ssm
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    PageAllocator, _chunk_in_place, _paged_decode_step, copy_pages,
    engine_pool_shapes, first_page_ids, kept_as_rows, own_first_pages,
    paged_chunk_prefill,
    pool_bytes_per_token, ring_pages, sequence_planes,
    state_bytes_per_sequence,
)

PAGE, CHUNK, MPP, SLOTS = 8, 16, 16, 3
BASE = dataclasses.replace(
    preset("tiny-phi4flash", dtype="float32", param_dtype="float32"),
    window_ring_pages=4)        # ring_pages at a chunk of 16 and a page of 8
PARAMS = init_decoder_params(jax.random.PRNGKey(11), BASE)
# two differential pairs of 128 values a token: what the in-place chunk
# program takes, the planes kept as rows read by the strided form
WIDE = dataclasses.replace(BASE, n_heads=8, n_kv_heads=4, head_dim=64)
RING = 4


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


# -- the scan ------------------------------------------------------------------

def _scan_operands(seed, b, t, e, n, underflow=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, e)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (n, e), minval=0.0, maxval=2.8))
    if underflow:       # channel 5: Delta A below float32's smallest exp
        delta = delta.at[:, :, 5].set(40.0)
        a = a.at[:, 5].set(-16.0)
    return (jax.random.normal(ks[0], (b, t, e)), delta,
            jax.random.normal(ks[2], (b, t, n)),
            jax.random.normal(ks[3], (b, t, n)), a,
            jax.random.normal(ks[5], (e,)), jax.random.normal(ks[6], (b, n, e)))


def _token_by_token(x, delta, bm, cm, a, d, h0):
    """The recurrence as the plain reference walks it: one position at a
    time, the state laid ``[E, N]``."""
    ys, h = [], jnp.swapaxes(h0, 1, 2)
    for t in range(x.shape[1]):
        h = jnp.exp(delta[:, t, :, None] * a.T) * h \
            + (delta[:, t] * x[:, t])[:, :, None] * bm[:, t, None, :]
        ys.append(jnp.einsum("ben,bn->be", h, cm[:, t]) + d * x[:, t])
    return jnp.stack(ys, axis=1), jnp.swapaxes(h, 1, 2)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("t,e,n,underflow", [
    (24, 128, 4, False),        # one block of positions, one row of lanes
    (512, 256, 4, True),        # two blocks of 256 positions: the carry
    (40, 1024, 16, False),      # one whole block of 8 rows, 16 states
    (7, 2048, 2, True),         # two blocks of channels
    (16, 96, 4, False),         # not whole lanes: the XLA form either way
])
def test_the_scan_is_the_recurrence(impl, t, e, n, underflow):
    """From a start state to an end state, the XLA form and the kernel
    (interpreted), against the token-by-token walk; a channel whose ``Delta
    A`` underflows forgets its state at once and stays finite."""
    args = _scan_operands(t, 2, t, e, n, underflow)
    want_y, want_h = _token_by_token(*args)
    y, h = ssm.ssm_scan(*args, impl=impl)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    if underflow:
        assert float(jnp.exp(args[1][0, 0, 5] * args[4][0, 5])) == 0.0
        np.testing.assert_allclose(     # h = Delta x B: the last token's alone
            h[:, :, 5], (args[1][:, -1, 5] * args[0][:, -1, 5])[:, None]
            * args[2][:, -1], rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_padded_tail_leaves_the_state_as_it_was(impl):
    """Delta = 0 behind a row's valid length: the end state is the one after
    the valid positions, so no program needs a second form."""
    x, delta, bm, cm, a, d, h0 = _scan_operands(5, 2, 32, 128, 4)
    valid = jnp.arange(32)[None, :, None] < jnp.asarray([20, 32])[:, None,
                                                                  None]
    _, want = ssm.ssm_scan(x[:1, :20], delta[:1, :20], bm[:1, :20],
                           cm[:1, :20], a, d, h0[:1])
    _, got = ssm.ssm_scan(x, jnp.where(valid, delta, 0.0), bm, cm, a, d, h0,
                          impl=impl)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)


def test_the_block_is_the_scan_behind_its_inputs_whatever_the_split():
    """``ssm_block`` over 40 positions at once, and as 24 then 16 from the
    state and the convolution's tail the first left: the same output, the
    same end state, the same memory; a padded row's state is the one at its
    valid length."""
    p = jax.tree.map(lambda a: a[0], PARAMS["layers"]["ssm"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, BASE.hidden))
    out, (h, tail), m = L.ssm_block(p, x, BASE)
    o1, state, m1 = L.ssm_block(p, x[:, :24], BASE)
    o2, (h2, tail2), m2 = L.ssm_block(p, x[:, 24:], BASE, state)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), out, atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate([m1, m2], 1), m, atol=1e-5)
    np.testing.assert_allclose(h2, h, atol=1e-5)
    np.testing.assert_allclose(tail2, tail, atol=1e-6)
    _, (hp, tp), _ = L.ssm_block(p, x, BASE, valid_len=jnp.asarray([24, 40]))
    np.testing.assert_allclose(hp[0], state[0][0], atol=1e-5)
    np.testing.assert_allclose(tp[0], state[1][0], atol=1e-6)
    np.testing.assert_allclose(hp[1], h[1], atol=1e-5)


# -- differential attention ------------------------------------------------------

def test_the_padded_query_form_is_two_softmaxes_and_a_subtraction():
    """``diff_q`` / ``diff_kv`` through plain GQA of H heads over KV / 2 of
    twice the width, then ``diff_output``, against the definition: per query
    pair two softmaxes of ``head_dim``-wide heads at ``head_dim ** -0.5``,
    ``(A1 - lambda A2) [v[2j] | v[2j+1]]``, the pair's RMSNorm, ``1 -
    lambda_init``."""
    from kubeflow_tpu.ops.attention import multi_head_attention

    cfg = dataclasses.replace(BASE, n_heads=8, n_kv_heads=4)
    p, _ = L.init_diff_attention(jax.random.PRNGKey(5), cfg)
    p = {**p, "lambda_init": L.diff_lambda_init(3),
         "bq": jax.random.normal(jax.random.PRNGKey(6), p["bq"].shape),
         "subln": 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                              p["subln"].shape)}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.hidden))
    q, (k, v) = L.diff_q(p, x, cfg), L.diff_kv(p, x, cfg)
    assert q.shape == (2, 12, 8, 32) and k.shape == v.shape == (2, 12, 2, 32)
    got = L.diff_output(p, multi_head_attention(q, k, v, causal=True), cfg)

    dh = cfg.head_dim
    qs = (x @ p["wq"].T + p["bq"]).reshape(2, 12, 8, dh)
    ks = (x @ p["wk"].T + p["bk"]).reshape(2, 12, 4, dh)
    vs = (x @ p["wv"].T + p["bv"]).reshape(2, 12, 4, dh)
    lam = jnp.exp(p["lambda_q1"] @ p["lambda_k1"]) \
        - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + p["lambda_init"]
    mask = jnp.tril(jnp.ones((12, 12), bool))
    outs = []
    for pair in range(4):
        j = pair // 2
        def soft(qh, kh):
            s = jnp.einsum("bqd,bkd->bqk", qh, kh) / dh ** 0.5
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = soft(qs[:, :, 2 * pair], ks[:, :, 2 * j]) \
            - lam * soft(qs[:, :, 2 * pair + 1], ks[:, :, 2 * j + 1])
        o = a @ jnp.concatenate([vs[:, :, 2 * j], vs[:, :, 2 * j + 1]], -1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * p["subln"]
        outs.append(o * (1 - p["lambda_init"]))
    want = jnp.concatenate(outs, -1) @ p["wo"] + p["bo"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the stack -------------------------------------------------------------------

def _old_layer_groups(cfg):
    """``layer_groups`` as every PR up to 46 had it: whole periods of the
    stack's ONE shortest pattern, and a cut period behind them."""
    def period(kinds):
        return next(p for p in range(1, len(kinds) + 1)
                    if all(kinds[i] == kinds[i - p]
                           for i in range(p, len(kinds))))

    def periodic(name, cfg, first, n):
        kinds = cfg.kinds[first:first + n]
        p = period(kinds)
        whole = n // p * p
        out = [(name, dataclasses.replace(
            cfg, n_layers=whole, layer_kinds=kinds[:p]), first)]
        if whole < n:
            out.append((name + "_rest", dataclasses.replace(
                cfg, n_layers=n - whole, layer_kinds=kinds[whole:]),
                first + whole))
        return out

    k = cfg.leading_dense_layers
    if not k:
        return periodic("layers", cfg, 0, cfg.n_layers) \
            if cfg.layer_kinds else [("layers", cfg, 0)]
    return periodic("dense_layers", dataclasses.replace(
        cfg, num_experts=0, leading_dense_layers=0), 0, k) + periodic(
        "layers", dataclasses.replace(cfg, leading_dense_layers=0), k,
        cfg.n_layers - k)


def _benchmark_cuts():
    import glob
    import json

    out = {}
    for path in sorted(glob.glob("benchmark/configs/*.json")):
        prog = json.load(open(path))["program"]
        out[path] = preset(prog["preset"], **prog["overrides"])
    return out


# (a stack with blocks of one sublayer, PR 61, is cut by kind AND by whether a
# block has its feed-forward part: tests/test_models_nemotronh.py has its
# groups)
@pytest.mark.parametrize("name", sorted(
    n for n in PRESETS if "phi" not in n and "nemotron" not in n))
def test_every_other_presets_groups_are_what_they_were(name):
    assert layer_groups(PRESETS[name]) == _old_layer_groups(PRESETS[name])


def test_the_benchmarks_cuts_group_as_they_did():
    for path, cfg in _benchmark_cuts().items():
        if "phi" not in path and "nemotron" not in path:
            assert layer_groups(cfg) == _old_layer_groups(cfg), path


@pytest.mark.parametrize("name,want", [
    ("phi-4-mini-flash", [("layers", 16, ("ssm", "window"), 0),
                          ("layers_rest", 2, ("ssm", "attention"), 16),
                          ("layers_rest2", 14, ("gmu", "cross"), 18)]),
    ("tiny-phi4flash", [("layers", 4, ("ssm", "window"), 0),
                        ("layers_rest", 2, ("ssm", "attention"), 4),
                        ("layers_rest2", 4, ("gmu", "cross"), 6)]),
])
def test_a_stack_of_several_patterns_is_a_scan_a_pattern(name, want):
    cfg = preset(name)
    got = [(n, g.n_layers, g.layer_kinds, first)
           for n, g, first in layer_groups(cfg)]
    assert got == want
    assert cfg.stateless_tail == want[2][1]
    # one (gmu, cross) behind the pair still never shares its group
    short = dataclasses.replace(cfg, n_layers=want[0][1] + 4,
                                layer_kinds=cfg.kinds[:want[0][1] + 2]
                                + ("gmu", "cross"))
    assert [g.layer_kinds for _, g, _ in layer_groups(short)][-1] \
        == ("gmu", "cross")


def test_the_tree_its_count_and_what_a_config_refuses():
    shapes = jax.tree.map(lambda a: a.shape, PARAMS)
    assert set(shapes) == {"embed", "final_norm", "final_norm_b", "layers",
                           "layers_rest", "layers_rest2"}
    assert shapes["layers"]["ssm"]["a_log"] == (2, 4, 128)      # [N, E]
    assert shapes["layers_rest"]["attn"]["wk"] == (1, 32, 64)   # out by in
    assert "wk" not in shapes["layers_rest2"]["cross"]
    assert shapes["layers_rest2"]["gmu"]["w1"] == (2, 64, 128)
    held = sum(a.size for a in jax.tree.leaves(PARAMS))
    assert held == BASE.num_params() + 5       # lambda_init: a constant a layer
    np.testing.assert_allclose(
        PARAMS["layers_rest2"]["cross"]["lambda_init"],
        0.8 - 0.6 * np.exp(-0.3 * np.asarray([7, 9])), rtol=1e-6)
    specs = decoder_param_specs(BASE)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, PARAMS)) \
        == jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, tuple)))
    with pytest.raises(ValueError, match="stand behind"):
        dataclasses.replace(BASE, layer_kinds=("gmu", "cross", "ssm",
                                               "attention"), n_layers=4)
    with pytest.raises(ValueError, match="ssm_state"):
        dataclasses.replace(BASE, ssm_inner=0)
    with pytest.raises(ValueError, match="both counts even"):
        dataclasses.replace(BASE, n_kv_heads=1)
    with pytest.raises(ValueError, match="norm_kind"):
        dataclasses.replace(BASE, norm_kind="batch")


def test_the_published_widths_count_3_85_billion_by_part():
    cfg = preset("phi-4-mini-flash")
    d, e = 2560, 5120
    mamba = 2 * d * e + 5 * e + e * 192 + 160 * e + e + 16 * e + e + e * d
    attn = 2561 * (2560 + 2 * 1280) + 2561 * 2560 + 6 * 64
    cross = 2561 * 2560 + 2561 * 2560 + 6 * 64
    assert cfg._ssm_params() == mamba == 41_241_600
    assert cfg._attn_params() == attn
    total = (9 * mamba + 9 * attn + 7 * 2 * d * e + 7 * cross
             + 32 * (3 * d * 10240 + 4 * d) + 200064 * d + 2 * d)
    assert cfg.num_params() == total
    assert abs(total / 3.85e9 - 1) < 0.01


# -- the pool ----------------------------------------------------------------------

def _empty_pool(cfg=BASE, pages=48):
    return {n: jnp.zeros(s, dt) for n, (s, dt) in
            engine_pool_shapes(cfg, SLOTS, pages, PAGE).items()}


def test_the_pool_holds_one_layers_rows_a_ring_and_an_entry_a_slot():
    assert [n for n, _, _ in sequence_planes(BASE)] == list(SSM_PLANES)
    assert all(plane_kind(n) == "ssm" for n in SSM_PLANES)
    assert own_first_pages(BASE) == RING
    assert ring_pages(BASE, CHUNK, PAGE, MPP) == RING
    assert first_page_ids(BASE, SLOTS) == SLOTS
    assert first_page_ids(preset("tiny-solar"), SLOTS) == 0   # a ring of one
    shapes = {n: s for n, (s, _) in
              engine_pool_shapes(BASE, SLOTS, 48, PAGE).items()}
    # differential attention's planes are kept as rows: a pair a row (one
    # pair of 32 values here; 10 of 128 at the published widths)
    assert kept_as_rows(BASE) == 1 and kept_as_rows(preset("tiny")) == 0
    assert kept_as_rows(preset("phi-4-mini-flash")) == 10
    assert shapes == {
        "k": (1, 48, PAGE, 32), "v": (1, 48, PAGE, 32),     # ONE layer
        "window_k": (2, SLOTS * RING, PAGE, 32),
        "window_v": (2, SLOTS * RING, PAGE, 32),
        "ssm_state": (3, SLOTS, 4, 128), "ssm_conv": (3, SLOTS, 3, 128)}
    # seven layers attend (two windows, one full, two cross + ...): a token
    # keeps rows in ONE of them
    assert pool_bytes_per_token(BASE) == 2 * 32 * 4
    assert state_bytes_per_sequence(BASE) == 3 * (4 * 128 + 3 * 128) * 4
    full = preset("phi-4-mini-flash")
    assert pool_bytes_per_token(full) == 5120
    assert state_bytes_per_sequence(full) == 9 * (16 * 5120 * 4
                                                  + 3 * 5120 * 2)
    assert ring_pages(full, 512, 128, 65) == 9
    assert engine_pool_shapes(
        dataclasses.replace(full, window_ring_pages=9), 32, 2080,
        128)["k"][0] == (1, 2080, 1280, 128)


def test_the_allocator_hands_first_pages_from_ids_of_their_own():
    alloc = PageAllocator(48, PAGE, enable_prefix_caching=False,
                          ring_pages=SLOTS * RING, first_pages=SLOTS)
    a = alloc.alloc(6, ring=RING, first=True)
    assert a[0] < SLOTS and all(SLOTS <= p < SLOTS * RING for p in a[1:RING])
    assert all(p >= SLOTS * RING for p in a[RING:])
    b = alloc.alloc(2, ring=2, first=True)
    grown = alloc.alloc(3, ring=2)          # the same sequence grows
    assert b[0] < SLOTS and b[0] != a[0]
    assert all(SLOTS <= p < SLOTS * RING for p in (b[1], *grown[:2]))
    alloc.free(a)
    assert alloc.alloc(1, ring=1, first=True)[0] < SLOTS
    # the first pages run out with the slots and not before
    alloc.alloc(1, ring=1, first=True)
    with pytest.raises(Exception, match="ring"):
        alloc.alloc(1, ring=1, first=True)


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


def _prefill(cfg, cache, tokens, row, plen, impl="gather", start=0,
             chunk=CHUNK):
    out = []
    for pos in range(start, plen, chunk):
        real = min(chunk, plen - pos)
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
    """A page-table row as the engine's allocator would hand it: the first
    page from the first pages' ids, the rest of the ring from the ring's,
    the others from above."""
    ring = list(range(SLOTS + first * (RING - 1),
                      SLOTS + (first + 1) * (RING - 1)))
    rest = list(range(SLOTS * RING + first * MPP,
                      SLOTS * RING + (first + 1) * MPP))
    row = np.full((MPP,), -1, np.int32)
    row[:pages] = ([first] + ring + rest)[:pages]
    return row


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("plen", [13, 40, 101])
def test_chunked_prefill_then_decode_is_the_full_forward(impl, plen):
    """Logits through the pool: the state carried chunk to chunk and step to
    step at ``table_row[0]``, the window's ring over 0.5 to 6 pages at a
    window of a page, the cross layers over the ONE layer's pages; over a
    dirty pool (what an entry held before a sequence's start is not read).
    101 tokens are seven chunks whose boundaries fall on pages' ends; the
    decode steps cross one inside."""
    tokens = _tokens(plen, plen + 6)
    want = _full(BASE, PARAMS, tokens)
    dirty = {n: (jnp.full_like(a, 3.0) if n in SSM_PLANES else a)
             for n, a in _empty_pool(pages=80).items()}
    row = _row(2)
    got, cache = _prefill(BASE, dirty, tokens, row, plen, impl)
    np.testing.assert_allclose(got, want[:plen], rtol=3e-4, atol=3e-4)
    got, cache = _decode(BASE, cache, tokens, row, plen, 6, impl)
    np.testing.assert_allclose(got, want[plen:], rtol=3e-4, atol=3e-4)
    for n in SSM_PLANES:      # entries 0 and 1 were nobody's: untouched
        assert float(jnp.abs(cache[n][:, :2] - 3.0).max()) == 0.0


def test_chunks_that_end_inside_a_page_carry_the_state_too():
    """Chunks of 12 tokens: every boundary but one lies inside a page (the
    radix tail's resume; the scan starts from the entry whatever the
    alignment)."""
    tokens = _tokens(77, 46)
    want = _full(BASE, PARAMS, tokens)
    got, cache = _prefill(BASE, _empty_pool(pages=80), tokens, _row(0), 40,
                          chunk=12)
    np.testing.assert_allclose(got, want[:40], rtol=3e-4, atol=3e-4)
    got, _ = _decode(BASE, cache, tokens, _row(0), 40, 6, slot=0)
    np.testing.assert_allclose(got, want[40:], rtol=3e-4, atol=3e-4)


def _reference():
    from benchmark.manifest import load_module_file

    return load_module_file(
        "benchmark.architectures", "phi4flash.reference",
        "benchmark/architectures/phi4flash/reference.py")


REFERENCE_CONF = dict(
    hidden_size=64, num_hidden_layers=10, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=160, vocab_size=256,
    layer_norm_eps=1e-5, sliding_window=8, d_state=4, d_conv=4, expand=2,
    dt_rank=4, layer_types=["mamba", "sliding_attention"] * 2
    + ["mamba", "full_attention"] + ["gmu", "cross_attention"] * 2)


def test_the_program_is_the_plain_reference_on_logits():
    """The whole forward, and three chunks then six decode steps through the
    pool, against the benchmark's reference (token-by-token scan, two
    softmaxes, no skipped layer), which shares no code with the program."""
    tokens = _tokens(5, 46)
    with jax.default_matmul_precision("highest"):
        want = _reference().logits(PARAMS, jnp.asarray(tokens),
                                   REFERENCE_CONF)
    np.testing.assert_allclose(_full(BASE, PARAMS, tokens), want, rtol=3e-4,
                               atol=3e-4)
    got, cache = _prefill(BASE, _empty_pool(pages=80), tokens, _row(1), 40)
    dec, _ = _decode(BASE, cache, tokens, _row(1), 40, 6)
    np.testing.assert_allclose(jnp.concatenate([got, dec]), want, rtol=3e-4,
                               atol=3e-4)


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_the_entry_a_prompt_leaves_is_the_recurrences_state(impl):
    """A sequence's entry after 101 tokens through the chunk programs, seven
    chunks that each carry the state on, against the entry the same tokens
    leave when every one goes through the decode step from length 0, which
    is the recurrence token by token: 1e-5 of the state's norm in float32. A
    carry lost between chunks (a chunk that starts from zeros) is off by the
    state's whole size."""
    tokens, row = _tokens(23, 101), _row(1)
    _, chunked = _prefill(BASE, _empty_pool(pages=80), tokens, row, 101, impl)
    _, stepped = _decode(BASE, _empty_pool(pages=80), tokens, row, 0, 101,
                         impl)

    def apart(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    want = stepped["ssm_state"][:, 1]
    assert float(jnp.linalg.norm(want)) > 0.5
    assert apart(chunked["ssm_state"][:, 1], want) < 1e-5
    assert apart(chunked["ssm_conv"][:, 1], stepped["ssm_conv"][:, 1]) < 1e-5
    _, lost = _prefill(BASE, _empty_pool(pages=80), tokens[96:], row, 5, impl)
    assert apart(lost["ssm_state"][:, 1], want) > 0.1


def test_the_state_is_found_through_the_harnesss_arange_row():
    """``benchmark/correctness.py::engine_logits`` hands the programs ONE
    row ``arange(pages)`` and no slot: the entry is 0, the ring pages 0 .. 3,
    and a second sequence through the same row starts from zeros again."""
    cache = _empty_pool(pages=80)
    for seed in (1, 2):
        tokens = _tokens(seed, 40)
        row = np.full((MPP,), -1, np.int32)
        row[:6] = np.arange(6)
        got, cache = _prefill(BASE, cache, tokens, row, 36)
        dec, cache = _decode(BASE, cache, tokens, row, 36, 4, slot=0)
        np.testing.assert_allclose(jnp.concatenate([got, dec]),
                                   _full(BASE, PARAMS, tokens), rtol=3e-4,
                                   atol=3e-4)


def test_the_in_place_chunk_program_at_pairs_of_128():
    """Pairs of 128 values take the chunk program built in place (the pool
    flat through the layer scans, the attention kernels over the pages, the
    scan kernel, interpreted): against the gathered form and the full
    forward."""
    params = _programs(WIDE, "pallas")[0]
    tokens = _tokens(21, 43)
    want = _full(WIDE, params, tokens)
    row = _row(1, 7)
    for impl in ("pallas", "gather"):
        got, cache = _prefill(WIDE, _empty_pool(WIDE, 80), tokens, row, 37,
                              impl)
        np.testing.assert_allclose(got, want[:37], rtol=2e-3, atol=2e-3)
        dec, _ = _decode(WIDE, cache, tokens, row, 37, 6, impl)
        np.testing.assert_allclose(dec, want[37:], rtol=2e-3, atol=2e-3)
    assert _chunk_in_place(_empty_pool(WIDE), WIDE, None, "pallas")
    assert not _chunk_in_place(_empty_pool(WIDE), WIDE, None, "gather")
    assert not _chunk_in_place(_empty_pool(), BASE, None, "pallas")


# -- the tail, skipped ---------------------------------------------------------------

def _rows_program(cfg, impl, logits_at, with_wanted=True):
    params = _programs(cfg, impl)[0]
    return jax.jit(lambda c, t, rows, st, vl, wanted: paged_chunk_prefill(
        params, c, t, rows, st, vl, cfg, context_pages=MPP,
        paged_attn_impl=impl, logits_at=logits_at,
        wanted=wanted if with_wanted and logits_at == "last" else None))


@pytest.mark.parametrize("cfg,impl", [(WIDE, "pallas"), (BASE, "gather")])
def test_the_tail_at_one_position_a_row_is_the_tail_at_all_of_them(cfg, impl):
    """The program over rows (``logits_at="last"``: in place, the tail's
    layers at each row's last valid position alone) against the ``[C, V]``
    program's last valid row, two rows at their own starts and a dead one:
    1e-6 of the logits' size, and the pool written alike."""
    ta, tb = _tokens(7, 48), _tokens(8, 48)
    ra, rb = _row(0, 8), _row(2, 8)
    cache = _empty_pool(cfg, 80)
    _, cache = _prefill(cfg, cache, ta, ra, 32, impl)
    block = np.zeros((3, CHUNK), np.int32)
    block[0, :11], block[2] = ta[32:43], tb[:16]
    rows = np.full((3, MPP), -1, np.int32)
    rows[0], rows[2] = ra, rb
    args = (jnp.asarray(block), jnp.asarray(rows),
            jnp.asarray([32, 0, 0], jnp.int32),
            jnp.asarray([11, 0, 16], jnp.int32),
            jnp.asarray([True, False, True]))
    every, pool_all = _rows_program(cfg, impl, "all")(cache, *args)
    last, pool_last = _rows_program(cfg, impl, "last")(cache, *args)
    assert last.shape == (3, cfg.vocab_size)
    scale = float(jnp.abs(every).max())
    np.testing.assert_allclose(last[0], every[0, 10], atol=1e-6 * scale)
    np.testing.assert_allclose(last[2], every[2, 15], atol=1e-6 * scale)
    for n in pool_all:
        np.testing.assert_array_equal(pool_all[n], pool_last[n])


def test_a_program_in_which_no_row_ends_runs_no_tail():
    """The in-place program over rows holds its tail (the cross layers'
    attention, the gated memory units) under ONE conditional on "some row
    ends its prompt": its lowered text has the tail's attention call only
    inside that branch, and with no row wanted the logits come back zeros
    while the pool is written as ever."""
    cache = _empty_pool(WIDE, 80)
    block = jnp.asarray(_tokens(3, CHUNK)[None])
    args = (block, jnp.asarray(_row(1, 8))[None],
            jnp.asarray([0], jnp.int32), jnp.asarray([CHUNK], jnp.int32))
    program = _rows_program(WIDE, "pallas", "last")
    none, pool_none = program(cache, *args, jnp.asarray([False]))
    some, pool_some = program(cache, *args, jnp.asarray([True]))
    assert float(jnp.abs(none).max()) == 0.0
    assert float(jnp.abs(some).max()) > 0.0
    for n in pool_some:
        np.testing.assert_array_equal(pool_none[n], pool_some[n])
    # at the program's top level: the two groups in front, one scan each,
    # and two conditionals (the tail, the head); the tail's scan stands
    # inside the first conditional's taken branch and nowhere else
    def primitives(jaxpr):
        return [e.primitive.name for e in jaxpr.eqns]

    def unwrapped(fn, *a):
        jaxpr = jax.make_jaxpr(fn)(*a).jaxpr
        while primitives(jaxpr) in (["pjit"], ["jit"]):
            jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr
        return jaxpr

    top = unwrapped(program, cache, *args, jnp.asarray([True]))
    assert primitives(top).count("scan") == 2
    conds = [e for e in top.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2
    inside = [primitives(br.jaxpr).count("scan")
              for br in conds[0].params["branches"]]
    assert sorted(inside) == [0, 1]
    always = unwrapped(_rows_program(WIDE, "pallas", "last",
                                     with_wanted=False),
                       cache, *args, jnp.asarray([True]))
    assert primitives(always).count("scan") == 3
    assert primitives(always).count("cond") == 0


def test_a_first_pages_copy_carries_the_entry_and_no_other_copy_does():
    cache = {n: jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
             for i, (n, a) in enumerate(_empty_pool().items())}
    out = copy_pages(cache, jnp.asarray([1, 20, 5]), jnp.asarray([0, 30, 7]))
    for n in SSM_PLANES:
        np.testing.assert_array_equal(out[n][:, 0], cache[n][:, 1])
        np.testing.assert_array_equal(out[n][:, 1:], cache[n][:, 1:])
    np.testing.assert_array_equal(out["k"][:, 30], cache["k"][:, 20])
    np.testing.assert_array_equal(out["window_k"][:, 7],
                                  cache["window_k"][:, 5])


# -- through the engine ------------------------------------------------------------

ENGINE = preset("tiny-phi4flash", dtype="float32", param_dtype="float32")


def _engine(**kw):
    spec = dict(max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                decode_steps=4, max_concurrent_prefills=2)
    return LLMEngine(ENGINE, BatchingSpec(**{**spec, **kw}), params=PARAMS)


@functools.lru_cache(maxsize=None)
def _full_padded():
    return jax.jit(lambda t: decoder_forward(PARAMS, t[None], ENGINE)[0][0])


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


@pytest.mark.parametrize("prefills", [1, 2])
def test_engine_tokens_are_the_full_recomputes(prefills):
    """Four prompts on three slots: every chunk through the program over
    rows (a prefill alone as a group of one row), chunks interleaved with
    decode rounds, a slot and its entry handed to a second sequence."""
    engine = _engine(max_concurrent_prefills=prefills)
    assert engine._plan.lone_at_last and engine._plan.rows == prefills
    assert engine._ring == RING and engine._window_pages == SLOTS * RING
    prompts = [_tokens(31, 75), _tokens(32, 5), _tokens(33, 50),
               _tokens(34, 21)]
    reqs = _serve(engine, prompts, 12)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 12)
    engine._allocator.assert_quiescent()
    counters = engine.counters()
    # 5 + 1 + 4 + 2 chunks, four of which end a prompt: the programs that
    # ran the tail are those with an end
    assert counters["prefill_chunks_dispatched"] == 12
    assert 4 >= counters["prefill_programs_with_end"] >= 2
    assert counters["prefill_programs_with_end"] \
        < counters["prefill_programs_dispatched"]
    assert engine._allocator.available(ring=True) == SLOTS * RING


def test_a_preempted_sequence_starts_its_state_again_from_zeros():
    """A pool too small for three growing contexts: the youngest gives its
    pages back, prefills again from position 0 (its entry and its ring,
    whatever they hold, are not read) and every request reads the full
    recompute's tokens."""
    engine = _engine(max_pages=24)
    prompts = [_tokens(41, 60), _tokens(42, 62), _tokens(43, 58)]
    reqs = _serve(engine, prompts, 30)
    counters = engine.counters()
    assert counters["preemptions"] >= 1
    assert counters["state_sequences_started"] == 3 + counters["preemptions"]
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 30)
    engine._allocator.assert_quiescent()


def test_counters_exist_from_construction_and_name_the_planes_by_kind():
    engine = _engine()
    before = engine.counters()
    assert before["kv_sequence_pool_bytes"] \
        == SLOTS * state_bytes_per_sequence(ENGINE)
    # ONE layer's rows a token, a ring a slot in the two window layers
    assert before["kv_global_pool_bytes"] == SLOTS * MPP * PAGE * 2 * 32 * 4
    assert before["kv_window_pool_bytes"] \
        == 2 * SLOTS * RING * PAGE * 2 * 32 * 4
    assert before["kv_pool_bytes"] == before["kv_sequence_pool_bytes"] \
        + before["kv_global_pool_bytes"] + before["kv_window_pool_bytes"]
    assert before["kv_bytes_per_token"] == 2 * 32 * 4
    assert before["kv_window_pages_a_sequence"] == RING
    assert before["kv_layers_sharing"] == 2
    assert before["state_sequences_started"] == 0
    _serve(engine, [_tokens(51, 40)], 9)
    after = engine.counters()
    assert set(after) == set(before)
    assert after["state_sequences_started"] == 1
    assert after["prefill_programs_dispatched"] == 3
    assert after["prefill_programs_with_end"] == 1
    plain = LLMEngine(preset("tiny"), BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=PAGE,
        chunked_prefill_tokens=CHUNK)).counters()
    assert plain["kv_layers_sharing"] == 0


@pytest.mark.parametrize("option,match", [
    (dict(enable_prefix_caching=True), "prefix reuse and the radix"),
    (dict(speculative=SpeculativeSpec(mode="ngram")), "speculative verify"),
    (dict(kv_cache_dtype="int8"), "int8 KV"),
    (dict(role="prefill"), "handoff"),
    (dict(host_kv_pages=8), "host tier"),
    (dict(host_kv_pages=8, remote_kv_root="/tmp/x"), "host tier"),
    (dict(lora=LoRASpec(max_adapters=2)), "LoRA"),
    (dict(quantize="int8"), "weight quantization"),
])
def test_what_the_new_kinds_cannot_take_yet_is_refused_by_name(option,
                                                               match):
    with pytest.raises(ValueError) as err:
        _engine(**option)
    assert "state-space (ssm) layers" in str(err.value)
    assert "gated memory units and cross-attention layers" in str(err.value)
    assert match in str(err.value)


def test_a_mesh_is_refused_by_name():
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices()[:1] * 2).reshape(2)
    if len(set(jax.devices())) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="a mesh"):
        LLMEngine(ENGINE, BatchingSpec(
            max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
            chunked_prefill_tokens=CHUNK, enable_prefix_caching=False),
            params=PARAMS, mesh=Mesh(np.asarray(jax.devices()[:2]),
                                     ("model",)))
