"""Attention and a Mamba-2 (SSD) mixer side by side in EVERY block, the SSD
state a SEQUENCE in the page pool beside the layer's own K and V (Falcon-H1's
structure), on the normal path at the tiny preset on the CPU: the chunked
form and both kernels (interpreted) against the token-by-token recurrence,
the mixer whatever the split, the stack's tree and counts, the pool's planes,
the programs (gathered, and in place at heads of 128) against the full
forward and against the benchmark's plain reference in float32 and in
bfloat16, the state an entry ends in, a comparison that sees each branch and
the carried state, and through the engine: tokens against the full recompute
(two chunks a program, and one chunk a program at one row: ISSUE 52),
preemption, the counters and the refused options by name; and the one
program that carries a chunk AND the slots' step over this stack (ISSUE 58)
against the reference's carried states."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.manifest import load_json, load_module_file
from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import PRESETS, DecoderConfig, preset
from kubeflow_tpu.models.decoder import (
    SSD_PLANES, decoder_forward, decoder_param_specs, holds,
    init_decoder_params, layer_groups, plane_kind,
)
from kubeflow_tpu.ops import ssd
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    _chunk_in_place, _paged_decode_step, chunk_carries_step, copy_pages,
    engine_pool_shapes, first_page_ids, own_first_pages, paged_chunk_prefill,
    paged_mixed_step, pool_bytes_per_token, sequence_planes,
    state_bytes_per_sequence,
)

PAGE, CHUNK, MPP, SLOTS = 8, 16, 16, 3
BASE = preset("tiny-falconh1", dtype="float32", param_dtype="float32")
PARAMS = init_decoder_params(jax.random.PRNGKey(11), BASE)
# one KV head of 128: what the in-place chunk program and the paged kernels
# take (interpreted here)
WIDE = dataclasses.replace(BASE, n_heads=2, n_kv_heads=1, head_dim=128)
REHEARSAL = load_json("benchmark/configs/rehearsal-tiny-falconh1.json")


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


def _reference():
    return load_module_file(
        "benchmark.architectures", "falcon-h1.reference",
        "benchmark/architectures/falcon-h1/reference.py")


# -- the recurrence, its chunked form and its kernels ---------------------------

def _operands(seed, b, s, h, p, g, n, forget=False, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.8))
    if forget:          # head 1: exp(A dt) underflows, exp(-l) would overflow
        dt = dt.at[:, :, 1].set(40.0)
        a = a.at[1].set(-16.0)
    return (jax.random.normal(ks[0], (b, s, h, p), dtype), dt, a,
            jax.random.normal(ks[3], (b, s, g, n), dtype),
            jax.random.normal(ks[4], (b, s, g, n), dtype),
            jax.random.normal(ks[5], (h,)),
            jax.random.normal(ks[6], (b, h, n, p)))


def _token_by_token(x, dt, a, bm, cm, d, s0):
    """The recurrence as the plain reference walks it: one position at a
    time, the state laid ``[P, N]``, a head's group by division."""
    per = x.shape[2] // bm.shape[2]
    ys, st = [], jnp.swapaxes(s0, 2, 3)
    for t in range(x.shape[1]):
        b_t, c_t = (jnp.repeat(m[:, t], per, axis=1) for m in (bm, cm))
        st = jnp.exp(a * dt[:, t])[..., None, None] * st \
            + (dt[:, t, :, None] * x[:, t])[..., None] * b_t[:, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", st, c_t)
                  + d[:, None] * x[:, t])
    return jnp.stack(ys, axis=1), jnp.swapaxes(st, 2, 3)


FORMS = {
    "scan": lambda args, block: ssd.ssd_scan_xla(*args),
    "blocks": lambda args, block: ssd.ssd_blocks_xla(*args, block=block),
    "kernel": lambda args, block: ssd.ssd_chunk(*args, impl="pallas",
                                                block=block),
}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("s,h,p,g,n,block,forget", [
    (24, 4, 16, 2, 32, 8, False),       # three whole blocks: the carry
    (21, 4, 16, 2, 32, 8, True),        # ends inside a block: a padded tail
    (256, 2, 128, 1, 256, 128, True),   # the published head, two blocks
    (5, 2, 8, 2, 16, 128, False),       # shorter than a block
])
def test_every_form_is_the_recurrence(form, s, h, p, g, n, block, forget):
    """From a start state to an end state: the scan over positions, the
    chunked form in XLA and the kernel (interpreted), against the
    token-by-token walk; a head whose decay underflows forgets its state at
    once and stays finite (the mask stands in the exponent's argument)."""
    args = _operands(s, 2, s, h, p, g, n, forget)
    want_y, want_s = _token_by_token(*args)
    y, st = FORMS[form](args, block)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(st).all())
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=2e-5 * scale)
    np.testing.assert_allclose(st, want_s, rtol=1e-4, atol=2e-5)
    if forget:
        assert float(jnp.exp(args[2][1] * args[1][0, 0, 1])) == 0.0


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_position_whose_step_is_zero_passes_the_state_through(form):
    """dt = 0 behind a row's valid length: the end state is the one after
    the valid positions, so no program needs a second form for a tail."""
    x, dt, a, bm, cm, d, s0 = _operands(5, 2, 32, 4, 16, 2, 32)
    valid = jnp.arange(32)[None, :, None] < jnp.asarray([20, 32])[:, None,
                                                                  None]
    _, want = ssd.ssd_scan_xla(x[:1, :20], dt[:1, :20], a, bm[:1, :20],
                               cm[:1, :20], d, s0[:1])
    _, got = FORMS[form]((x, jnp.where(valid, dt, 0.0), a, bm, cm, d, s0), 8)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("follows", [
    (False, True, False), (False, True, True), (False, False, True),
    (False, False, False)], ids=lambda f: "".join("-f"[v] for v in f))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_row_that_follows_starts_from_the_end_of_the_row_in_front(
        form, follows):
    """``follows`` (ISSUE 63): rows that are consecutive chunks of one
    sequence run one behind the other inside the ONE call, a row behind from
    the state the row in front ENDS in (not from its own), every other row
    from its own; every row's end state comes back. Against the walk, a row
    at a time; heads of 64, the second row ending inside a block."""
    x, dt, a, bm, cm, d, s0 = _operands(63, 3, 24, 4, 64, 2, 16)
    dt = dt.at[1, 21:].set(0.0)
    want_y, want_s = [], []
    for r in range(3):
        start = want_s[-1] if follows[r] else s0[r:r + 1]
        y, st = _token_by_token(x[r:r + 1], dt[r:r + 1], a, bm[r:r + 1],
                                cm[r:r + 1], d, start)
        want_y.append(y)
        want_s.append(st)
    form = {**FORMS, "kernel": lambda args, block: ssd.ssd_chunk(
        *args[:-1], follows=args[-1], impl="pallas", block=block)}[form]
    y, st = form((x, dt, a, bm, cm, d, s0, jnp.asarray(follows)), 8)
    np.testing.assert_allclose(y, jnp.concatenate(want_y), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(st, jnp.concatenate(want_s), rtol=1e-4,
                               atol=2e-5)


def test_the_chunk_kernel_rounds_its_operands_to_the_activation_type():
    """bfloat16 in: the matrix unit's operands are bfloat16, sums, decays and
    the state float32: within bfloat16's rounding of the float32 scan."""
    args = _operands(9, 2, 40, 4, 16, 2, 32, dtype=jnp.bfloat16)
    want_y, want_s = ssd.ssd_scan_xla(*args)
    y, st = ssd.ssd_chunk(*args, impl="pallas", block=8)
    assert y.dtype == st.dtype == jnp.float32
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y).max()) < 0.02 * scale
    assert float(jnp.abs(st - want_s).max()) \
        < 0.02 * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_step_moves_live_rows_entries_and_no_other(impl):
    """One token a row against a plane of twelve entries: dead rows between
    live ones (one of them aimed past the plane), a row that starts its
    sequence from zeros whatever its entry holds; every entry no live row
    names stays bit for bit."""
    x, dt, a, bm, cm, d, _ = _operands(3, 5, 1, 4, 16, 2, 32)
    plane = jax.random.normal(jax.random.PRNGKey(9), (12, 4, 32, 16))
    idx = jnp.asarray([3, 12, 7, 0, 9])
    live = jnp.asarray([True, False, True, True, False])
    fresh = jnp.asarray([False, False, True, False, False])
    y, out = ssd.ssd_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, plane,
                          idx, fresh, live, impl=impl)
    start = jnp.where(fresh[:, None, None, None], 0.0,
                      plane[jnp.clip(idx, 0, 11)])
    want_y, want_s = ssd.ssd_step_xla(x[:, 0], dt[:, 0], a, bm[:, 0],
                                      cm[:, 0], d, start)
    for r in (0, 2, 3):
        np.testing.assert_allclose(y[r], want_y[r], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out[int(idx[r])], want_s[r], rtol=1e-5,
                                   atol=1e-6)
    assert float(jnp.abs(y[jnp.asarray([1, 4])]).max()) == 0.0
    untouched = jnp.asarray([i for i in range(12) if i not in (3, 7, 0)])
    np.testing.assert_array_equal(out[untouched], plane[untouched])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_step_with_no_live_row_moves_nothing(impl):
    x, dt, a, bm, cm, d, _ = _operands(4, 3, 1, 4, 16, 2, 32)
    plane = jax.random.normal(jax.random.PRNGKey(2), (6, 4, 32, 16))
    y, out = ssd.ssd_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, plane,
                          jnp.asarray([0, 1, 2]), jnp.zeros((3,), bool),
                          jnp.zeros((3,), bool), impl=impl)
    np.testing.assert_array_equal(out, plane)
    assert float(jnp.abs(y).max()) == 0.0


def test_the_mixer_is_the_scan_behind_its_inputs_whatever_the_split():
    """``ssd_block`` over 40 positions at once, and as 24 then 16 from the
    state and the convolution's tail the first left (a chunk that starts
    mid-sequence with a carried state): the same output, the same end state;
    a padded row's state is the one at its valid length."""
    p = jax.tree.map(lambda a: a[0], PARAMS["layers"]["parallel"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, BASE.hidden))
    out, (s, tail) = L.ssd_block(p, x, BASE)
    o1, state = L.ssd_block(p, x[:, :24], BASE)
    o2, (s2, tail2) = L.ssd_block(p, x[:, 24:], BASE, state)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), out, atol=1e-5)
    np.testing.assert_allclose(s2, s, atol=1e-5)
    np.testing.assert_allclose(tail2, tail, atol=1e-6)
    _, (sp, tp) = L.ssd_block(p, x, BASE, valid_len=jnp.asarray([24, 40]))
    np.testing.assert_allclose(sp[0], state[0][0], atol=1e-5)
    np.testing.assert_allclose(tp[0], state[1][0], atol=1e-6)
    np.testing.assert_allclose(sp[1], s[1], atol=1e-5)
    ok, _ = L.ssd_block(p, x, BASE, impl="pallas")
    np.testing.assert_allclose(ok, out, atol=1e-4)


# -- the stack, its tree, its counts, its pool ------------------------------------

def test_the_stack_is_one_group_of_alike_blocks_with_two_operators():
    for name in ("tiny-falconh1", "falcon-h1-34b"):
        cfg = PRESETS[name]
        assert set(cfg.kinds) == {"parallel"}
        assert [(n, g.n_layers, at) for n, g, at in layer_groups(cfg)] \
            == [("layers", cfg.n_layers, 0)]
    block = PARAMS["layers"]["parallel"]
    assert set(block) == {"wq", "wk", "wv", "wo", "w_z", "w_xbc", "w_dt",
                          "conv", "conv_b", "a_log", "d_skip", "dt_bias",
                          "ssd_norm", "w_out"}
    assert block["w_xbc"].shape == (3, 64, 64 + 2 * 2 * 32)
    specs = decoder_param_specs(BASE)
    assert jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, tuple)) \
        == jax.tree.structure(PARAMS)
    assert sum(a.size for a in jax.tree.leaves(PARAMS)) == BASE.num_params()


def test_the_published_counts_are_the_hand_written_ones():
    full = preset("falcon-h1-34b")
    assert full._attn_params() == 5120 * (2560 + 512 + 512) + 2560 * 5120 \
        == 31_457_280
    assert full._ssd_params() == 47_349_760 + 20_971_520 + 5120 * 4 + 5120 \
        + 96 + 4096 == 68_351_072
    assert full.num_params() == 72 * 430_120_032 + 2_673_868_800 + 5120
    assert preset("falcon-h1-34b", n_layers=5).num_params() == 4_824_474_080
    assert preset("falcon-h1-34b", n_layers=4).num_params() == 4_394_354_048
    assert full.ssd_inner == 4096 and full.ssd_conv_dim == 5120


def test_what_a_parallel_stack_cannot_be_is_refused_by_name():
    with pytest.raises(ValueError, match="ssd_heads"):
        DecoderConfig(layer_kinds=("parallel",))
    with pytest.raises(NotImplementedError, match="beside another kind"):
        dataclasses.replace(BASE, layer_kinds=("parallel", "attention"),
                            n_layers=4)
    with pytest.raises(ValueError, match="holds 3 multipliers or none"):
        dataclasses.replace(BASE, attn_multipliers=(1.0, 2.0))


def test_a_layer_holds_k_and_v_and_an_entry_a_sequence():
    # the planes are an ssd layer's (the mixer as a block's only operator:
    # PR 61); a parallel layer holds them beside its K and V
    assert [plane_kind(n) for n in SSD_PLANES] == ["ssd"] * 2
    assert holds("parallel", "k") and holds("parallel", "ssd_state")
    assert holds("ssd", "ssd_state") and not holds("ssd", "k")
    assert not holds("attention", "ssd_conv") and holds("attention", "v")
    assert BASE.layers_holding("attention") == BASE.layers_holding(
        "ssd") == 3 and BASE.layers_of("attention") == 0
    assert [p[:2] for p in sequence_planes(BASE)] == [
        ("ssd_state", (4, 32, 16)), ("ssd_conv", (3, 192))]
    assert own_first_pages(BASE) == 1 and first_page_ids(BASE, SLOTS) == 0
    shapes = engine_pool_shapes(BASE, SLOTS, 40, PAGE)
    assert {n: s for n, (s, _) in shapes.items()} == {
        "k": (3, 40, PAGE, 2, 16), "v": (3, 40, PAGE, 2, 16),
        "ssd_state": (3, SLOTS, 4, 32, 16), "ssd_conv": (3, SLOTS, 3, 192)}
    assert shapes["ssd_state"][1] == jnp.float32
    assert pool_bytes_per_token(BASE) == 3 * 2 * 2 * 16 * 4
    assert state_bytes_per_sequence(BASE) == 3 * (4 * 32 * 16 + 3 * 192) * 4
    full = preset("falcon-h1-34b", n_layers=5)
    assert pool_bytes_per_token(full) == 10_240
    assert state_bytes_per_sequence(full) == 5 * (4_194_304 + 30_720)
    pool = engine_pool_shapes(full, 48, 624, 128)
    assert pool["ssd_state"][0] == (5, 48, 32, 256, 128)
    assert pool["k"][0] == (5, 624, 128, 4, 128)


# -- the programs against the full forward and the plain reference -----------------

def _empty_pool(cfg=BASE, pages=40):
    return {n: jnp.zeros(s, d) for n, (s, d) in engine_pool_shapes(
        cfg, SLOTS, pages, PAGE).items()}


@functools.lru_cache(maxsize=None)
def _programs(cfg, impl):
    params = PARAMS if cfg is BASE else init_decoder_params(
        jax.random.PRNGKey(11), cfg)
    chunk = jax.jit(lambda c, t, rows, st, vl: paged_chunk_prefill(
        params, c, t, rows, st, vl, cfg, context_pages=MPP,
        paged_attn_impl=impl))
    step = jax.jit(lambda c, table, t, ln, lv: _paged_decode_step(
        params, {**c, "table": table}, t, ln, lv, cfg, attn_impl=impl))
    return params, chunk, step


def _full(cfg, params, tokens):
    return decoder_forward(params, jnp.asarray(tokens)[None], cfg)[0][0]


def _prefill(cfg, cache, tokens, row, plen, impl="gather", start=0,
             chunk=CHUNK, between=None):
    out = []
    for pos in range(start, plen, chunk):
        if between is not None and pos > start:
            cache = between(cache)
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
    page from the first pages' ids (one a slot), the others from above."""
    rest = list(range(SLOTS + first * MPP, SLOTS + (first + 1) * MPP))
    row = np.full((MPP,), -1, np.int32)
    row[:pages] = ([first] + rest)[:pages]
    return row


@pytest.mark.parametrize("impl", ["gather", "pallas"])
@pytest.mark.parametrize("plen", [13, 40, 101])
def test_chunked_prefill_then_decode_is_the_full_forward(impl, plen):
    """Logits through the pool: K and V a token in the layer's pages, the
    state carried chunk to chunk and step to step at ``table_row[0]``; over a
    dirty pool (what an entry held before a sequence's start is not read).
    101 tokens are seven chunks (thirteen blocks of 8, the last one cut);
    the decode steps cross a page's end."""
    cfg = WIDE if impl == "pallas" else BASE
    params = _programs(cfg, impl)[0]
    tokens = _tokens(plen, plen + 6)
    want = _full(cfg, params, tokens)
    dirty = {n: (jnp.full_like(a, 3.0) if n in SSD_PLANES else a)
             for n, a in _empty_pool(cfg, 80).items()}
    row = _row(2)
    got, cache = _prefill(cfg, dirty, tokens, row, plen, impl)
    np.testing.assert_allclose(got, want[:plen], rtol=3e-4, atol=3e-4)
    got, cache = _decode(cfg, cache, tokens, row, plen, 6, impl)
    np.testing.assert_allclose(got, want[plen:], rtol=3e-4, atol=3e-4)
    for n in SSD_PLANES:      # entries 0 and 1 were nobody's: untouched
        assert float(jnp.abs(cache[n][:, :2] - 3.0).max()) == 0.0
    assert _chunk_in_place(dirty, cfg, None, impl) == (impl == "pallas")
    # the kind rides since PR 58, where the chunk meets the pool in place
    assert chunk_carries_step(dirty, cfg, None, impl) == (impl == "pallas")
    assert not chunk_carries_step(_empty_pool(BASE, 80), BASE, None,
                                  "pallas")      # heads of 16: gathered


def test_chunks_that_end_inside_a_page_and_a_block_carry_the_state_too():
    """Chunks of 12 tokens: every boundary but one lies inside a page, and
    inside a block of 8 positions too."""
    tokens = _tokens(77, 46)
    want = _full(BASE, PARAMS, tokens)
    got, cache = _prefill(BASE, _empty_pool(pages=80), tokens, _row(0), 40,
                          chunk=12)
    np.testing.assert_allclose(got, want[:40], rtol=3e-4, atol=3e-4)
    got, _ = _decode(BASE, cache, tokens, _row(0), 40, 6, slot=0)
    np.testing.assert_allclose(got, want[40:], rtol=3e-4, atol=3e-4)


def test_a_step_at_length_zero_starts_from_zeros():
    """A sequence whose first token comes through the decode step, over an
    entry and pages that hold another sequence's leavings."""
    tokens = _tokens(3, 9)
    dirty = {n: jnp.full_like(a, 2.0) for n, a in _empty_pool().items()}
    got, _ = _decode(BASE, dirty, tokens, _row(1), 0, 9)
    np.testing.assert_allclose(got, _full(BASE, PARAMS, tokens), rtol=3e-4,
                               atol=3e-4)


def _through_the_pool(cfg, params, tokens, plen, impl="gather",
                      between=None):
    run = _programs(cfg, impl)
    assert run[0] is params or cfg is not BASE
    got, cache = _prefill(cfg, _empty_pool(cfg, 80), tokens, _row(1), plen,
                          impl, between=between)
    dec, cache = _decode(cfg, cache, tokens, _row(1), plen, len(tokens)
                         - plen, impl)
    return jnp.concatenate([got, dec]), cache


def test_the_program_is_the_plain_reference_on_logits_in_float32():
    """The whole forward, and three chunks then six decode steps through the
    pool, against the benchmark's reference (the recurrence token by token,
    one softmax, every multiplier where the published forward has it), which
    shares no code with the program: tight in float32."""
    tokens = _tokens(5, 46)
    with jax.default_matmul_precision("highest"):
        want = _reference().logits(PARAMS, jnp.asarray(tokens), REHEARSAL)
    np.testing.assert_allclose(_full(BASE, PARAMS, tokens), want, rtol=3e-4,
                               atol=3e-4)
    got, _ = _through_the_pool(BASE, PARAMS, tokens, 40)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_the_engines_programs_are_the_reference_in_bfloat16():
    """The comparison that decides ``correct``, at the configuration's type:
    the engine's own chunk program and decode step on seeded bfloat16
    weights against the float32 reference, under the rehearsal file's
    limits."""
    from benchmark import architecture
    from benchmark.weights import make_params

    cfg = architecture.part(REHEARSAL, "program").program_config(REHEARSAL)
    params = make_params(REHEARSAL, 2_190_500_001, cfg.param_dtype)
    engine = LLMEngine(cfg, BatchingSpec(
        max_batch_size=2, max_seq_len=128, page_size=16,
        chunked_prefill_tokens=32, enable_prefix_caching=False),
        params=params)
    numbers = correctness.serving_numbers(
        engine, params, REHEARSAL, REHEARSAL["correctness"], 2_190_500_001)
    ok, lines = correctness.judge(numbers,
                                  REHEARSAL["correctness"]["limits"])
    assert ok, lines
    assert numbers["prefill_logit_err"] < 0.03
    assert numbers["decode_logit_err"] < 0.03


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_the_entry_a_prompt_leaves_is_the_references_carried_state(impl):
    """A sequence's entry after 101 tokens through the chunk programs, seven
    chunks that each carry the state on, against the state the plain
    reference's token-by-token walk ends in: 1e-5 of the state's norm in
    float32. A plane rounded to bfloat16 between two chunks, or a carry lost
    between them (a chunk that starts from zeros), is off by far more: this
    is where the state's type and its carry are held (no limit on logits
    can: the recurrence forgets)."""
    cfg = WIDE if impl == "pallas" else BASE
    params = _programs(cfg, impl)[0]
    conf = {**REHEARSAL, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim}
    tokens, row = _tokens(23, 101), _row(1)
    with jax.default_matmul_precision("highest"):
        want = jnp.swapaxes(_reference().carried_states(
            params, jnp.asarray(tokens), conf), 2, 3)       # [L, H, N, P]

    def apart(cache):
        got = cache["ssd_state"][:, 1]
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    assert float(jnp.linalg.norm(want)) > 0.0
    _, chunked = _prefill(cfg, _empty_pool(cfg, 80), tokens, row, 101, impl)
    assert apart(chunked) < 1e-5

    def rounded(cache):
        return {**cache, "ssd_state": cache["ssd_state"].astype(
            jnp.bfloat16).astype(jnp.float32)}

    _, coarse = _prefill(cfg, _empty_pool(cfg, 80), tokens, row, 101, impl,
                         between=rounded)
    assert apart(coarse) > 1e-4

    def dropped(cache):
        return {**cache, "ssd_state": jnp.zeros_like(cache["ssd_state"])}

    _, lost = _prefill(cfg, _empty_pool(cfg, 80), tokens, row, 101, impl,
                       between=dropped)
    assert apart(lost) > 1e-2


def test_the_one_program_carries_both_sequences_states_to_the_references():
    """ISSUE 58: a prompt's seven chunks through ``paged_mixed_step`` at one
    row while another sequence's slot takes a decode step inside each of
    those programs (``ssd_chunk`` writes the chunk row's entry, ``ssd_step``
    the slot's, in one layer scan). Against what shares no code with either:
    the prompt's entry is the state the plain reference's token-by-token
    walk ends in after its 101 tokens, the slot's entry the one it ends in
    after the slot's prompt and the seven tokens fed, both to 1e-5 of the
    state's norm; the slot's seven tokens are the full forward's greedy
    ones and the last chunk's logits its last position's; the third entry,
    nobody's, is untouched. A program nothing rides with (``ride`` false)
    moves the prompt's entry alone."""
    cfg, impl = WIDE, "pallas"
    params = _programs(cfg, impl)[0]
    conf = {**REHEARSAL, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim}
    ta, tb = _tokens(23, 101), _tokens(24, 21)
    row_a, row_b = _row(1), _row(2)
    mixed = jax.jit(lambda c, t, tr, st, vl, ends, ride, tok, ln, lv:
                    paged_mixed_step(
                        params, c, t, tr, st, vl, ends, ride, tok, ln, lv,
                        jnp.zeros((SLOTS,), jnp.float32),
                        jnp.zeros((SLOTS,), jnp.int32),
                        jnp.ones((SLOTS,), jnp.float32),
                        jnp.full((SLOTS,), -1, jnp.int32),
                        jnp.full((SLOTS,), 99, jnp.int32),
                        jax.random.PRNGKey(0), cfg, sample_mode="greedy",
                        attn_impl=impl))
    dirty = {n: (jnp.full_like(a, 3.0) if n in SSD_PLANES else a)
             for n, a in _empty_pool(cfg, 80).items()}
    logits_b, cache = _prefill(cfg, dirty, tb, row_b, 21, impl)
    fed = [int(jnp.argmax(logits_b[-1]))]
    table = np.full((SLOTS, MPP), -1, np.int32)
    table[0] = row_b
    live = jnp.asarray([True, False, False])

    def program(cache, pos, ride):
        real = min(CHUNK, 101 - pos)
        block = np.zeros((1, CHUNK), np.int32)
        block[0, :real] = ta[pos:pos + real]
        tok = np.zeros((SLOTS,), np.int32)
        lens = np.zeros((SLOTS,), np.int32)
        tok[0], lens[0] = fed[-1], 21 + len(fed) - 1
        logits, out, cache, *_ = mixed(
            {**cache, "table": jnp.asarray(table)}, jnp.asarray(block),
            jnp.asarray(row_a)[None], jnp.asarray([pos], jnp.int32),
            jnp.asarray([real], jnp.int32),
            jnp.asarray([pos + real == 101]), jnp.asarray(ride),
            jnp.asarray(tok), jnp.asarray(lens), live)
        cache.pop("table")
        return logits, out, cache

    _, out, alone = program(cache, 0, False)
    assert np.all(np.asarray(out) == -1)
    for n in SSD_PLANES:        # entry 2 (the slot's) as the prefill left it
        np.testing.assert_array_equal(alone[n][:, 2], cache[n][:, 2])
        assert float(jnp.abs(alone[n][:, 1] - cache[n][:, 1]).max()) > 0.0
    for pos in range(0, 101, CHUNK):
        logits, out, cache = program(cache, pos, True)
        assert np.asarray(out)[1:, 0].tolist() == [-1, -1]
        fed.append(int(out[0, 0]))
    stream = np.concatenate([tb, np.asarray(fed, np.int32)])
    assert len(fed) == 8
    full_b = _full(cfg, params, stream)
    assert fed == [int(t) for t in jnp.argmax(full_b[20:28], axis=-1)]
    np.testing.assert_allclose(logits[0], _full(cfg, params, ta)[100],
                               rtol=3e-4, atol=3e-4)
    with jax.default_matmul_precision("highest"):
        want_a, want_b = (jnp.swapaxes(_reference().carried_states(
            params, jnp.asarray(t), conf), 2, 3) for t in (ta, stream[:28]))
    for want, entry in ((want_a, 1), (want_b, 2)):
        got = cache["ssd_state"][:, entry]
        assert float(jnp.linalg.norm(got - want)
                     / jnp.linalg.norm(want)) < 1e-5, entry
    for n in SSD_PLANES:        # entry 0 was nobody's
        assert float(jnp.abs(cache[n][:, 0] - 3.0).max()) == 0.0


@pytest.mark.parametrize("broken", ["ssd_zeroed", "attention_zeroed",
                                    "carry_dropped"])
def test_the_comparison_sees_each_branch_and_the_carried_state(broken,
                                                               monkeypatch):
    """The comparison that decides ``correct`` (the median over positions of
    the relative logit error, held to the rehearsal file's limits) FAILS when
    the program's SSD branch puts out zeros, when its attention branch does,
    and when the carried state is dropped between two chunks; sound, it
    passes far under them. (The seeded weights undo the model's multipliers,
    benchmark/architectures/falcon-h1/weights.py: with the plain draw the
    mixers' outputs are hundredths of the embedding's and this test fails.)"""
    from benchmark import architecture
    from benchmark.weights import make_params

    cfg = architecture.part(REHEARSAL, "program").program_config(
        REHEARSAL, dtype="float32", param_dtype="float32")
    params = make_params(REHEARSAL, 2_190_500_002, "float32")
    tokens = _tokens(8, 46)
    with jax.default_matmul_precision("highest"):
        want = _reference().logits(params, jnp.asarray(tokens), REHEARSAL)
    limit = min(REHEARSAL["correctness"]["limits"].values())

    def median_error(between=None):
        chunk = jax.jit(lambda c, t, rows, st, vl: paged_chunk_prefill(
            params, c, t, rows, st, vl, cfg, context_pages=MPP))
        cache, out = _empty_pool(cfg, 80), []
        for pos in range(0, 46, CHUNK):
            if between is not None and pos:
                cache = between(cache)
            real = min(CHUNK, 46 - pos)
            block = np.zeros((1, CHUNK), np.int32)
            block[0, :real] = tokens[pos:pos + real]
            logits, cache = chunk(
                cache, jnp.asarray(block), jnp.asarray(_row(1))[None],
                jnp.asarray([pos], jnp.int32), jnp.asarray([real], jnp.int32))
            out.append(logits[0, :real])
        return float(np.median(correctness.position_errors(
            jnp.concatenate(out), want)))

    assert median_error() < 1e-4
    if broken == "ssd_zeroed":
        sound = L.ssd_output
        monkeypatch.setattr(L, "ssd_output",
                            lambda *a: jnp.zeros_like(sound(*a)))
        assert median_error() > limit
    elif broken == "attention_zeroed":
        sound = L.attention_block
        monkeypatch.setattr(
            L, "attention_block", lambda *a, **kw: (
                lambda out: (jnp.zeros_like(out[0]), out[1]))(sound(*a, **kw)))
        assert median_error() > limit
    else:
        err = median_error(lambda c: {
            **c, "ssd_state": jnp.zeros_like(c["ssd_state"]),
            "ssd_conv": jnp.zeros_like(c["ssd_conv"])})
        assert err > limit


def test_the_state_is_found_through_the_harnesss_arange_row():
    """``benchmark/correctness.py::engine_logits`` hands the programs ONE
    row ``arange(pages)`` and no slot: the entry is 0, and a second sequence
    through the same row starts from zeros again."""
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


def test_two_rows_of_one_program_do_not_mix():
    """The program over rows: two prompts' chunks at their own starts and a
    dead row between them, against each prompt alone."""
    ta, tb = _tokens(7, 48), _tokens(8, 48)
    ra, rb = _row(0, 8), _row(2, 8)
    cache = _empty_pool(pages=80)
    _, cache = _prefill(BASE, cache, ta, ra, 32)
    block = np.zeros((3, CHUNK), np.int32)
    block[0, :11], block[2] = ta[32:43], tb[:16]
    rows = np.full((3, MPP), -1, np.int32)
    rows[0], rows[2] = ra, rb
    logits, cache = paged_chunk_prefill(
        PARAMS, cache, jnp.asarray(block), jnp.asarray(rows),
        jnp.asarray([32, 0, 0], jnp.int32),
        jnp.asarray([11, 0, 16], jnp.int32), BASE, context_pages=MPP)
    np.testing.assert_allclose(logits[0, :11], _full(BASE, PARAMS, ta)[32:43],
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(logits[2], _full(BASE, PARAMS, tb)[:16],
                               rtol=3e-4, atol=3e-4)
    assert float(jnp.abs(cache["ssd_state"][:, 1]).max()) == 0.0


def test_a_first_pages_copy_carries_the_entry_and_no_other_copy_does():
    cache = {n: jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
             for i, (n, a) in enumerate(_empty_pool().items())}
    out = copy_pages(cache, jnp.asarray([1, 20, 5]), jnp.asarray([0, 30, 7]))
    for n in SSD_PLANES:
        np.testing.assert_array_equal(out[n][:, 0], cache[n][:, 1])
        np.testing.assert_array_equal(out[n][:, 1:], cache[n][:, 1:])
    for n in ("k", "v"):
        np.testing.assert_array_equal(out[n][:, 0], cache[n][:, 1])
        np.testing.assert_array_equal(out[n][:, 30], cache[n][:, 20])
        np.testing.assert_array_equal(out[n][:, 7], cache[n][:, 5])


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


@pytest.mark.parametrize("prefills", [2, 1])
def test_engine_tokens_are_the_full_recomputes(prefills):
    """Four prompts on three slots: chunks interleaved with decode rounds, a
    slot and its entry handed to a second sequence; an iteration with a
    chunk and live slots is two programs (on the CPU the kernels are off
    and the chunk goes the gathered way, which carries no step; where they
    are on it does since PR 58: tests/test_serve_mixed_program.py). An
    engine of one prefill at a time (one chunk a program, no step carried:
    the assistant cell's way until PR 58) sends every chunk through the
    program over rows at one row: the ``[C,V]`` program is never called and
    the head runs at one position a prompt."""
    engine = _engine(max_concurrent_prefills=prefills)
    assert not engine._plan.carries_step and engine._ring == 1
    assert (engine._plan.rows, engine._plan.lone_at_last) == (
        prefills, prefills == 1)
    all_positions, program = [], engine._programs.lone
    engine._programs.lone = lambda *a: all_positions.append(a) or program(*a)
    prompts = [_tokens(31, 75), _tokens(32, 5), _tokens(33, 50),
               _tokens(34, 21)]
    reqs = _serve(engine, prompts, 12)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 12)
    engine._allocator.assert_quiescent()
    counters = engine.counters()
    assert counters["prefill_chunks_dispatched"] == 5 + 1 + 4 + 2
    assert counters["mixed_programs_dispatched"] == 0
    assert counters["state_sequences_started"] == 4
    assert engine._allocator.available(ring=True) == SLOTS
    if prefills == 1:
        assert not all_positions
        assert counters["prefill_head_positions"] == 4
        assert counters["prefill_programs_with_end"] == 4


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
    assert counters["state_sequences_started"] == 3 + counters["preemptions"]
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 30)
    engine._allocator.assert_quiescent()


def test_counters_exist_from_construction_and_name_the_planes_by_kind():
    engine = _engine()
    before = engine.counters()
    assert before["kv_sequence_pool_bytes"] \
        == SLOTS * state_bytes_per_sequence(BASE)
    assert before["kv_global_pool_bytes"] \
        == SLOTS * MPP * PAGE * pool_bytes_per_token(BASE)
    assert before["kv_pool_bytes"] == before["kv_sequence_pool_bytes"] \
        + before["kv_global_pool_bytes"]
    assert before["kv_token_pool_bytes"] == before["kv_global_pool_bytes"]
    assert before["kv_bytes_per_token"] == 3 * 2 * 2 * 16 * 4
    assert before["kv_window_pool_bytes"] == before["state_pool_bytes"] == 0
    assert before["state_sequences_started"] == 0
    _serve(engine, [_tokens(51, 40)], 9)
    after = engine.counters()
    assert set(after) == set(before)
    assert after["state_sequences_started"] == 1
    assert after["prefill_programs_dispatched"] == 3


@pytest.mark.parametrize("option,match", [
    (dict(enable_prefix_caching=True),
     "prefix reuse and the radix copy-on-write tail over parallel layers"),
    (dict(speculative=SpeculativeSpec(mode="ngram")), "speculative verify"),
    (dict(kv_cache_dtype="int8"), "int8 KV"),
    (dict(role="prefill"), "handoff"),
    (dict(host_kv_pages=8), "host tier"),
    (dict(host_kv_pages=8, remote_kv_root="/tmp/x"), "host tier"),
    (dict(lora=LoRASpec(max_adapters=2)), "LoRA"),
    (dict(quantize="int8"), "weight quantization"),
])
def test_what_the_new_kind_cannot_take_yet_is_refused_by_name(option, match):
    with pytest.raises(ValueError) as err:
        _engine(**option)
    assert "parallel layers (attention beside a Mamba-2 mixer)" \
        in str(err.value)
    assert match in str(err.value)


def test_a_mesh_is_refused_by_name():
    from jax.sharding import Mesh

    if len(set(jax.devices())) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="a mesh"):
        LLMEngine(BASE, BatchingSpec(
            max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
            chunked_prefill_tokens=CHUNK, enable_prefix_caching=False),
            params=PARAMS, mesh=Mesh(np.asarray(jax.devices()[:2]),
                                     ("model",)))
