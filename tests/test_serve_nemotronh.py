"""Nemotron-3-Super's structure through the page pool and the engine (ISSUE
61) at the tiny preset on the CPU: an SSD mixer as a block's ONLY operator
(kind "ssd": a state a sequence and no K and V rows) beside attention blocks
in one stack, a block of one sublayer, experts behind a latent projection.
The pool's planes (narrow heads side by side in a lane tile), the step kernel
over such a plane (interpreted), the programs (gathered; in place with the
kernels interpreted; the one that carries a chunk AND the slots' step)
against the benchmark's plain reference, two rows of one program, and through
the engine: tokens against the full recompute, the state bytes stepped
against a count by hand and against the model's need, the spans' attributes,
the refused options by name.

A row of a chunk program hands its SSD state and conv tail to the row behind
it (ISSUE 63): two consecutive chunks of ONE prompt as the rows of one
program against one program after the other, which rows follow and which
write the entry, the mixed program with a row ahead and the slots riding,
and an engine that sends a prompt alone two chunks a program."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture
from benchmark.manifest import load_json
from benchmark.weights import make_params
from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import (
    SSD_PLANES, decoder_forward, holds, init_decoder_params, plane_kind,
)
from kubeflow_tpu.ops import ssd
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    STEP_CARRYING_KINDS, _chunk_in_place, _paged_chunk_in_place,
    _paged_decode_step, _rows_follow, chunk_carries_step, chunk_rows_follow,
    engine_pool_shapes, own_first_pages, paged_chunk_prefill,
    paged_mixed_step, pool_bytes_per_token, sequence_planes,
    state_bytes_per_sequence,
)
from test_serve_mixed_program import _chunk_counts

PAGE, CHUNK, MPP, SLOTS = 8, 16, 16, 3
REHEARSAL = load_json("benchmark/configs/rehearsal-tiny-nemotronh.json")
BASE = preset("tiny-nemotron-h", dtype="float32", param_dtype="float32")
# heads the kernels take (interpreted here): one KV head of 128 for the paged
# attention kernels, and SSD heads of 64 values, TWO to a lane tile of the
# state plane, as at the published widths
WIDE = dataclasses.replace(BASE, n_heads=2, n_kv_heads=1, head_dim=128,
                           ssd_heads=4, ssd_head_dim=64, ssd_groups=2)
WIDE_CONF = {**REHEARSAL, "num_attention_heads": 2, "num_key_value_heads": 1,
             "head_dim": 128, "mamba_num_heads": 4, "mamba_head_dim": 64}


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _params(cfg):
    if cfg is BASE:     # the benchmark's own tree: a balanced bias
        return make_params(REHEARSAL, 11, "float32")
    return init_decoder_params(jax.random.PRNGKey(11), cfg)


def _reference_logits(cfg, tokens):
    conf = REHEARSAL if cfg is BASE else WIDE_CONF
    with jax.default_matmul_precision("highest"):
        return architecture.part(REHEARSAL, "reference").logits(
            _params(cfg), jnp.asarray(tokens), conf)


# -- the planes ----------------------------------------------------------------------

def test_an_ssd_layer_holds_an_entry_a_sequence_and_no_rows():
    assert [plane_kind(n) for n in SSD_PLANES] == ["ssd"] * 2
    assert holds("ssd", "ssd_state") and not holds("ssd", "k")
    assert BASE.layers_holding("ssd") == 3 and \
        BASE.layers_holding("attention") == 1
    assert [p[:2] for p in sequence_planes(BASE)] == [
        ("ssd_state", (4, 16, 16)), ("ssd_conv", (3, 128))]
    assert own_first_pages(BASE) == 1
    shapes = {n: s for n, (s, _) in engine_pool_shapes(
        BASE, SLOTS, 40, PAGE).items()}
    assert shapes == {
        "k": (1, 40, PAGE, 2, 16), "v": (1, 40, PAGE, 2, 16),
        "ssd_state": (3, SLOTS, 4, 16, 16), "ssd_conv": (3, SLOTS, 3, 128),
        "moe_rows": (2,)}
    assert pool_bytes_per_token(BASE) == 2 * 2 * 16 * 4
    assert state_bytes_per_sequence(BASE) == 3 * (4 * 16 * 16 + 3 * 128) * 4
    # the mixer hands a chunk's end to the row behind it (ISSUE 63); beside a
    # kind that does not, the stack sends no row ahead
    assert "ssd" in STEP_CARRYING_KINDS and chunk_rows_follow(BASE)
    assert not chunk_rows_follow(preset("tiny-falconh1"))
    # at the published widths: heads of 64 lie two to a lane tile
    cfg = architecture.part(REHEARSAL, "program").program_config(
        load_json("benchmark/configs/nemotron-3-super-120b-a12b.json"))
    assert ssd.heads_a_tile(128, 8, 64) == 2
    pool = engine_pool_shapes(cfg, 128, 2944, 128)
    assert pool["ssd_state"][0] == (5, 128, 64, 128, 128)
    assert pool["ssd_conv"][0] == (5, 128, 3, 10240)
    assert pool["k"][0] == (1, 2944, 128, 2, 128)
    assert pool_bytes_per_token(cfg) == 1024
    assert state_bytes_per_sequence(cfg) == 21_278_720


def _packed_by_transposition(state, r: int):
    """``ssd.pack_state`` as it was written up to PR 61: heads in sets of
    ``r``, the set's axis moved behind the state's rows, the two merged."""
    *lead, h, n, p = state.shape
    return jnp.swapaxes(state.reshape(*lead, h // r, r, n, p), -3, -2) \
        .reshape(*lead, h // r, n, r * p)


def _unpacked_by_transposition(state, heads: int):
    """``ssd.unpack_state`` as it was written up to PR 61."""
    *lead, hp, n, wide = state.shape
    r = heads // hp
    return jnp.swapaxes(state.reshape(*lead, hp, n, r, wide // r), -3, -2) \
        .reshape(*lead, heads, n, wide // r)


@pytest.mark.parametrize("h,g,p,r", [
    (128, 8, 64, 2), (32, 2, 128, 1), (4, 2, 16, 1), (16, 2, 32, 4),
    (6, 2, 64, 1)])
def test_narrow_heads_of_a_group_share_a_lane_tile(h, g, p, r):
    assert ssd.heads_a_tile(h, g, p) == r
    state = jax.random.normal(jax.random.PRNGKey(0), (2, h, 8, p))
    packed = ssd.pack_state(state, r)
    assert packed.shape == (2, h // r, 8, r * p)
    np.testing.assert_array_equal(ssd.unpack_state(packed, h), state)
    # the DEFINITION, whatever form the chip's compiler is handed (PR 62)
    np.testing.assert_array_equal(packed, _packed_by_transposition(state, r))
    np.testing.assert_array_equal(
        ssd.unpack_state(packed, h), _unpacked_by_transposition(packed, h))
    if r > 1:       # head r j + i in lanes i p .. of packed head j
        np.testing.assert_array_equal(packed[:, 1, :, p:2 * p],
                                      state[:, r + 1])
    else:           # nothing to turn: the argument itself
        assert packed is state and ssd.unpack_state(state, h) is state


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_step_over_a_packed_plane_is_the_recurrence(impl):
    """Heads of 64, two to a tile: live rows' entries move as the plain
    recurrence says, a fresh row starts from zeros, a dead row and every
    other entry stay as they were."""
    b, h, p, g, n, e = 3, 8, 64, 2, 16, 7
    ks = jax.random.split(jax.random.PRNGKey(1), 7)
    ops = (jax.random.normal(ks[0], (b, h, p)),
           jax.nn.softplus(jax.random.normal(ks[1], (b, h)) - 1.0),
           -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.7)),
           jax.random.normal(ks[3], (b, g, n)),
           jax.random.normal(ks[4], (b, g, n)),
           jax.random.normal(ks[5], (h,)))
    state = jax.random.normal(ks[6], (e, h, n, p))
    idx = jnp.asarray([4, 1, 6])
    fresh = jnp.asarray([False, True, False])
    live = jnp.asarray([True, True, False])
    want_y, want_s = ssd.ssd_step_xla(*ops, jnp.where(
        fresh[:, None, None, None], 0.0, state[idx]))
    y, plane = ssd.ssd_step(*ops, ssd.pack_state(state, 2), idx, fresh, live,
                            impl=impl)
    got = ssd.unpack_state(plane, h)
    np.testing.assert_allclose(y[:2], want_y[:2], rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y[2]).max()) == 0.0
    np.testing.assert_allclose(got[idx[:2]], want_s[:2], rtol=1e-5,
                               atol=1e-5)
    untouched = jnp.asarray([0, 2, 3, 5, 6])
    np.testing.assert_array_equal(got[untouched], state[untouched])


# -- the programs against the plain reference -------------------------------------------

def _empty_pool(cfg=BASE, pages=80):
    return {n: jnp.zeros(s, d) for n, (s, d) in engine_pool_shapes(
        cfg, SLOTS, pages, PAGE).items()}


@functools.lru_cache(maxsize=None)
def _programs(cfg, impl):
    params = _params(cfg)
    chunk = jax.jit(lambda c, t, rows, st, vl: paged_chunk_prefill(
        params, c, t, rows, st, vl, cfg, context_pages=MPP,
        paged_attn_impl=impl))
    step = jax.jit(lambda c, table, t, ln, lv: _paged_decode_step(
        params, {**c, "table": table}, t, ln, lv, cfg, attn_impl=impl))
    return chunk, step


def _prefill(cfg, cache, tokens, row, plen, impl="gather", chunk=CHUNK):
    out = []
    for pos in range(0, plen, chunk):
        real = min(chunk, plen - pos)
        block = np.zeros((1, CHUNK), np.int32)
        block[0, :real] = tokens[pos:pos + real]
        logits, cache = _programs(cfg, impl)[0](
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
        logits, cache = _programs(cfg, impl)[1](
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


@pytest.mark.parametrize("impl,plen", [
    ("gather", 13), ("gather", 40), ("gather", 101), ("pallas", 40),
    ("pallas", 53)])
def test_chunks_then_steps_through_the_pool_are_the_plain_reference(impl,
                                                                    plen):
    """Logits through the pool against the reference's ONE full forward
    (which shares no code with the program): the attention block's K and V a
    token in its pages, every mixer's state carried chunk to chunk and step
    to step at ``table_row[0]``, over a dirty pool. 13 and 53 end inside an
    SSD block of 8 positions, 40 on its edge, 101 is seven chunks; the steps
    cross a page's end. "pallas": the chunk in place and both SSD kernels
    interpreted, the state plane two heads a tile."""
    cfg = WIDE if impl == "pallas" else BASE
    tokens = _tokens(plen, plen + 5)
    want = _reference_logits(cfg, tokens)
    dirty = {n: (jnp.full_like(a, 3.0) if n in SSD_PLANES else a)
             for n, a in _empty_pool(cfg).items()}
    row = _row(2)
    got, cache = _prefill(cfg, dirty, tokens, row, plen, impl)
    np.testing.assert_allclose(got, want[:plen], rtol=5e-4, atol=5e-4)
    got, cache = _decode(cfg, cache, tokens, row, plen, 5, impl)
    np.testing.assert_allclose(got, want[plen:], rtol=5e-4, atol=5e-4)
    for n in SSD_PLANES:      # entries 0 and 1 were nobody's: untouched
        assert float(jnp.abs(cache[n][:, :2] - 3.0).max()) == 0.0
    assert _chunk_in_place(dirty, cfg, None, impl) == (impl == "pallas")
    assert chunk_carries_step(dirty, cfg, None, impl) == (impl == "pallas")


def test_the_entry_a_prompt_leaves_is_the_references_carried_state():
    """The packed plane after 53 tokens through the in-place chunk programs
    against the states the reference's token-by-token walk ends in."""
    tokens = _tokens(23, 53)
    with jax.default_matmul_precision("highest"):
        want = jnp.swapaxes(architecture.part(
            REHEARSAL, "reference").carried_states(
                _params(WIDE), jnp.asarray(tokens), WIDE_CONF), 2, 3)
    _, cache = _prefill(WIDE, _empty_pool(WIDE), tokens, _row(1), 53,
                        "pallas")
    got = ssd.unpack_state(cache["ssd_state"][:, 1], WIDE.ssd_heads)
    assert cache["ssd_state"].shape[2:] == (2, 16, 128)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5


@pytest.mark.parametrize("rows", [1, 2])
def test_a_chunk_moves_its_entry_as_the_transposed_form_moved_it(
        rows, monkeypatch):
    """``paged._ssd`` over a chunk of one row (a dynamic slice of the plane:
    where the chip's compiler took PR 61's transposition onto the whole
    plane) and of two (a gather), heads of 64 two to a lane tile, a dirty
    plane: the mixer's output and both planes as written, entry for entry,
    against the same call with pack and unpack as PR 61 wrote them. A
    permutation of float32 entries rounds nothing: EQUAL, not close."""
    from kubeflow_tpu.serve import paged

    cfg = WIDE
    sp = jax.tree.map(lambda a: a[1], _params(cfg)["layers"]["ssd"])
    ks = jax.random.split(jax.random.PRNGKey(62), 3)
    shapes = engine_pool_shapes(cfg, SLOTS, 8, PAGE)
    pools = {n: jax.random.normal(k, (2 * SLOTS, *shapes[n][0][2:]),
                                  shapes[n][1])
             for n, k in zip(SSD_PLANES, ks)}
    assert pools["ssd_state"].shape[1:] == (2, 16, 128)
    h = jax.random.normal(ks[2], (rows, CHUNK, cfg.hidden))
    entries = [4, 2][:rows]
    args = (jnp.asarray([16, 0][:rows], jnp.int32),         # start
            jnp.asarray([13, 16][:rows], jnp.int32), pools,  # valid
            jnp.asarray(entries, jnp.int32))
    got_y, got = paged._ssd(sp, h, *args, cfg, "pallas")
    monkeypatch.setattr(ssd, "pack_state", _packed_by_transposition)
    monkeypatch.setattr(ssd, "unpack_state", _unpacked_by_transposition)
    want_y, want = paged._ssd(sp, h, *args, cfg, "pallas")
    np.testing.assert_array_equal(got_y, want_y)
    for n in SSD_PLANES:
        np.testing.assert_array_equal(got[n], want[n])
        moved = np.any(np.asarray(got[n] != pools[n]),
                       axis=tuple(range(1, got[n].ndim)))
        assert moved.tolist() == [e in entries for e in range(2 * SLOTS)]


@functools.lru_cache(maxsize=None)
def _in_place(cfg, impl):
    """The chunk program over rows as the engine builds it (the head at a
    row's last valid position), IN PLACE under either arm: "gather" runs the
    mixers' XLA forms (``ssd_scan_xla``), "pallas" the kernels interpreted."""
    params = _params(cfg)
    return jax.jit(lambda c, t, rows, st, vl: _paged_chunk_in_place(
        params, c, t, rows, st, vl, cfg, impl, "last"))


def _send(cfg, impl, cache, rows):
    """``rows``: (tokens, table row, start, valid) each, None a dead row."""
    block = np.zeros((len(rows), CHUNK), np.int32)
    table = np.full((len(rows), MPP), -1, np.int32)
    start, valid = (np.zeros((len(rows),), np.int32) for _ in range(2))
    for r, row in enumerate(rows):
        if row is not None:
            tokens, table[r], start[r], valid[r] = row
            block[r, :valid[r]] = tokens[start[r]:start[r] + valid[r]]
    return _in_place(cfg, impl)(cache, *map(jnp.asarray, (
        block, table, start, valid)))


def _planes(cache):
    """The pool's planes: not the expert rows' running sums, which count a
    program's every row, dead ones too."""
    return [n for n in cache if n != "moe_rows"]


def _dirty(cfg):
    return {n: (jnp.full_like(a, 3.0) if n in SSD_PLANES else a)
            for n, a in _empty_pool(cfg).items()}


ARMS = [("gather", BASE), ("pallas", WIDE)]


def _same(got, want, impl, what):
    """Bit for bit under the XLA forms against programs of the same width
    (the handed state is the float32 one, the tail the stored type's: the
    same operations on the same bits; a program of another width rounds its
    matrix products another way on the CPU, hand-over or none); the kernel's
    own tolerance interpreted."""
    if impl == "gather":
        np.testing.assert_array_equal(got, want, err_msg=what)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=what)


@pytest.mark.parametrize("first", [0, CHUNK], ids=["fresh", "held"])
@pytest.mark.parametrize("impl,cfg", ARMS, ids=[a for a, _ in ARMS])
def test_a_row_behind_starts_where_the_row_in_front_ends(impl, cfg, first):
    """Two consecutive chunks of ONE prompt as the two rows of one program
    (the same table row, the second start a chunk on; the second 11 tokens
    long and the prompt's last) against two one-row programs sent one after
    the other, over a dirty pool: every plane (the states, the conv tails, K
    and V) and the last position's logits. ``fresh``: the row in front
    starts its sequence; ``held``: a chunk of the prompt is in the pool."""
    tokens, row = _tokens(63, first + CHUNK + 11), _row(1)
    cache = _dirty(cfg)
    if first:
        _, cache = _send(cfg, impl, cache, [(tokens, row, 0, CHUNK)])
    front, behind = (tokens, row, first, CHUNK), \
        (tokens, row, first + CHUNK, 11)
    _, after = _send(cfg, impl, cache, [front, None])
    want, after = _send(cfg, impl, after, [None, behind])
    got, planes = _send(cfg, impl, cache, [front, behind])
    _same(got[1], want[1], impl, "the logits of the row behind")
    for n in _planes(after):
        _same(planes[n], after[n], impl, f"plane {n}")
    _, after = _send(cfg, impl, cache, [front])
    want, after = _send(cfg, impl, after, [behind])
    _same(got[1], want[0], "one row wide", "the logits of the row behind")
    for n in _planes(after):
        _same(planes[n], after[n], "one row wide", f"plane {n}")
    np.testing.assert_allclose(
        got[1], _reference_logits(cfg, tokens)[first + CHUNK + 10],
        rtol=5e-4, atol=5e-4)
    for n in SSD_PLANES:        # entries 0 and 2 were nobody's
        assert float(jnp.abs(planes[n][:, [0, 2]] - 3.0).max()) == 0.0
    # three rows of one sequence in one program: a run of two hand-overs
    longer = _tokens(64, 3 * CHUNK)
    rows = [(longer, row, i * CHUNK, CHUNK) for i in range(3)]
    after = cache
    for i, r in enumerate(rows):
        want, after = _send(cfg, impl, after,
                            [None] * i + [r] + [None] * (2 - i))
    got, planes = _send(cfg, impl, cache, rows)
    _same(got[2], want[2], impl, "the logits of the third row")
    for n in _planes(after):
        _same(planes[n], after[n], impl, f"plane {n}, three rows")


@pytest.mark.parametrize("impl,cfg", [("gathered", BASE), *ARMS],
                         ids=["gathered", *(a for a, _ in ARMS)])
def test_two_rows_of_one_program_do_not_mix(impl, cfg):
    """The program over rows: two prompts' chunks at their own starts and a
    dead row between them, against each prompt alone. ``gathered``: the form
    every pool takes without the kernels (``decoder_forward``'s cache path).
    In place, the second prompt's row starts where the first one's ENDS (32 +
    16 against 48) under ANOTHER entry: a row follows the row in front only
    within one sequence, so nothing is handed on."""
    ta, tb = _tokens(7, 48), _tokens(8, 64)
    ra, rb = _row(0, 8), _row(2, 8)
    if impl == "gathered":
        params = _params(BASE)
        _, cache = _prefill(BASE, _empty_pool(), ta, ra, 32)
        block = np.zeros((3, CHUNK), np.int32)
        block[0, :11], block[2] = ta[32:43], tb[:16]
        rows = np.full((3, MPP), -1, np.int32)
        rows[0], rows[2] = ra, rb
        logits, cache = paged_chunk_prefill(
            params, cache, jnp.asarray(block), jnp.asarray(rows),
            jnp.asarray([32, 0, 0], jnp.int32),
            jnp.asarray([11, 0, 16], jnp.int32), BASE, context_pages=MPP)
        np.testing.assert_allclose(logits[0, :11],
                                   _reference_logits(BASE, ta)[32:43],
                                   rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(logits[2],
                                   _reference_logits(BASE, tb)[:16],
                                   rtol=5e-4, atol=5e-4)
        assert float(jnp.abs(cache["ssd_state"][:, 1]).max()) == 0.0
        return
    cache = _empty_pool(cfg)
    for held in ([(ta, ra, 0, CHUNK)], [(ta, ra, CHUNK, CHUNK)],
                 [(tb, rb, 0, CHUNK)], [(tb, rb, CHUNK, CHUNK)],
                 [(tb, rb, 2 * CHUNK, CHUNK)]):
        _, cache = _send(cfg, impl, cache, held)
    a, b = (ta, ra, 32, CHUNK), (tb, rb, 48, CHUNK)
    got, planes = _send(cfg, impl, cache, [a, None, b])
    np.testing.assert_allclose(got[0], _reference_logits(cfg, ta)[47],
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(got[2], _reference_logits(cfg, tb)[63],
                               rtol=5e-4, atol=5e-4)
    # ... and side by side, where a row in front is all that tells them apart
    want_a, after = _send(cfg, impl, cache, [a, None])
    want_b, after = _send(cfg, impl, after, [None, b])
    got, planes = _send(cfg, impl, cache, [a, b])
    _same(got[0], want_a[0], impl, "the first prompt's row")
    _same(got[1], want_b[1], impl, "the second prompt's row")
    for n in _planes(after):
        _same(planes[n], after[n], impl, f"plane {n}")
    assert float(jnp.abs(planes["ssd_state"][:, 1]).max()) == 0.0


def test_which_rows_follow_and_which_write():
    """``_rows_follow`` over what a program is handed, and the entries the
    mixer writes: a row follows the row in front iff both are live, name the
    same entry, the start is a whole chunk on and the row in front is FULL;
    row 0 never does. Only the LAST row of a run writes its sequence's entry:
    in the planes ``_ssd`` leaves, a run's entry holds the state and the tail
    its last row ended in, the entry of a followed row that is nobody's last
    row is untouched, and a poisoned state handed to a FOLLOWED row's write
    never lands."""
    from kubeflow_tpu.ops import ssd
    from kubeflow_tpu.serve import paged

    total, t = 12, CHUNK

    def follows(entry, start, valid):
        return _rows_follow(*(jnp.asarray(v, jnp.int32) for v in (
            entry, start, valid)), t, total).tolist()

    assert follows([4, 4], [16, 32], [16, 9]) == [False, True]
    assert follows([4, 4, 4], [0, 16, 32], [16, 16, 1]) == [False, True, True]
    assert follows([4, 5], [16, 32], [16, 16]) == [False, False]   # another
    assert follows([4, 4], [16, 32], [11, 16]) == [False, False]   # not full
    assert follows([4, 4], [16, 16], [16, 16]) == [False, False]   # no chunk on
    assert follows([4, 4], [16, 48], [16, 16]) == [False, False]
    assert follows([12, 12], [0, 16], [16, 16]) == [False, False]  # both dead
    assert follows([4, 12, 4], [0, 0, 16], [16, 0, 16]) == [False] * 3
    assert follows([4, 4, 7, 7], [0, 16, 32, 48], [16] * 4) == [
        False, True, False, True]

    cfg = WIDE
    sp = jax.tree.map(lambda a: a[1], _params(cfg)["layers"]["ssd"])
    ks = jax.random.split(jax.random.PRNGKey(63), 3)
    shapes = engine_pool_shapes(cfg, SLOTS, 8, PAGE)
    pools = {n: jax.random.normal(k, (2 * SLOTS, *shapes[n][0][2:]),
                                  shapes[n][1])
             for n, k in zip(SSD_PLANES, ks)}
    h = jax.random.normal(ks[2], (3, CHUNK, cfg.hidden))
    args = (jnp.asarray([16, 32, 0], jnp.int32),            # start
            jnp.asarray([16, 16, 13], jnp.int32), pools,    # valid
            jnp.asarray([4, 4, 2], jnp.int32))              # entry
    _, got = paged._ssd(sp, h, *args, cfg, "pallas")
    # one row after the other: rows 0 and 1 are entry 4's run, row 2 alone
    want = pools
    for r in range(3):
        _, want = paged._ssd(sp, h[r:r + 1], args[0][r:r + 1],
                             args[1][r:r + 1], want, args[3][r:r + 1], cfg,
                             "pallas")
    for n in SSD_PLANES:
        np.testing.assert_allclose(got[n], want[n], rtol=2e-5, atol=2e-5)
        moved = np.any(np.asarray(got[n] != pools[n]),
                       axis=tuple(range(1, got[n].ndim)))
        assert moved.tolist() == [e in (4, 2) for e in range(2 * SLOTS)]

    # what the FOLLOWED row would have written is poison: it must not land
    chunk = ssd.ssd_chunk

    def poisoned(*a, **kw):
        y, ends = chunk(*a, **kw)
        return y, ends.at[0].set(jnp.nan)

    try:
        ssd.ssd_chunk = poisoned
        _, got = paged._ssd(sp, h, *args, cfg, "pallas")
    finally:
        ssd.ssd_chunk = chunk
    assert not bool(jnp.isnan(got["ssd_state"]).any())
    np.testing.assert_allclose(got["ssd_state"], want["ssd_state"],
                               rtol=2e-5, atol=2e-5)


def test_the_one_program_carries_a_chunk_and_the_slots_step():
    """``paged_mixed_step`` over this stack: a prompt's four chunks while
    another sequence's slot takes a decode step inside each of those programs
    (a mixer's ``ssd_chunk`` writes the chunk row's entry, ``ssd_step`` the
    slot's; the block of one sublayer and the expert layers, once over both
    groups' tokens, in one layer scan). The slot's tokens are the full
    forward's greedy ones, the last chunk's logits the reference's at the
    prompt's last position, nobody's entry untouched."""
    cfg, impl = WIDE, "pallas"
    params = _params(cfg)
    ta, tb = _tokens(23, 53), _tokens(24, 21)
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
             for n, a in _empty_pool(cfg).items()}
    logits_b, cache = _prefill(cfg, dirty, tb, row_b, 21, impl)
    fed = [int(jnp.argmax(logits_b[-1]))]
    table = np.full((SLOTS, MPP), -1, np.int32)
    table[0] = row_b
    live = jnp.asarray([True, False, False])
    for pos in range(0, 53, CHUNK):
        real = min(CHUNK, 53 - pos)
        block = np.zeros((1, CHUNK), np.int32)
        block[0, :real] = ta[pos:pos + real]
        tok = np.zeros((SLOTS,), np.int32)
        lens = np.zeros((SLOTS,), np.int32)
        tok[0], lens[0] = fed[-1], 21 + len(fed) - 1
        logits, out, cache, *_ = mixed(
            {**cache, "table": jnp.asarray(table)}, jnp.asarray(block),
            jnp.asarray(row_a)[None], jnp.asarray([pos], jnp.int32),
            jnp.asarray([real], jnp.int32), jnp.asarray([pos + real == 53]),
            jnp.asarray(True), jnp.asarray(tok), jnp.asarray(lens), live)
        cache.pop("table")
        assert np.asarray(out)[1:, 0].tolist() == [-1, -1]
        fed.append(int(out[0, 0]))
    stream = np.concatenate([tb, np.asarray(fed, np.int32)])
    full_b = decoder_forward(params, jnp.asarray(stream)[None], cfg)[0][0]
    assert fed == [int(t) for t in jnp.argmax(full_b[20:25], axis=-1)]
    np.testing.assert_allclose(logits[0], _reference_logits(cfg, ta)[52],
                               rtol=5e-4, atol=5e-4)
    for n in SSD_PLANES:        # entry 0 was nobody's
        assert float(jnp.abs(cache[n][:, 0] - 3.0).max()) == 0.0


# -- through the engine ------------------------------------------------------------------

def _engine(**kw):
    spec = dict(max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                decode_steps=4, max_concurrent_prefills=2)
    return LLMEngine(BASE, BatchingSpec(**{**spec, **kw}),
                     params=_params(BASE))


@functools.lru_cache(maxsize=None)
def _full_padded(cfg):
    params = _params(cfg)
    return jax.jit(lambda t: decoder_forward(params, t[None], cfg)[0][0])


def _greedy(prompt, n, cfg=BASE):
    """``n`` greedy tokens behind ``prompt`` by full recompute."""
    toks, out = list(prompt), []
    for _ in range(n):
        padded = np.zeros((PAGE * MPP,), np.int32)
        padded[:len(toks)] = toks
        t = int(jnp.argmax(_full_padded(cfg)(jnp.asarray(padded))[
            len(toks) - 1]))
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


def test_engine_tokens_are_the_full_recomputes_and_the_state_is_counted(
        monkeypatch):
    """Four prompts on three slots: chunks interleaved with decode rounds, a
    slot and its entry handed to a second sequence. ``state_bytes_stepped``
    against a count by hand (every decode step reads AND writes every live
    row's entry in the three mixers) and against the model's own need
    (``counts.state_bytes_per_sequence``); the dispatch spans say the same
    of each round."""
    from test_serve_chunk_rows import record_spans

    engine = _engine()
    assert not engine._plan.carries_step        # the CPU: gathered chunks
    before = engine.counters()
    assert before["state_bytes_stepped"] == 0
    assert before["kv_sequence_pool_bytes"] \
        == SLOTS * state_bytes_per_sequence(BASE)
    seen = record_spans(monkeypatch)
    prompts = [_tokens(31, 75), _tokens(32, 5), _tokens(33, 50),
               _tokens(34, 21)]
    reqs = _serve(engine, prompts, 10)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(p, 10)
    engine._allocator.assert_quiescent()
    after = engine.counters()
    assert set(after) == set(before)
    assert after["state_sequences_started"] == 4
    rounds = [a for name, a in seen if name == "engine.decode_dispatch"]
    entry = 3 * (4 * 16 * 16 + 3 * 128) * 4     # three mixers, float32
    counts = architecture.part(REHEARSAL, "counts")
    assert entry == counts.state_bytes_per_sequence(REHEARSAL, 4) \
        == state_bytes_per_sequence(BASE)
    by_hand = sum(2 * entry * r["k_steps"] * r["live"] for r in rounds)
    assert after["state_bytes_stepped"] == by_hand > 0
    assert all(r["live_rows"] == r["live"] and r["state_bytes"]
               == 2 * entry * r["k_steps"] * r["live"] for r in rounds)
    chunks = [a for name, a in seen if name == "engine.prefill_dispatch"]
    assert chunks and all(c["live_rows"] == c["state_bytes"] == 0
                          for c in chunks)      # no step rides on the CPU
    # the expert rows: a quarter held, level by the stratified bias
    held = after["expert_rows_held"] / after["expert_rows_routed"]
    assert 0.15 < held < 0.35


def _ahead_engine(**kw):
    spec = dict(max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                decode_steps=1, prefill_interleave_steps=1,
                max_concurrent_prefills=2, paged_attn_impl="pallas")
    return LLMEngine(WIDE, BatchingSpec(**{**spec, **kw}),
                     params=_params(WIDE))


@pytest.mark.parametrize("chunks", [2, 3, 4, 5])
def test_a_prompt_alone_goes_two_chunks_a_program_and_hands_its_state_on(
        chunks):
    """The kernels interpreted, the plan the agent-turns cell's (two rows,
    the step carried, spare rows AHEAD): a prompt of so many chunks sent
    alone goes two chunks a program, the row behind from the state and the
    tail the row in front ends in; an odd last chunk beside a dead row. Its
    greedy tokens are the full recompute's."""
    engine = _ahead_engine()
    plan = engine._plan
    assert (plan.carries_step, plan.rows, plan.ahead, plan.rows_only) \
        == (True, 2, True, False)
    prompt = _tokens(70 + chunks, (chunks - 1) * CHUNK + 5)
    (req,) = _serve(engine, [prompt], 6)
    assert req.output_tokens == _greedy(prompt, 6, WIDE)
    # no slot is live while it prefills: the odd last chunk of an engine
    # with nothing else to do takes the one-row program (``ChunkPlan.send``)
    assert _chunk_counts(engine) == (-(-chunks // 2), chunks, chunks // 2, 0)
    engine._allocator.assert_quiescent()


@pytest.mark.parametrize("prefills", [2, 3])
def test_prompts_at_once_take_their_rows_and_the_rest_goes_ahead(prefills):
    """Prompts of 2, 3, 4 and 5 chunks, two at once and then two more beside
    their streams: a program's rows are the due chunks of the prompts in it
    and, where rows are spare, the chunks BEHIND them, each right behind its
    own prompt's row (three rows wide: ``[A, A + C, B]``, never ``[A, B, A +
    C]``: a row takes the state of the row in front of it). Every prompt's
    tokens are the full recompute's; rows went ahead, and the odd chunks
    beside a live stream left theirs dead."""
    engine = _ahead_engine(max_concurrent_prefills=prefills,
                           max_batch_size=4)
    assert engine._plan.ahead and engine._plan.rows == prefills
    sent_rows, rows_of = [], engine._rows_of
    engine._rows_of = lambda group: (
        sent_rows.append([(id(ch), pos) for ch, pos in rows_of(group)]),
        rows_of(group))[1]
    prompts = [_tokens(80 + n, (n - 1) * CHUNK + 3 + n) for n in (2, 3, 4, 5)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    reqs = [engine.submit([int(t) for t in p], sp) for p in prompts[2:]]
    for _ in range(4000):
        if len(reqs) == 2 and any(r.first_token_time for r in reqs):
            reqs += [engine.submit([int(t) for t in p], sp)
                     for p in prompts[:2]]
        if len(reqs) == 4 and all(r.done.is_set() for r in reqs):
            break
        engine.step()
    for p, r in zip(prompts[2:] + prompts[:2], reqs):
        assert r.output_tokens == _greedy(p, 8, WIDE)
    programs, sent, ahead, _ = _chunk_counts(engine)
    assert sent == 2 + 3 + 4 + 5 and ahead > 0 and programs < sent
    assert engine.counters()["mixed_programs_dispatched"] > 0
    for rows in sent_rows:      # a prefill's rows one behind the other
        for (a, pa), (b, pb) in zip(rows, rows[1:]):
            assert pb == pa + CHUNK if a == b else b not in [
                r[0] for r in rows[:rows.index((b, pb))]]
    if prefills == 3:       # two prompts due, the spare row right behind ITS
        assert any(len(rows) == 3 and rows[0][0] == rows[1][0] != rows[2][0]
                   for rows in sent_rows)
    engine._allocator.assert_quiescent()


def test_the_counter_is_zero_for_a_stack_that_keeps_no_state():
    cfg = preset("tiny", dtype="float32", param_dtype="float32")
    engine = LLMEngine(cfg, BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=8,
        chunked_prefill_tokens=16), params=init_decoder_params(
            jax.random.PRNGKey(0), cfg))
    _serve(engine, [_tokens(1, 12)], 4)
    assert engine.counters()["state_bytes_stepped"] == 0
    assert engine.counters()["decode_steps_dispatched"] > 0


def test_the_metrics_endpoint_exposes_the_bytes_stepped():
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.serve.server import ModelServer

    engine = _engine()
    server = ModelServer("m", engine)
    _serve(engine, [_tokens(51, 20)], 5)
    values = {name: v for name, labels, v in parse_exposition(
        server.metrics_text()) if labels.get("model") == "m"}
    assert values["kftpu_engine_state_bytes_stepped_total"] \
        == engine.counters()["state_bytes_stepped"] > 0


@pytest.mark.parametrize("option,match", [
    (dict(enable_prefix_caching=True),
     "prefix reuse and the radix copy-on-write tail over ssd layers"),
    (dict(speculative=SpeculativeSpec(mode="ngram")), "speculative verify"),
    (dict(kv_cache_dtype="int8"), "int8 KV"),
    (dict(role="prefill"), "handoff"),
    (dict(host_kv_pages=8), "host tier"),
    (dict(host_kv_pages=8, remote_kv_root="/tmp/x"), "host tier"),
    (dict(lora=LoRASpec(max_adapters=2)), "LoRA"),
    (dict(quantize="int8"), "weight quantization"),
])
def test_what_this_stack_cannot_take_yet_is_refused_by_name(option, match):
    with pytest.raises(ValueError) as err:
        _engine(**option)
    for has in ("ssd layers (a Mamba-2 mixer alone)",
                "blocks of one sublayer", "experts behind a latent "
                "projection of 32", "expert layers that hold 4 of 16"):
        assert has in str(err.value)
    assert match in str(err.value)


def test_a_mesh_is_refused_by_name():
    from jax.sharding import Mesh

    if len(set(jax.devices())) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="a mesh"):
        LLMEngine(BASE, BatchingSpec(
            max_batch_size=SLOTS, max_seq_len=PAGE * MPP, page_size=PAGE,
            chunked_prefill_tokens=CHUNK, enable_prefix_caching=False),
            params=_params(BASE), mesh=Mesh(np.asarray(jax.devices()[:2]),
                                            ("model",)))
