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
the refused options by name."""

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
    STEP_CARRYING_KINDS, _chunk_in_place, _paged_decode_step,
    chunk_carries_step, chunk_rows_follow, engine_pool_shapes,
    own_first_pages, paged_chunk_prefill, paged_mixed_step,
    pool_bytes_per_token, sequence_planes, state_bytes_per_sequence,
)

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
    assert "ssd" in STEP_CARRYING_KINDS and not chunk_rows_follow(BASE)
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


def test_two_rows_of_one_program_do_not_mix():
    """The program over rows: two prompts' chunks at their own starts and a
    dead row between them, against each prompt alone."""
    ta, tb = _tokens(7, 48), _tokens(8, 48)
    ra, rb = _row(0, 8), _row(2, 8)
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
    np.testing.assert_allclose(logits[2], _reference_logits(BASE, tb)[:16],
                               rtol=5e-4, atol=5e-4)
    assert float(jnp.abs(cache["ssd_state"][:, 1]).max()) == 0.0


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
def _full_padded():
    params = _params(BASE)
    return jax.jit(lambda t: decoder_forward(params, t[None], BASE)[0][0])


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
