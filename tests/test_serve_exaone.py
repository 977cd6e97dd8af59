"""Window layers beside global ones over ONE page table, and an expert layer
that holds a share of its experts (K-EXAONE's structure), on the normal path
at the tiny preset on the CPU: the window in the plain attention and in both
paged kernels (interpreted), the stack's groups and the pool's planes by
kind, the ring (a window layer keeps logical page ``i`` at ``row[i mod R]``),
the chunk program and the decode step against the full forward, the shares
of all chips adding up to the uncut layer, and through the engine:
allocation of a sequence's first pages from the ring's ids, preemption in
the middle of a ring, the counters and the refused options."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import DecoderConfig, preset
from kubeflow_tpu.models.decoder import (
    WINDOW_PLANES, decoder_forward, decoder_param_specs, init_decoder_params,
    layer_groups,
)
from kubeflow_tpu.ops.attention import multi_head_attention
from kubeflow_tpu.ops.paged_attention import (
    paged_chunk_attention, paged_decode_attention,
)
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.paged import (
    MOE_ROWS, PageAllocator, PagePoolExhausted, _paged_decode_step,
    engine_pool_shapes, paged_chunk_prefill, pool_bytes_per_token,
    pool_shapes, ring_pages, ring_table, window_bytes_per_page,
    window_planes,
)

PAGE, CHUNK, MPP, SLOTS = 8, 16, 16, 2
BASE = preset("tiny-exaone", dtype="float32", param_dtype="float32")
PARAMS = init_decoder_params(jax.random.PRNGKey(11), BASE)


def _cfg(window: int) -> DecoderConfig:
    """The preset at a window shorter (5) or longer (24: its own) than a
    page of 8, with the ring an engine of these sizes would set."""
    cfg = dataclasses.replace(BASE, attn_window=window)
    return dataclasses.replace(cfg, window_ring_pages=ring_pages(
        cfg, CHUNK, PAGE, MPP))


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


def _empty_pool(cfg):
    return {n: jnp.zeros(shape, dt) for n, (shape, dt) in
            engine_pool_shapes(cfg, SLOTS, SLOTS * MPP, PAGE).items()}


@functools.lru_cache(maxsize=None)
def _programs(cfg, impl):
    """The one-row chunk program and the decode step over ``cfg``, jitted
    once a config."""
    chunk = jax.jit(lambda c, t, row, st, vl: paged_chunk_prefill(
        PARAMS, c, t, row, st, vl, cfg, context_pages=MPP,
        paged_attn_impl=impl))
    step = jax.jit(lambda c, table, t, ln, lv: _paged_decode_step(
        PARAMS, {**c, "table": table}, t, ln, lv, cfg, attn_impl=impl))
    return chunk, step


def _prefill(cfg, cache, tokens, row, plen, impl="gather"):
    out = []
    for pos in range(0, plen, CHUNK):
        real = min(CHUNK, plen - pos)
        block = np.zeros((1, CHUNK), np.int32)
        block[0, :real] = tokens[pos:pos + real]
        logits, cache = _programs(cfg, impl)[0](
            cache, jnp.asarray(block), jnp.asarray(row)[None],
            jnp.asarray([pos], jnp.int32), jnp.asarray([real], jnp.int32))
        out.append(logits[0, :real])
    return jnp.concatenate(out), cache


def _decode(cfg, cache, tokens, row, start, n, impl="gather", slot=0):
    table = np.full((SLOTS, MPP), -1, np.int32)
    table[slot] = row
    out = []
    for i in range(start, start + n):
        tok, lens = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), np.int32)
        tok[slot], lens[slot] = tokens[i], i
        logits, cache = _programs(cfg, impl)[1](
            cache, jnp.asarray(table), jnp.asarray(tok), jnp.asarray(lens),
            jnp.asarray(np.arange(SLOTS) == slot))
        cache.pop("table")
        out.append(logits[slot])
    return jnp.stack(out), cache


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda t: decoder_forward(PARAMS, t[None], cfg)[0][0])


def _full(cfg, tokens):
    return _forward(cfg)(jnp.asarray(tokens))


# -- the window in plain attention -------------------------------------------------

@pytest.mark.parametrize("window", [1, 5, 24, 40])
def test_a_window_sees_its_last_keys_and_itself(window):
    key = jax.random.PRNGKey(window)
    q, k, v = (jax.random.normal(kk, (1, 33, n, 16)) for kk, n in zip(
        jax.random.split(key, 3), (4, 2, 2)))
    got = multi_head_attention(q, k, v, window=window)
    for i in (0, 4, 17, 32):
        lo = max(0, i - window + 1)
        alone = multi_head_attention(q[:, i:i + 1], k[:, lo:i + 1],
                                     v[:, lo:i + 1], causal=False)
        np.testing.assert_allclose(got[:, i], alone[:, 0], rtol=2e-5,
                                   atol=2e-5)
    if window >= 33:        # no key is ever behind it: plain causal attention
        np.testing.assert_array_equal(got, multi_head_attention(q, k, v))
    with pytest.raises(ValueError, match="causal"):
        multi_head_attention(q, k, v, causal=False, window=window)


def test_the_flash_path_falls_back_where_a_window_is_set():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 128))
    np.testing.assert_array_equal(
        multi_head_attention(q, q, q, window=16, impl="pallas"),
        multi_head_attention(q, q, q, window=16, impl="xla"))


# -- the stack -----------------------------------------------------------------------

def test_groups_and_the_tree_by_kind():
    groups = [(n, g.layer_kinds, g.n_layers, first)
              for n, g, first in layer_groups(BASE)]
    assert groups == [
        ("dense_layers", ("window",), 1, 0),
        ("layers", ("window", "window", "attention"), 3, 1),
        ("layers_rest", ("window",), 1, 4)]
    assert BASE.kinds == ("window", "window", "window", "attention",
                          "window")
    shapes = jax.tree.map(lambda a: a.shape, PARAMS)
    assert shapes["layers"]["window"]["wq"] == (2, 64, 4, 16)
    assert shapes["layers"]["attn"]["wq"] == (1, 64, 4, 16)
    # the router scores all 16 experts; the stack holds 4 of them
    assert shapes["layers"]["mlp"]["router"] == (3, 64, 16)
    assert shapes["layers"]["mlp"]["router_bias"] == (3, 16)
    assert shapes["layers"]["mlp"]["gate"] == (3, 4, 64, 48)
    assert shapes["layers"]["mlp"]["shared"]["gate"] == (3, 64, 48)
    assert jax.tree.structure(
        decoder_param_specs(BASE), is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, PARAMS))
    with pytest.raises(ValueError, match="attn_window"):
        DecoderConfig(layer_kinds=("window",))
    with pytest.raises(ValueError, match="held"):
        DecoderConfig(num_experts=8, experts_held=6, expert_offset=4)


def test_params_held_and_work_met_here():
    """``num_params`` counts the experts HELD, ``flops_per_token`` the
    experts a token meets HERE (its 4 choices x 4 / 16 held, and the shared
    one)."""
    whole = dataclasses.replace(BASE, experts_held=0)
    one = 3 * 64 * 48
    assert whole.num_params() - BASE.num_params() == 4 * 12 * one
    assert sum(a.size for a in jax.tree.leaves(PARAMS)) == BASE.num_params()
    assert whole.flops_per_token() - BASE.flops_per_token() \
        == 6.0 * 4 * 3 * one
    real = preset("k-exaone-236b-a23b")
    assert 236e9 < real.num_params() < 237e9
    assert real.kinds[:5] == BASE.kinds and real.attn_window == 128


def test_only_window_layers_rotate():
    a, _ = L.init_attention(jax.random.PRNGKey(0), BASE)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 2, 16))
    pos = jnp.arange(6)[None] + 9
    normed = L.rmsnorm(q, a["q_norm"], BASE)
    np.testing.assert_array_equal(L.qk_rope(a, q, k, pos, BASE)[0], normed)
    rotated = L.qk_rope(a, q, k, pos, BASE, window=24)[0]
    np.testing.assert_allclose(rotated, L.rope(normed, pos, BASE.rope_theta))
    assert float(jnp.abs(rotated - normed).max()) > 0.1


# -- an expert layer that holds a share ------------------------------------------------

def _expert_layer(cfg, key=3):
    p, _ = L.init_moe(jax.random.PRNGKey(key), cfg)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(key + 1),
                                                (cfg.num_experts,))
    return p


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer: the parts
    they compute, the shared expert counted ONCE, are the uncut layer's
    result; each routes over all 16 and computes about a quarter of the
    rows."""
    whole = dataclasses.replace(BASE, experts_held=0, moe_impl="sorted")
    p = _expert_layer(whole)
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
    # a dense layer over the same weights agrees (the oracle of the sorted
    # path), and a share under any other path is refused by name
    dense, _ = L.moe_block(p, x, dataclasses.replace(whole, moe_impl="dense"))
    np.testing.assert_allclose(want, dense, rtol=2e-5, atol=2e-5)
    with pytest.raises(NotImplementedError, match="share"):
        L.moe_block(p, x, dataclasses.replace(BASE, moe_impl="dispatch"))


def test_rows_of_absent_experts_never_reach_the_result(monkeypatch):
    """The grouped matmul computes the held groups' rows and leaves the rest
    of its result unwritten (the kernel's uninitialised memory): here every
    row behind the groups comes back as NaN, and none reaches the layer's
    output; however uneven the routing, every row of a held expert does."""
    p = _expert_layer(BASE)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 64))
    want, _, rows = L.moe_block(p, x, BASE, rows_out=True)
    plain = L.grouped_matmul

    def unwritten_behind_the_groups(rows_in, w, sizes, cfg):
        out = plain(rows_in, w, sizes, cfg)
        behind = jnp.arange(out.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(behind, jnp.nan, out)

    monkeypatch.setattr(L, "grouped_matmul", unwritten_behind_the_groups)
    got, _ = L.moe_block(p, x, BASE)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert 0 < int(rows[1]) < int(rows[0]) == 40 * 4
    # a router that sends every token to held experts only: all rows held
    loaded = {**p, "router_bias": jnp.where(jnp.arange(16) < 4, 10.0, 0.0)}
    _, _, rows = L.moe_block(loaded, x, BASE, rows_out=True)
    assert int(rows[1]) == int(rows[0])


# -- the pool and the ring -------------------------------------------------------------

def test_the_pool_holds_each_kind_its_own_planes():
    cfg = _cfg(24)
    assert cfg.window_ring_pages == ring_pages(cfg, CHUNK, PAGE, MPP) == 6
    assert ring_pages(_cfg(5), CHUNK, PAGE, MPP) == 4      # 2 + 1 + 1
    assert ring_pages(preset("k-exaone-236b-a23b"), 512, 128, 72) == 6
    assert ring_pages(preset("tiny"), CHUNK, PAGE, MPP) == 0
    assert ring_pages(cfg, CHUNK, PAGE, 3) == 3            # a short table
    assert window_planes(preset("tiny")) == ()
    assert [n for n, _, _ in window_planes(cfg)] == ["window_k", "window_v"]
    shapes = {n: s for n, (s, _) in
              engine_pool_shapes(cfg, SLOTS, 32, PAGE).items()}
    assert shapes == {"k": (1, 32, PAGE, 2, 16), "v": (1, 32, PAGE, 2, 16),
                      "window_k": (4, 12, PAGE, 2, 16),
                      "window_v": (4, 12, PAGE, 2, 16),
                      MOE_ROWS: (2,)}
    # a caller that sizes no ring gets window planes as large as the pool
    assert pool_shapes(cfg, 32, PAGE)["window_k"][0] == (4, 32, PAGE, 2, 16)
    # a token keeps rows in the global layer only; a ring page in the four
    assert pool_bytes_per_token(cfg) == 2 * 2 * 16 * 4
    assert window_bytes_per_page(cfg, PAGE) == 4 * PAGE * 2 * 2 * 16 * 4
    with pytest.raises(ValueError, match="window layers"):
        window_planes(cfg, kv_quant=True)


def test_a_window_layer_keeps_logical_page_i_at_row_i_mod_r():
    cfg = _cfg(24)
    row = jnp.asarray([[7, 3, 9, 0, 5, 2, 30, 31, 32, -1, -1, -1, -1, -1, -1,
                        -1]], jnp.int32)
    np.testing.assert_array_equal(
        ring_table(row, jnp.asarray([4]), 5, cfg), [[5, 2, 7, 3, 9]])
    np.testing.assert_array_equal(
        ring_table(row, jnp.asarray([0]), 3, dataclasses.replace(
            cfg, window_ring_pages=0)), [[7, 3, 9]])


@pytest.mark.parametrize("window", [5, 24])
@pytest.mark.parametrize("plen", [13, 64, 101])
def test_chunked_prefill_then_decode_is_the_full_forward(window, plen):
    """Logits, through the ring, past several windows and page ends: a
    prompt of up to 13 pages over a ring of 4 or 6."""
    cfg = _cfg(window)
    tokens = _tokens(plen, plen + 12)
    row = np.full((MPP,), -1, np.int32)
    row[:MPP] = np.arange(MPP)          # the harness's row: arange
    want = _full(cfg, tokens)
    got, cache = _prefill(cfg, _empty_pool(cfg), tokens, row, plen)
    np.testing.assert_allclose(got, want[:plen], rtol=2e-4, atol=2e-4)
    got, cache = _decode(cfg, cache, tokens, row, plen, 12)
    np.testing.assert_allclose(got, want[plen:], rtol=2e-4, atol=2e-4)
    # the window matters at these lengths: a model that ignored it is not
    # this model
    if plen > window:
        ignored = _full(dataclasses.replace(cfg, attn_window=4096), tokens)
        assert float(jnp.abs(ignored - want).max()) > 1e-2


def test_a_long_sequence_reads_exactly_its_window_and_its_own_ring():
    """Two sequences whose first pages interleave in the ring's ids: each
    decodes to the full forward's logits while the other writes its own
    ring, and a page of the window planes that neither owns, or that lies
    behind a window, can hold anything."""
    cfg = _cfg(24)
    rows = np.full((2, MPP), -1, np.int32)
    rows[0, :12] = [0, 2, 4, 6, 8, 10] + list(range(12, 18))
    rows[1, :12] = [1, 3, 5, 7, 9, 11] + list(range(18, 24))
    toks = [_tokens(21, 90), _tokens(22, 90)]
    cache = _empty_pool(cfg)
    poison = {n: cache[n] + 1e3 for n in WINDOW_PLANES}
    cache = {**cache, **poison}         # everything unwritten is far off
    want = [_full(cfg, t) for t in toks]
    for s in (0, 1):
        got, cache = _prefill(cfg, cache, toks[s], rows[s], 70)
        np.testing.assert_allclose(got, want[s][:70], rtol=2e-4, atol=2e-4)
    for i in range(70, 90, 5):          # the two take turns
        for s in (0, 1):
            got, cache = _decode(cfg, cache, toks[s], rows[s], i, 5, slot=s)
            np.testing.assert_allclose(got, want[s][i:i + 5], rtol=2e-4,
                                       atol=2e-4)
    # only ring pages were written: ids 12 and up of the window planes do
    # not exist, and the global planes hold every page of both
    assert cache["window_k"].shape[1] == 12
    assert bool(jnp.isfinite(cache["k"][:, rows[0, :12]]).all())


@pytest.mark.parametrize("window", [20, 128])
def test_the_window_kernels_are_the_plain_window_attention(window):
    """Both paged kernels (interpreted) with the window set, at heads of
    128: the decode call over a ring's two or three pages with a lower
    bound, the chunk call over the pages its chunk and window touch."""
    page, kv, d, h = 16, 2, 128, 4
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    n = 96
    k = jax.random.normal(ks[0], (n, kv, d))
    v = jax.random.normal(ks[1], (n, kv, d))
    ids = np.random.default_rng(0).permutation(10)[:n // page]
    pool_k = jnp.zeros((10, page, kv, d)).at[ids].set(
        k.reshape(-1, page, kv, d))
    pool_v = jnp.zeros((10, page, kv, d)).at[ids].set(
        v.reshape(-1, page, kv, d))
    # decode at position 77: pages from that of 77 - window + 1 on
    q = jax.random.normal(ks[2], (1, 1, h, d))
    t = 77
    low = max(t - window + 1, 0)
    first = low // page
    table = jnp.asarray(ids[first:first + (window - 1) // page + 2])[None]
    got = paged_decode_attention(
        q, pool_k, pool_v, table.astype(jnp.int32),
        jnp.asarray([t - first * page], jnp.int32),
        lower=jnp.asarray([low - first * page], jnp.int32))
    want = multi_head_attention(q, k[None, low:t + 1], v[None, low:t + 1],
                                causal=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # a chunk of 32 queries from position 48
    c, start = 32, 48
    qc = jax.random.normal(ks[3], (h, c, d))
    first = max(start - window + 1, 0) // page
    row = jnp.asarray(ids[first:(start + c) // page], jnp.int32)
    got = paged_chunk_attention(qc, pool_k, pool_v, row,
                                start - first * page, window=window)
    want = multi_head_attention(
        jnp.swapaxes(qc, 0, 1)[None], k[None, :start + c],
        v[None, :start + c], q_offset=start, window=window)[0]
    np.testing.assert_allclose(jnp.swapaxes(got, 0, 1), want, rtol=2e-5,
                               atol=2e-5)


# -- the allocator's ring ids ----------------------------------------------------------

def test_ring_ids_go_to_a_sequences_first_pages_only():
    a = PageAllocator(20, PAGE, enable_prefix_caching=False, ring_pages=8)
    assert (a.available(), a.available(ring=True)) == (12, 8)
    first = a.alloc(4, ring=4)
    rest = a.alloc(12)
    assert max(first) < 8 <= min(rest)
    with pytest.raises(PagePoolExhausted):
        a.alloc(1)                      # the ring's ids are not spare pages
    a.free(first + rest)
    assert (a.available(), a.available(ring=True)) == (12, 8)
    mixed = a.alloc(6, ring=2)          # a sequence's first pages, then more
    assert max(mixed[:2]) < 8 <= min(mixed[2:])
    with pytest.raises(PagePoolExhausted):
        a.alloc(9, ring=7)              # all of them or none
    assert (a.available(), a.available(ring=True)) == (8, 6)
    a.free(mixed)
    a.assert_quiescent()


# -- through the engine --------------------------------------------------------------------

def _engine(window=24, **kw):
    cfg = dataclasses.replace(BASE, attn_window=window)
    spec = dict(max_batch_size=3, max_seq_len=PAGE * MPP, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                decode_steps=4)
    return cfg, LLMEngine(cfg, BatchingSpec(**{**spec, **kw}), params=PARAMS)


def _greedy(cfg, prompt, n):
    """The full recompute's greedy tokens: every step a whole forward pass
    over what stands so far, padded to one length (causal: what lies behind
    a position cannot move it), so it compiles once."""
    toks, out = list(prompt), []
    for _ in range(n):
        padded = np.zeros((PAGE * MPP,), np.int32)
        padded[:len(toks)] = toks
        t = int(jnp.argmax(_full(cfg, padded)[len(toks) - 1]))
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


@pytest.mark.parametrize("window", [5, 24])
def test_engine_tokens_are_the_full_recomputes(window):
    cfg, engine = _engine(window)
    prompts = [_tokens(31, 75), _tokens(32, 19), _tokens(33, 50)]
    reqs = _serve(engine, prompts, 30)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _greedy(cfg, p, 30)
    engine._allocator.assert_quiescent()
    # every sequence's first pages came from the ring's ids
    assert engine._ring == cfg.window_ring_pages or engine._ring > 0
    assert engine._allocator.available(ring=True) == engine._window_pages


def test_a_sequence_preempted_in_the_middle_of_its_ring_resumes():
    """A pool too small for three growing contexts: the youngest gives its
    pages back mid-ring, prefills again from its prompt and what it had
    generated, and every request still reads the full recompute's tokens."""
    cfg, engine = _engine(24, max_pages=26)
    prompts = [_tokens(41, 60), _tokens(42, 62), _tokens(43, 58)]
    reqs = _serve(engine, prompts, 40)
    assert engine.counters()["preemptions"] >= 1
    for p, r in zip(prompts, reqs):
        assert len(r.output_tokens) == 40
        assert r.output_tokens == _greedy(cfg, p[:len(p)], 40)
    engine._allocator.assert_quiescent()


def test_counters_exist_from_construction_and_say_the_pool_by_kind():
    cfg, engine = _engine(24)
    before = engine.counters()
    assert before["kv_window_pages_a_sequence"] == 6
    assert before["kv_window_pool_bytes"] == 2 * 4 * 18 * PAGE * 2 * 16 * 4
    assert before["kv_global_pool_bytes"] == 2 * 1 * 48 * PAGE * 2 * 16 * 4
    assert before["kv_pool_bytes"] == before["kv_window_pool_bytes"] \
        + before["kv_global_pool_bytes"]
    assert before["kv_bytes_per_token"] == pool_bytes_per_token(cfg)
    assert (before["expert_rows_routed"], before["expert_rows_held"]) \
        == (0, 0)
    _serve(engine, [_tokens(51, 40)], 12)
    after = engine.counters()
    assert set(after) == set(before)
    assert after["expert_rows_routed"] > 0
    share = after["expert_rows_held"] / after["expert_rows_routed"]
    assert 0.1 < share < 0.45           # 4 of 16 held: a quarter expected
    # a stack without window layers or a share reads 0 everywhere
    plain = LLMEngine(preset("tiny"), BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=PAGE,
        chunked_prefill_tokens=CHUNK)).counters()
    assert [plain[k] for k in (
        "kv_window_pool_bytes", "kv_window_pages_a_sequence",
        "expert_rows_routed", "expert_rows_held")] == [0, 0, 0, 0]
    assert plain["kv_global_pool_bytes"] == plain["kv_pool_bytes"]


def test_a_decode_round_says_its_window_context(monkeypatch):
    from test_serve_chunk_rows import record_spans

    cfg, engine = _engine(24)
    spans = record_spans(monkeypatch)
    _serve(engine, [_tokens(61, 40), _tokens(62, 9)], 8)
    rounds = [a for n, a in spans if n == "engine.decode_dispatch"]
    assert rounds and all("window_context" in r for r in rounds)
    for r in rounds:
        assert 0 < r["window_context"] <= r["context"]
        assert r["window_context"] <= r["live"] * r["k_steps"] * 24


@pytest.mark.parametrize("what,kw", [
    ("prefix reuse over window layers", {"enable_prefix_caching": True}),
    ("int8 KV", {"kv_cache_dtype": "int8"}),
    ("quantize=int8", {"quantize": "int8"}),
    ("role=", {"role": "prefill"}),
])
def test_an_option_that_does_not_take_this_model_is_refused_by_name(what, kw):
    with pytest.raises(ValueError, match="window layers") as err:
        _engine(24, **kw)
    assert what in str(err.value)
    assert "hold 4 of 16 experts" in str(err.value)


def test_a_mesh_is_refused_by_name_for_a_held_share():
    """A stack that is all alike but for its expert layers' share: a mesh
    has no form for one chip's part of an expert-parallel group."""
    from jax.sharding import Mesh

    cfg = dataclasses.replace(
        preset("tiny-moe"), moe_impl="sorted", experts_held=2)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("model",))
    with pytest.raises(ValueError, match="hold 2 of 4 experts") as err:
        LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=PAGE,
            chunked_prefill_tokens=CHUNK), mesh=mesh)
    assert "a mesh" in str(err.value)
