"""A chunk program that carries the decode step (ISSUE 49), on the CPU with
the kernels interpreted, in float32. The program: ``paged.paged_mixed_step``
(a chunk's rows and the slots' rows, two groups through ``_pool_block``,
ONE feed-forward over their tokens) against the chunk program and then the
decode step on the same inputs, over three tiny stacks: per-head planes with
a plain MLP, per-head planes with capacity-dispatch experts at a factor that
DROPS chunk rows, a latent pool with a leading dense layer, sorted experts
and a shared expert. The engine: which stacks send it, that a run's greedy
tokens are the full recompute's, the counters, the spans, the programs'
names, and that nothing compiles after the engine is built.

A program's spare rows carry the NEXT chunks of the prompts in it (ISSUE
56): the program, two consecutive chunks of one prompt as two rows of one
program against one after the other; the engine, a prompt alone two chunks
a program, what keeps a row dead, what a pass between two programs finds,
and which stacks never send a chunk ahead.

The kind "parallel" rides too (ISSUE 58): attention beside an SSD mixer in
every block, the mixer's state a sequence ONE entry of planes of its own,
written by ``ssd_chunk`` for the chunk's row and by ``ssd_step`` for the
slots' in one program. It is a fourth stack of ``KINDS`` wherever the case
does not need rows ahead (``AHEAD``: a state is handed from a chunk's END to
the next chunk's start, so a parallel stack never sends one), and has the
cases of an engine ONE row wide, the assistant cell's, to itself.

The kind "linear" rides too (ISSUE 60): a Solar-like stack, KDA layers whose
recurrent matrices and conv tails are ONE entry a sequence (``kda_chunk`` and
a scatter of the end state for the chunk's rows, ``kda_step`` in place for
the slots') beside a gated GQA layer in four, sorted experts of which a share
is held. A fifth stack of ``KINDS``. An engine several rows wide that sends no
row ahead (this one, and the parallel stack at two rows) lets the step ride
only a program whose rows are all filled (``ChunkPlan.rides``).

The kind "ssd" sends rows ahead (ISSUE 63): a Nemotron-like stack, SSD mixers
alone in their blocks beside one attention block, a block of one sublayer,
sorted experts behind a latent projection of which a share is held. Its mixer
hands the END state and the conv tail of a row to the row that follows it
inside the program (``paged._ssd``), so it is a sixth stack of ``KINDS`` and
the fourth of ``AHEAD``: a row behind the row in front with the slots riding,
a prompt alone two chunks a program, the odd last chunk beside a dead row."""

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
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.serve.chunk_programs import pack_rows
from kubeflow_tpu.serve.engine import (
    LLMEngine, SamplingParams, serving_configs,
)
from kubeflow_tpu.serve.paged import (
    mixed_step_rows, paged_chunk_prefill, paged_decode_multi,
    paged_mixed_step, pool_shapes,
)
from test_serve_chunk_plan import WIDE, plan_of

PAGE, CHUNK, MPP = 16, 32, 8
SLOTS = 4
KINDS = ("dense", "dispatch", "latent", "parallel", "linear", "ssd")
# the stacks whose every layer hands a chunk's end to the row behind it (kind
# "attention": by the pool; "ssd": by its mixer): a program's spare rows may
# carry the NEXT chunks of the prompts in it (``paged.chunk_rows_follow``)
AHEAD = (*KINDS[:3], "ssd")
# two rows wide, the step carried, and no row sent ahead
FILLED_ONLY = ("parallel", "linear")


def _config(kind: str):
    over = dict(dtype="float32", param_dtype="float32", max_seq_len=1024)
    if kind == "dense":
        return preset("tiny", head_dim=128, **over)
    if kind == "dispatch":
        # Mixtral-like; the published factor drops rows of crowded chunks
        return preset("tiny-moe", head_dim=128, capacity_factor=1.25, **over)
    if kind == "parallel":
        # Falcon-H1-like: attention beside an SSD mixer in every block, one
        # KV head of 128 (what the kernels take, interpreted here), blocks
        # of 8 positions: the scene's chunks start inside a page AND end
        # inside a block
        return preset("tiny-falconh1", n_heads=2, n_kv_heads=1, head_dim=128,
                      **over)
    if kind == "linear":
        # Solar-like (``rehearsal-tiny-solar``'s stack at the widths the
        # kernels take): one gated GQA layer in four beside KDA layers, 4 of
        # 16 sorted experts held and a shared expert
        return preset("tiny-solar", **WIDE["tiny-solar"], **over)
    if kind == "ssd":
        # Nemotron-like: one KV head of 128 for the attention kernels, SSD
        # heads of 64, TWO to a lane tile of the state plane, blocks of 8
        return preset("tiny-nemotron-h", **WIDE["tiny-nemotron-h"], **over)
    return preset("tiny-glm", **over)


@functools.lru_cache(maxsize=None)
def _model(kind: str):
    cfg = _config(kind)
    return cfg, init_decoder_params(jax.random.PRNGKey(3), cfg)


def _tokens(seed: int, n: int) -> np.ndarray:
    """Tokens from FEW ids, so a chunk crowds the same experts and a
    capacity of 1.25 x the even share overflows."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, 256, 3)
    return ids[rng.choice(3, n, p=[0.8, 0.1, 0.1])].astype(np.int32)


def _batching(slots=SLOTS):
    return BatchingSpec(max_batch_size=slots, max_seq_len=MPP * PAGE,
                        page_size=PAGE, chunked_prefill_tokens=CHUNK,
                        paged_attn_impl="pallas")


@functools.lru_cache(maxsize=None)
def _programs(kind: str, slots: int = SLOTS):
    """(the chunk program over rows, one decode step with its sampler, the
    two in one), each as the engine builds it for ``kind``'s stack."""
    cfg, _ = _model(kind)
    pre, dec = serving_configs(cfg, _batching(slots))
    chunk = jax.jit(lambda p, c, t, tr, st, vl, ends: paged_chunk_prefill(
        p, c, t, tr, st, vl, pre, context_pages=MPP,
        paged_attn_impl="pallas", logits_at="last", wanted=ends))
    step = jax.jit(lambda p, c, s, key: paged_decode_multi(
        p, c, s["tokens"], s["lengths"], s["live"], s["temps"], s["top_k"],
        s["top_p"], s["stops"], s["budgets"], key, dec, 1,
        sample_mode="greedy", attn_impl="pallas"))
    mixed = jax.jit(lambda p, c, t, tr, st, vl, ends, ride, s, key:
                    paged_mixed_step(
                        p, c, t, tr, st, vl, ends, ride, s["tokens"],
                        s["lengths"], s["live"], s["temps"], s["top_k"],
                        s["top_p"], s["stops"], s["budgets"], key, pre,
                        sample_mode="greedy", attn_impl="pallas"))
    return chunk, step, mixed


def _rows(rows):
    """The chunk program's arrays for ``rows``: (tokens, table row, start,
    valid, ends its prompt) each, None a dead row (one with no token)."""
    dead = ((), np.full((MPP,), -1, np.int32), 0, False)
    return tuple(map(jnp.asarray, pack_rows(
        [dead if row is None else (
            row[0][row[2]:row[2] + row[3]], row[1], row[2], row[4])
         for row in rows], len(rows), CHUNK, MPP)))


@functools.lru_cache(maxsize=None)
def _scene(kind: str, slots: int = SLOTS):
    """A pool in which prompt a holds 24 tokens (its next chunk starts
    MID-PAGE, whole, and does not end it) and prompt b 64 (its next chunk 19
    tokens long, its last), and every slot a context of its own length on
    pages of its own; the slots' table; each slot's next token."""
    cfg, params = _model(kind)
    chunk, _, _ = _programs(kind, slots)
    pool = {n: jnp.zeros(shape, dt) for n, (shape, dt) in
            pool_shapes(cfg, 10 + 2 * slots, PAGE).items()}
    a, b = _tokens(1, 24 + CHUNK), _tokens(2, 64 + 19)
    row_a = np.asarray([0, 1, 2, 3, -1, -1, -1, -1], np.int32)
    row_b = np.asarray([4, 5, 6, 7, 8, 9, -1, -1], np.int32)
    table = np.full((slots, MPP), -1, np.int32)
    held = []
    for s in range(slots):
        table[s, :2] = 10 + 2 * s, 11 + 2 * s
        held.append((_tokens(10 + s, 32), table[s], 0, 5 + 3 * (s % 7),
                     True))
    for rows in ([(a, row_a, 0, 24, False), (b, row_b, 0, CHUNK, False)],
                 [None, (b, row_b, CHUNK, CHUNK, False)],
                 *[held[i:i + 2] for i in range(0, slots, 2)]):
        _, pool = chunk(params, pool, *_rows(rows))
    state = {
        "tokens": np.asarray([int(h[0][h[3]]) for h in held], np.int32),
        "lengths": np.asarray([h[3] for h in held], np.int32),
        "live": np.ones((slots,), np.bool_),
        "temps": np.zeros((slots,), np.float32),
        "top_k": np.zeros((slots,), np.int32),
        "top_p": np.ones((slots,), np.float32),
        "stops": np.full((slots,), -1, np.int32),
        "budgets": np.full((slots,), 9, np.int32)}
    rows = [(a, row_a, 24, CHUNK, False), (b, row_b, 64, 19, True)]
    return pool, table, state, rows


def _close(got, want, what):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=2e-5,
                               atol=2e-5, err_msg=what)


def _both_ways(kind, state, rows, ride=True, params=None, slots=SLOTS):
    """(what the chunk program and then the decode step leave, what the one
    program leaves): each (chunk logits, the round's tokens, cache, tokens,
    lengths, live, budgets)."""
    chunk, step, mixed = _programs(kind, slots)
    pool, table, _, _ = _scene(kind, slots)
    params = _model(kind)[1] if params is None else params
    state = {n: jnp.asarray(v) for n, v in state.items()}
    key = jax.random.PRNGKey(11)
    logits, after = chunk(params, pool, *_rows(rows))
    want = (logits,) + tuple(step(
        params, {**after, "table": jnp.asarray(table)}, state, key))
    got = mixed(params, {**pool, "table": jnp.asarray(table)}, *_rows(rows),
                jnp.asarray(ride), state, key)
    return want, got


SLOT_CASES = {
    "all-live": [True, True, True, True],
    "some-dead": [True, False, False, True],
    "all-dead": [False, False, False, False],
    "one-at-its-budget": [True, True, True, True],
}


@pytest.mark.parametrize("case", sorted(SLOT_CASES))
@pytest.mark.parametrize("kind", KINDS)
def test_the_one_program_is_the_chunk_program_then_the_decode_step(kind,
                                                                   case):
    """Row a's chunk does not end its prompt, row b's does: b's logits, the
    slots' tokens, every plane of the pool and the slots' carried state."""
    _, _, state, rows = _scene(kind)
    state = {**state, "live": np.asarray(SLOT_CASES[case])}
    if case == "one-at-its-budget":
        state["budgets"] = np.asarray([9, 1, 9, 9], np.int32)
    want, got = _both_ways(kind, state, rows)
    _close(got[0][1], want[0][1], "the logits of the row that ends")
    np.testing.assert_array_equal(got[1], want[1])      # the round's tokens
    live = np.asarray(SLOT_CASES[case])
    assert (np.asarray(got[1])[:, 0] >= 0).tolist() == live.tolist()
    for name in want[2]:
        _close(got[2][name], want[2][name], f"plane {name}")
    for i, name in enumerate(("tokens", "lengths", "live", "budgets"), 3):
        np.testing.assert_array_equal(got[i], want[i], err_msg=name)
    if case == "one-at-its-budget":
        assert np.asarray(got[5]).tolist() == [True, False, True, True]


@pytest.mark.parametrize("kind", KINDS)
def test_where_the_slots_do_not_ride_it_is_the_chunk_program(kind):
    """``ride`` false: live slots or not, nothing of theirs is written,
    emitted or advanced; a dead chunk row beside a live one."""
    pool, table, state, rows = _scene(kind)
    chunk, _, mixed = _programs(kind)
    _, params = _model(kind)
    rows = [None, rows[1]]
    logits, after = chunk(params, pool, *_rows(rows))
    dev = {n: jnp.asarray(v) for n, v in state.items()}
    got = mixed(params, {**pool, "table": jnp.asarray(table)}, *_rows(rows),
                jnp.asarray(False), dev, jax.random.PRNGKey(11))
    _close(got[0][1], logits[1], "the logits of the row that ends")
    assert np.all(np.asarray(got[1]) == -1)
    for name in after:
        _close(got[2][name], after[name], f"plane {name}")
    for i, name in enumerate(("tokens", "lengths", "live", "budgets"), 3):
        np.testing.assert_array_equal(got[i], state[name], err_msg=name)


@pytest.mark.parametrize("ride", [False, True], ids=["alone", "riding"])
@pytest.mark.parametrize("kind", AHEAD)
def test_a_row_may_be_the_chunk_behind_the_row_in_front(kind, ride):
    """Two consecutive chunks of ONE prompt as the two rows of one program
    (same table row, the second start a chunk on): every layer writes both
    rows' keys before either attends, so the second finds the first's there.
    The logits of the row that ends, every plane and the slots' step are
    those of the two chunks sent one after the other (a capacity-dispatch
    layer drops by the row, so what a chunk keeps does not depend on the
    row beside it)."""
    pool, table, state, rows = _scene(kind)
    chunk, step, mixed = _programs(kind)
    _, params = _model(kind)
    a, row_a = rows[0][:2]
    a = np.concatenate([a, _tokens(9, 8)])          # 24 held, 32 + 8 to go
    first, behind = (a, row_a, 24, CHUNK, False), (a, row_a, 56, 8, True)
    _, after = chunk(params, pool, *_rows([first, None]))
    logits, after = chunk(params, after, *_rows([None, behind]))
    dev = {n: jnp.asarray(v) for n, v in state.items()}
    key = jax.random.PRNGKey(11)
    want = (logits,) + tuple(step(
        params, {**after, "table": jnp.asarray(table)}, dev, key)) \
        if ride else (logits, None, after)
    got = mixed(params, {**pool, "table": jnp.asarray(table)},
                *_rows([first, behind]), jnp.asarray(ride), dev, key)
    _close(got[0][1], want[0][1], "the logits of the row behind")
    for name in want[2]:
        _close(got[2][name], want[2][name], f"plane {name}")
    if ride:
        np.testing.assert_array_equal(got[1], want[1])
    together, planes = chunk(params, pool, *_rows([first, behind]))
    _close(together[1], logits[1], "the chunk program's own two rows")
    for name in after:
        _close(planes[name], after[name], f"plane {name}")


def test_sixteen_decode_tokens_on_one_expert_lose_none():
    """A router that sends EVERY token to experts 0 and 1: a chunk row
    overflows its capacity there (the two programs drop the same pairs),
    and the sixteen slots' tokens, a group whose capacity is its size, are
    the drop-free decode step's."""
    cfg, params = _model("dispatch")
    mlp = params["layers"]["mlp"]
    flat = {**params, "layers": {**params["layers"], "mlp": {
        **mlp, "router": jnp.zeros_like(mlp["router"])}}}
    _, _, state, rows = _scene("dispatch", 16)
    assert L.moe_capacity(cfg, CHUNK) < CHUNK       # a row's 32 cannot fit
    want, got = _both_ways("dispatch", state, rows, params=flat, slots=16)
    _close(got[0][1], want[0][1], "the logits of the row that ends")
    np.testing.assert_array_equal(got[1], want[1])
    for name in want[2]:
        _close(got[2][name], want[2][name], f"plane {name}")
    # and dropping DID change the chunk rows: ample capacity reads otherwise
    ample = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, CHUNK, cfg.hidden))
    layer = jax.tree.map(lambda a: a[0], flat["layers"]["mlp"])
    tight, _ = L.moe_block(layer, x, cfg, capacity_per_row=True)
    roomy, _ = L.moe_block(layer, x, ample, capacity_per_row=True)
    assert float(jnp.abs(tight - roomy).max()) > 1e-3


def test_the_tail_of_a_dispatch_is_a_group_that_cannot_drop():
    """``layers._moe_dispatch(tail=)``: the rows of ``x`` are what they are
    without a tail; the tail's tokens are the dense oracle's, also where
    every one of them and every token of ``x`` wants the same expert."""
    cfg, params = _model("dispatch")
    layer = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    layer = {**layer, "router": jnp.zeros_like(layer["router"])}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, CHUNK, cfg.hidden))
    tail = jax.random.normal(jax.random.PRNGKey(6), (16, cfg.hidden))
    alone, _ = L.moe_block(layer, x, cfg, capacity_per_row=True)
    (out, beside), _ = L.moe_block(layer, x, cfg, capacity_per_row=True,
                                   tail=tail)
    _close(out, alone, "the rows beside a tail")
    oracle, _ = L.moe_block(layer, tail[:, None],
                            dataclasses.replace(cfg, moe_impl="dense"))
    _close(beside, oracle[:, 0], "the tail")
    with pytest.raises(NotImplementedError, match="tail of tokens"):
        L.moe_block(layer, x, dataclasses.replace(cfg, moe_impl="dense"),
                    tail=tail)


@pytest.mark.parametrize("chunk_tokens,slots,k,rows", [
    (1024, 16, 4, 32),      # GLM-4.7-Flash's cell: 4224 = 33 tiles
    (512, 16, 4, 32),
    (64, 4, 2, 64),         # the tiny stack here
    (1024, 16, 6, 64),
    (1000, 16, 4, 24),
])
def test_sorted_rows_are_whole_tiles(chunk_tokens, slots, k, rows):
    cfg = preset("tiny-glm", experts_per_token=k)
    got = mixed_step_rows(cfg, chunk_tokens, slots)
    assert got == rows >= slots
    assert (chunk_tokens + got) * k % L.GROUPED_TILE_ROWS == 0
    assert mixed_step_rows(preset("tiny-moe"), chunk_tokens, slots) == slots
    assert mixed_step_rows(preset("tiny"), chunk_tokens, slots) == slots


def test_other_stacks_and_pools_are_refused():
    cfg, params = _model("dense")
    pool = {n: jnp.zeros(shape, dt) for n, (shape, dt) in
            pool_shapes(cfg, 8, PAGE).items()}
    state = _scene("dense")[2]
    args = (*_rows([None]), jnp.asarray(True),
            *(jnp.asarray(state[n]) for n in (
                "tokens", "lengths", "live", "temps", "top_k", "top_p",
                "stops", "budgets")), jax.random.PRNGKey(0))
    table = jnp.full((SLOTS, MPP), -1, jnp.int32)
    with pytest.raises(NotImplementedError, match="in one program"):
        paged_mixed_step(params, {**pool, "table": table}, *args, cfg,
                         attn_impl="gather")
    lfm2 = preset("tiny-lfm2", dtype="float32", param_dtype="float32")
    with pytest.raises(NotImplementedError, match="in one program"):
        paged_mixed_step(
            init_decoder_params(jax.random.PRNGKey(1), lfm2),
            {**{n: jnp.zeros(shape, dt) for n, (shape, dt) in
                pool_shapes(lfm2, 8, PAGE).items()}, "table": table},
            *args, lfm2, attn_impl="pallas")


# -- the engine ------------------------------------------------------------------

def _engine(kind, impl="pallas", **kw):
    cfg, params = _model(kind)
    spec = dict(max_batch_size=SLOTS, max_seq_len=256, page_size=PAGE,
                chunked_prefill_tokens=CHUNK, enable_prefix_caching=False,
                max_concurrent_prefills=2, paged_attn_impl=impl,
                decode_steps=1, prefill_interleave_steps=1)
    return LLMEngine(cfg, BatchingSpec(**{**spec, **kw}), params=params)


PROMPTS = [_tokens(4, 70), _tokens(5, 40), _tokens(6, 100), _tokens(7, 33),
           _tokens(8, 90)]
ARRIVALS = {0: (0, 1), 3: (2, 3), 8: (4,)}      # scheduler iteration: prompts


def _serve(eng, n=12, sampling=None):
    """PROMPTS arriving while earlier ones prefill and decode: a run that
    meets iterations with a chunk alone, a round alone and both."""
    sp = sampling or SamplingParams(max_new_tokens=n, temperature=0.0)
    reqs = {}
    for i in range(600):
        for j in ARRIVALS.get(i, ()):
            reqs[j] = eng.submit(list(map(int, PROMPTS[j])), sp)
        eng.step()
        if len(reqs) == len(PROMPTS) and all(
                r.done.is_set() for r in reqs.values()):
            return [list(reqs[j].output_tokens) for j in sorted(reqs)]
    raise AssertionError("requests did not finish")


def _recompute(kind: str, j: int, n: int = 12) -> list:
    """``n`` greedy tokens behind prompt ``j`` by full recompute."""
    return _recompute_behind(kind, tuple(map(int, PROMPTS[j])), n)


@functools.lru_cache(maxsize=None)
def _recompute_behind(kind: str, prompt: tuple, n: int,
                      pad: int = 256) -> list:
    """``n`` greedy tokens behind ``prompt`` by full recompute: a whole
    forward pass over what stands so far, padded to one length, ``pad``
    (causal: what lies behind a position cannot move it). A
    capacity-dispatch stack drops by the CHUNK a token stands in, which no
    whole forward pass does: its prompt is served alone by the two programs
    (the gathered arm, one prefill at a time)."""
    if kind == "dispatch":
        eng = _engine(kind, "gather", max_concurrent_prefills=1)
        req = eng.submit(list(prompt), SamplingParams(
            max_new_tokens=n, temperature=0.0))
        while not req.done.is_set():
            eng.step()
        return list(req.output_tokens)
    forward = _padded_forward(kind)
    toks = list(prompt)
    for _ in range(n):
        block = np.zeros((pad,), np.int32)
        block[:len(toks)] = toks
        toks.append(int(jnp.argmax(forward(jnp.asarray(block))[
            len(toks) - 1])))
    return toks[len(prompt):]


@functools.lru_cache(maxsize=None)
def _padded_forward(kind: str):
    cfg, params = _model(kind)
    return jax.jit(lambda t: decoder_forward(params, t[None], cfg)[0][0])


@pytest.mark.parametrize("kind,kw", [
    *((kind, {}) for kind in KINDS),
    # the option governs decode-only rounds; a step that rides goes the
    # prefill path's drop-free way (``serving_configs``): the same tokens
    ("dispatch", {"moe_decode_impl": "zero_drop"}),
], ids=[*KINDS, "dispatch-zero_drop"])
def test_engine_tokens_are_the_full_recomputes(kind, kw):
    eng = _engine(kind, **kw)
    assert eng._plan.carries_step
    c = eng.counters()
    assert (c["mixed_programs_dispatched"], c["mixed_decode_rows_sum"]) \
        == (0, 0)
    assert _serve(eng) == [_recompute(kind, j) for j in range(len(PROMPTS))]
    c = eng.counters()
    # fourteen chunks in eight programs; the first two find no slot live.
    # Prompts 2 and 4 each go alone for a while, a chunk ahead a program;
    # prompts 0 and 4 end in an odd chunk beside a dead row. A parallel or
    # a linear stack sends no chunk ahead, and the step rides only a program
    # whose rows are both filled (``ChunkPlan.rides``): ten programs, four
    # of them pairs (two with no slot live yet), and each of the six lone
    # chunks the one-row program with the iteration's step behind it
    programs, riding, ahead, dead = (8, 6, 2, 2) if kind in AHEAD \
        else (10, 2, 0, 0)
    assert eng._plan.ahead == (kind in AHEAD)
    assert (c["prefill_programs_dispatched"],
            c["mixed_programs_dispatched"]) == (programs, riding)
    assert (c["prefill_chunks_dispatched"], c["prefill_rows_ahead"],
            c["prefill_rows_dead"]) == (14, ahead, dead)
    assert c["mixed_decode_rows_sum"] >= c["mixed_programs_dispatched"]
    # a program that carried a round is one round, one step, one program
    assert c["decode_rounds"] == c["decode_steps_dispatched"]
    assert c["decode_tokens_emitted"] == 11 * len(PROMPTS)
    eng._allocator.assert_quiescent()


@pytest.mark.parametrize("kind", KINDS)
def test_a_longer_round_follows_as_the_decode_program(kind):
    """At the defaults' caps (32 steps, 8 beside a prefill) the chunk
    program carries ONE step and the round of the cap follows it."""
    eng = _engine(kind, decode_steps=32, prefill_interleave_steps=8)
    assert _serve(eng) == [_recompute(kind, j) for j in range(len(PROMPTS))]
    c = eng.counters()
    assert c["mixed_programs_dispatched"] > 0
    assert c["decode_steps_dispatched"] > c["decode_rounds"]


def test_sampled_streams_ride_too():
    """Sampling traffic compiles the program's other modes at first use,
    like the decode program's; every request gets its tokens."""
    eng = _engine("dense")
    out = _serve(eng, sampling=SamplingParams(
        max_new_tokens=6, temperature=0.8, top_k=5))
    assert [len(o) for o in out] == [6] * len(PROMPTS)
    assert eng.counters()["mixed_programs_dispatched"] > 0


OTHER_STACKS = {
    # kind of layer -> (preset, engine options that its tests use)
    "conv": ("tiny-lfm2", dict(max_seq_len=128, page_size=16,
                               chunked_prefill_tokens=32)),
    "window": ("tiny-exaone", dict(max_seq_len=128, page_size=8,
                                   chunked_prefill_tokens=16,
                                   enable_prefix_caching=False)),
    "linear": ("tiny-solar", dict(max_seq_len=128, page_size=8,
                                  chunked_prefill_tokens=16,
                                  enable_prefix_caching=False)),
    "ssm": ("tiny-phi4flash", dict(max_seq_len=128, page_size=8,
                                   chunked_prefill_tokens=16,
                                   enable_prefix_caching=False)),
    # the KINDS "parallel" and "linear" ride since PR 58 and PR 60 (``KINDS``
    # above: one KV head of 128); the presets AS THEY STAND have heads of 16,
    # which ``paged_chunk_attention`` does not take, so their chunks stay on
    # the gathered form (``paged._chunk_in_place``) and THAT keeps their two
    # programs
    "parallel": ("tiny-falconh1", dict(max_seq_len=128, page_size=8,
                                       chunked_prefill_tokens=16,
                                       enable_prefix_caching=False)),
}


@pytest.mark.parametrize("layer", sorted(OTHER_STACKS))
def test_a_stack_with_another_kind_of_layer_sends_two_programs(layer):
    name, opts = OTHER_STACKS[layer]
    cfg = preset(name, dtype="float32", param_dtype="float32")
    assert layer in cfg.kinds
    eng = LLMEngine(cfg, BatchingSpec(
        max_batch_size=3, max_concurrent_prefills=2, decode_steps=1,
        prefill_interleave_steps=1, paged_attn_impl="pallas", **opts))
    # nor does a spare row ever carry a chunk ahead: the layer hands a
    # state, a ring or a tail from a chunk's END to the next chunk's start
    assert not eng._plan.carries_step and not eng._plan.ahead
    sp = SamplingParams(max_new_tokens=5, temperature=0.0)
    rng = np.random.default_rng(3)
    reqs = [eng.submit([int(t) for t in rng.integers(3, 200, 40)], sp)]
    for i in range(300):
        eng.step()
        if i == 2:
            reqs.append(eng.submit(
                [int(t) for t in rng.integers(3, 200, 50)], sp))
        if len(reqs) == 2 and all(r.done.is_set() for r in reqs):
            break
    c = eng.counters()
    assert c["prefill_programs_dispatched"] > 0 and c["decode_rounds"] > 0
    assert (c["mixed_programs_dispatched"], c["mixed_decode_rows_sum"]) \
        == (0, 0)
    # the first prompt went alone for its first chunks
    assert c["prefill_rows_ahead"] == 0


@pytest.mark.parametrize("why,kw", [
    ("the gathered arm", dict(impl="gather")),
    ("an int8 pool", dict(kv_cache_dtype="int8")),
    ("adapter buffers", dict(lora={"max_adapters": 2})),
    ("a speculative round", dict(speculative={"mode": "ngram"})),
])
def test_what_else_keeps_two_programs(why, kw):
    from kubeflow_tpu.core.serving import LoRASpec, SpeculativeSpec

    if "lora" in kw:
        kw = {"lora": LoRASpec(**kw["lora"])}
    if "speculative" in kw:
        kw = {"speculative": SpeculativeSpec(**kw["speculative"])}
    eng = _engine("dense", **kw)
    assert not eng._plan.carries_step and not eng._plan.ahead, why
    got = _serve(eng, n=4)
    assert [len(o) for o in got] == [4] * len(PROMPTS)
    c = eng.counters()
    assert (c["mixed_programs_dispatched"], c["prefill_rows_ahead"]) == (0, 0)


@pytest.mark.parametrize("kind", KINDS)
def test_no_compile_after_construction(kind):
    """The program is compiled and run when the engine is built, at its
    one width, as are the decode programs: a run that meets chunk-only,
    decode-only and mixed iterations (one prompt's chunk beside a dead row
    among them) compiles nothing once every OTHER program it needs has run
    (a first run of the same traffic)."""
    eng = _engine(kind)
    assert eng._plan.rows == 2
    # the engine's own warm-up reached it, no slot riding
    sizes = eng._programs.mixed._cache_size()
    assert sizes == 1
    _serve(eng)
    assert eng._programs.mixed._cache_size() == sizes
    compiles = CompileCounter()
    compiles.start()
    _serve(eng)
    assert compiles.stop() == 0, compiles.names
    c = eng.counters()
    assert 0 < c["mixed_programs_dispatched"] \
        < c["prefill_programs_dispatched"]
    assert c["decode_rounds"] > c["mixed_programs_dispatched"]


def test_a_dense_model_at_its_ridge_builds_it_one_row_wide():
    """The plan alone (no engine: ``_one_row_engine`` below is this one
    built, its program warmed once): the step carried, one row, and no
    program over rows beside it."""
    plan = plan_of(_config("dense"), BatchingSpec(
        max_batch_size=2, max_seq_len=1024, page_size=PAGE,
        chunked_prefill_tokens=256, paged_attn_impl="pallas",
        decode_steps=1, prefill_interleave_steps=1,
        max_concurrent_prefills=2), "pallas")
    assert plan.carries_step and plan.rows == 1
    assert plan.programs() == {"mixed", "lone"}


def test_both_dispatch_spans_carry_their_attributes(monkeypatch):
    from test_serve_chunk_rows import record_spans

    eng = _engine("dispatch")
    seen = record_spans(monkeypatch)
    _serve(eng)
    names = [name for name, _ in seen]
    mixed = [i for i, name in enumerate(names[:-1])
             if name == "engine.prefill_dispatch"
             and names[i + 1:i + 2] == ["engine.decode_dispatch"]]
    c = eng.counters()
    assert len(mixed) == c["mixed_programs_dispatched"] > 0
    rows = 0
    for i in mixed:
        # (``context``: the pairs its chunks' queries can see, every
        # engine's since PR 57)
        # (``live_rows`` / ``state_bytes``: the step a program carries and
        # what it moves of sequence entries, PR 61: none here)
        assert set(seen[i][1]) == {"slot", "pos", "chunks", "context",
                                   "live_rows", "state_bytes"}
        step = seen[i + 1][1]
        assert set(step) == {"round", "k_steps", "live", "context",
                             "live_rows", "state_bytes"}
        assert seen[i][1]["live_rows"] == step["live_rows"] == step["live"]
        assert seen[i][1]["state_bytes"] == step["state_bytes"] == 0
        assert step["k_steps"] == 1 and step["live"] >= 1
        assert step["context"] >= step["live"]
        rows += step["live"]
    assert rows == c["mixed_decode_rows_sum"]
    # every round has its span, carried or not, and its fetch
    rounds = [a["round"] for n, a in seen if n == "engine.decode_dispatch"]
    assert rounds == list(range(c["decode_rounds"]))


def test_the_programs_keep_their_names(monkeypatch):
    """On the chip ``program_kernels`` names what was dispatched (wrapped
    here as ``__init__`` wraps them there): the one-row chunk program and
    the decode program under the names the benchmark asks for, the new
    program under a third, and no program over rows beside it."""
    monkeypatch.setattr(
        "kubeflow_tpu.runtime.device_report.lowered_kernel_calls",
        lambda jitted, *args: {})
    eng = _engine("dense")
    assert eng._plan.programs() == {"lone", "mixed"}
    assert not hasattr(eng._programs, "rows")
    for name, key in (("lone", "paged_chunk_prefill"),
                      ("mixed", "paged_mixed")):
        setattr(eng._programs, name, eng._introspected(
            key, getattr(eng._programs, name)))
    eng._paged_decode_n = eng._introspected("paged_decode",
                                            eng._paged_decode_n)
    eng._programs.warm(eng._warm)
    # the program of the one width, and the one-row program under every
    # bucket's name (this engine sends chunks ahead)
    assert set(eng.program_kernels) == {
        f"paged_mixed[2x{CHUNK},greedy]",
        *(f"paged_chunk_prefill[1x{CHUNK},{b}]" for b in (2, 4, 8, 16))}
    _serve(eng, n=3)
    names = set(eng.program_kernels)
    assert "paged_decode[1,greedy]" in names
    # one prompt's chunk with no slot to carry: the one-row program, named
    # by its bucket
    assert any(n.startswith(f"paged_chunk_prefill[1x{CHUNK},")
               for n in names)
    assert not any(n.startswith("paged_chunk_prefill[2x") for n in names)
    assert [n for n in names if n.startswith("paged_mixed[")] == [
        f"paged_mixed[2x{CHUNK},greedy]"]


# -- an engine ONE row wide: the assistant cell's (ISSUE 58) ----------------------

ONE_ROW = ("dense", "parallel")


@pytest.mark.parametrize("ends", [True, False], ids=["ends", "goes-on"])
@pytest.mark.parametrize("kind", ONE_ROW)
def test_one_row_wide_it_is_the_chunk_program_then_the_decode_step(kind,
                                                                   ends):
    """The program as an engine that sends one chunk a program builds it
    (``R`` = 1): a chunk that ends its prompt (19 tokens from position 64:
    inside a page, and inside a block of the SSD scan) and one that does
    not (32 from position 24, mid-page), a dead slot among the riding ones.
    The row's logits where they are read, the round's tokens, every plane
    (K and V pages; the SSD state and the conv tail of EVERY entry, the
    chunk's and the slots') and the slots' carried state."""
    _, _, state, rows = _scene(kind)
    state = {**state, "live": np.asarray([True, False, True, True])}
    want, got = _both_ways(kind, state, [rows[1] if ends else rows[0]])
    if ends:
        _close(got[0][0], want[0][0], "the logits of the row that ends")
    np.testing.assert_array_equal(got[1], want[1])
    assert (np.asarray(got[1])[:, 0] >= 0).tolist() == [True, False, True,
                                                        True]
    for name in want[2]:
        _close(got[2][name], want[2][name], f"plane {name}")
    for i, name in enumerate(("tokens", "lengths", "live", "budgets"), 3):
        np.testing.assert_array_equal(got[i], want[i], err_msg=name)


def _one_row_engine(kind, **kw):
    """Chunks of 256 tokens are over the ridge for a dense feed-forward: one
    chunk a program whatever ``max_concurrent_prefills`` says."""
    cfg, params = _model(kind)
    spec = dict(max_batch_size=3, max_seq_len=1024, page_size=PAGE,
                chunked_prefill_tokens=256, enable_prefix_caching=False,
                max_concurrent_prefills=2, paged_attn_impl="pallas",
                decode_steps=1, prefill_interleave_steps=1)
    return LLMEngine(cfg, BatchingSpec(**{**spec, **kw}), params=params)


LONG = [_tokens(60 + i, n) for i, n in enumerate((300, 520, 270, 700))]
LONG_ARRIVALS = {0: (0,), 2: (1, 2), 3: (3,)}


def _long_recompute(kind: str, j: int, n: int = 10) -> tuple:
    return tuple(_recompute_behind(kind, tuple(map(int, LONG[j])), n, 1024))


@pytest.mark.parametrize("steps", [1, 2], ids=["one-program-a-pass",
                                               "two-programs-a-pass"])
@pytest.mark.parametrize("kind", ONE_ROW)
def test_one_row_wide_every_chunk_beside_a_live_slot_takes_the_program(
        kind, steps):
    """Four prompts of two and three chunks on three slots, two prefills in
    flight: greedy tokens are the full recompute's. With a round of ONE
    step a pass sends one program and it carries the step; with a round of
    two it sends two, the step rides the first, and the second, which no
    step rides with, is the SAME program with every decode row dead
    (``ride`` false), not the ``[C, V]`` program: that one is called only
    for the first prompt, alone on an idle engine (``_otherwise_idle``), so
    the head runs at one position a chunk or at none from then on. The
    engine builds no program over rows beside it, and sends no chunk
    ahead."""
    eng = _one_row_engine(kind, decode_steps=steps,
                          prefill_interleave_steps=steps)
    assert eng._plan.carries_step and eng._plan.rows == 1
    assert not eng._plan.ahead
    assert eng._plan.programs() == {"mixed", "lone"}
    assert not hasattr(eng._programs, "rows")
    assert eng._programs.mixed._cache_size() == 1      # warmed when built
    live_at_call, program = [], eng._programs.lone
    eng._programs.lone = lambda *a: live_at_call.append(
        sum(s is not None for s in eng.slots)) or program(*a)
    sp = SamplingParams(max_new_tokens=10, temperature=0.0)
    reqs = {}
    for i in range(600):
        for j in LONG_ARRIVALS.get(i, ()):
            reqs[j] = eng.submit(list(map(int, LONG[j])), sp)
        eng.step()
        if len(reqs) == len(LONG) and all(
                r.done.is_set() for r in reqs.values()):
            break
    assert [tuple(reqs[j].output_tokens) for j in range(len(LONG))] == [
        _long_recompute(kind, j) for j in range(len(LONG))]
    c = eng.counters()
    assert live_at_call == [0, 0]                   # the first prompt's two
    assert c["prefill_programs_dispatched"] == c[
        "prefill_chunks_dispatched"] == 10
    riding = c["mixed_programs_dispatched"]
    assert riding == (8 if steps == 1 else 6)
    assert c["prefill_rows_ahead"] == c["prefill_rows_dead"] == 0
    # the two ``[C, V]`` chunks at every position, a riding program's one
    # head over its chunk's row too, a program nothing rides with only
    # where its chunk ends a prompt
    assert c["prefill_programs_with_end"] == len(LONG)
    silent = c["prefill_programs_dispatched"] - 2 - riding
    assert silent == (0 if steps == 1 else 2)
    assert 2 * 256 + riding <= c["prefill_head_positions"] \
        <= 2 * 256 + riding + silent
    assert eng._programs.mixed._cache_size() == 1
    eng._allocator.assert_quiescent()


@pytest.mark.parametrize("kind", ONE_ROW)
def test_one_row_wide_a_burst_on_an_idle_engine_takes_the_program_too(kind):
    """Three prompts at once on an idle engine: the pass finds no slot live
    and sends program after program with no wait between them, so every
    chunk takes the carrying program with ``ride`` false (``[1, V]`` back)
    and none the ``[C, V]`` program, whose results would pile up (C x V
    float32 each, allocated when sent). One prompt alone on an idle engine
    still takes that one, under its bucket's name."""
    eng = _one_row_engine(kind)
    calls, program = [], eng._programs.lone
    eng._programs.lone = lambda *a: calls.append(a[6]) or program(*a)
    sp = SamplingParams(max_new_tokens=10, temperature=0.0)
    reqs = [eng.submit(list(map(int, LONG[j])), sp) for j in (0, 1, 2)]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    assert not calls
    c = eng.counters()
    assert c["prefill_programs_dispatched"] == 7
    assert c["prefill_head_positions"] <= 7
    assert [tuple(r.output_tokens) for r in reqs] == [
        _long_recompute(kind, j) for j in (0, 1, 2)]
    alone = eng.submit(list(map(int, LONG[3])), sp)     # 700: three chunks
    while not alone.done.is_set():
        eng.step()
    assert calls == [16, 32, 64]                        # pages of 16
    assert tuple(alone.output_tokens) == _long_recompute(kind, 3)
    eng._allocator.assert_quiescent()


@pytest.mark.parametrize("kind", ONE_ROW)
def test_a_pass_that_ends_a_prompt_sends_the_next_round_before_it_waits(
        kind):
    """A prompt of one chunk beside a live stream: the pass's program
    carries the stream's step AND ends the prompt, so the pass waits for
    it (the first token). The next round goes out before that wait and is
    still in flight when the iteration ends (the pipeline is not drained:
    the device would stand idle through the emit and the next dispatch);
    the prompt's stream joins the round after it. A pass whose chunk ends
    nothing waits for nothing and sends nothing more. The tokens are the
    full recompute's either way."""
    eng = _one_row_engine(kind)
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    first = eng.submit(list(map(int, LONG[2][:40])), SamplingParams(
        max_new_tokens=60, temperature=0.0))
    while first.first_token_time is None:
        eng.step()
    eng.step()
    assert len(eng._rounds) == 1
    before = eng.counters()
    short = eng.submit(list(map(int, LONG[0])), sp)     # 300: two chunks
    eng.step()
    c = eng.counters()
    # its first chunk ends nothing: one program, it carried the one round
    assert c["mixed_programs_dispatched"] \
        == before["mixed_programs_dispatched"] + 1
    assert c["decode_rounds"] == before["decode_rounds"] + 1
    assert short.first_token_time is None and len(eng._rounds) == 1
    emitted = len(first.output_tokens)
    eng.step()
    # its second ends the prompt: the program carried a round, another went
    # out ahead of the wait and is the one in flight; both earlier rounds
    # were emitted in this iteration, the stream's first token too
    c2 = eng.counters()
    assert c2["mixed_programs_dispatched"] \
        == c["mixed_programs_dispatched"] + 1
    assert c2["decode_rounds"] == c["decode_rounds"] + 2
    assert short.first_token_time is not None and len(eng._rounds) == 1
    assert eng._rounds[0].active == [
        (i, s) for i, s in eng._rounds[0].active if s.request is first]
    assert len(first.output_tokens) == emitted + 2
    while not (first.done.is_set() and short.done.is_set()):
        eng.step()
    assert tuple(short.output_tokens) == _long_recompute(kind, 0)[:8]
    one = _one_row_engine(kind, max_concurrent_prefills=1,
                          pipelined_decode=False)
    want = one.submit(list(map(int, LONG[2][:40])), SamplingParams(
        max_new_tokens=60, temperature=0.0))
    while not want.done.is_set():
        one.step()
    assert first.output_tokens == want.output_tokens
    eng._allocator.assert_quiescent()


@pytest.mark.parametrize("kind", ONE_ROW)
def test_a_pass_that_ends_a_prompt_sends_a_due_chunk_before_it_waits(kind):
    """Two prompts in flight beside a live stream, one program a pass: the
    pass that ends the first prompt has the second one's chunk deferred,
    and sends it BEFORE it waits for the first token, in a pass of its own,
    the stream's next step riding (not a decode-only round, which would
    read every weight for a step the next chunk program carries anyway).
    The tokens are the full recompute's."""
    eng = _one_row_engine(kind)
    first = eng.submit(list(map(int, LONG[2][:40])), SamplingParams(
        max_new_tokens=60, temperature=0.0))
    while first.first_token_time is None:
        eng.step()
    eng.step()
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    a = eng.submit(list(map(int, LONG[2])), sp)         # 270: 256 + 14
    b = eng.submit(list(map(int, LONG[0])), sp)         # 300: 256 + 44
    before = eng.counters()
    eng.step()
    assert [ch.pos for ch in eng._chunkings] == [256, 0]
    emitted = len(first.output_tokens)
    eng.step()
    c = eng.counters()
    assert a.first_token_time is not None and b.first_token_time is None
    assert [(ch.request, ch.pos) for ch in eng._chunkings] == [(b, 256)]
    for name, n in (("prefill_programs_dispatched", 3),
                    ("mixed_programs_dispatched", 3), ("prefill_passes", 3),
                    ("decode_rounds", 3)):
        assert c[name] == before[name] + n, name
    assert len(eng._rounds) == 1 and len(first.output_tokens) == emitted + 2
    while not all(r.done.is_set() for r in (first, a, b)):
        eng.step()
    assert tuple(a.output_tokens) == _long_recompute(kind, 2)[:8]
    assert tuple(b.output_tokens) == _long_recompute(kind, 0)[:8]
    c = eng.counters()
    assert c["prefill_programs_dispatched"] == c["prefill_passes"] \
        == before["prefill_passes"] + 4
    eng._allocator.assert_quiescent()


def test_one_row_wide_nothing_compiles_after_a_first_run():
    """The parallel stack's program set is the engine's own: the mixed
    program (warmed when built, no slot riding) and the decode program; a
    second run of the same traffic compiles nothing."""
    eng = _one_row_engine("parallel")
    sp = SamplingParams(max_new_tokens=4, temperature=0.0)

    def run():
        reqs = [eng.submit(list(map(int, p)), sp) for p in LONG[:3]]
        while not all(r.done.is_set() for r in reqs):
            eng.step()

    run()
    compiles = CompileCounter()
    compiles.start()
    run()
    assert compiles.stop() == 0, compiles.names
    assert eng._programs.mixed._cache_size() == 1
    c = eng.counters()
    assert 0 < c["mixed_programs_dispatched"] < c[
        "prefill_programs_dispatched"]


@pytest.mark.parametrize("kind", FILLED_ONLY)
def test_a_stack_that_keeps_a_state_sends_no_chunk_ahead(kind):
    """Two rows a program (a chunk under the ridge) and the step carried,
    but a prompt alone goes ONE chunk a program: the chunk behind needs the
    state and the conv tail the chunk in front ENDS in, which the rows of one
    program do not hand on (``paged.chunk_rows_follow``: a KDA mixer's do
    not yet, and a parallel stack is served one row wide; an SSD mixer alone
    in its block does, ``AHEAD``). And a chunk alone
    fills one row of two, so no step rides with it (``ChunkPlan.rides``): it
    takes the one-row program, not the two-row one beside a dead row, and
    the live stream's step goes out as the decode program."""
    from kubeflow_tpu.serve.paged import chunk_rows_follow

    assert [chunk_rows_follow(_model(k)[0]) for k in KINDS] == [
        True, True, True, False, False, True]
    eng = _engine(kind)
    assert eng._plan.carries_step and eng._plan.rows == 2
    assert not eng._plan.ahead and not eng._plan.rows_only
    assert [eng._plan.rides(n) for n in (1, 2)] == [False, True]
    first = _beside_a_live_stream(eng)
    before, riding = _chunk_counts(eng), eng.counters()[
        "mixed_programs_dispatched"]
    got = _alone(eng)
    programs, chunks, ahead, dead = (
        a - b for a, b in zip(_chunk_counts(eng), before))
    assert (programs, chunks, ahead, dead) == (5, 5, 0, 0)
    assert eng.counters()["mixed_programs_dispatched"] == riding
    assert got == _alone(_engine(kind, max_concurrent_prefills=1))
    assert not first.done.is_set()


@pytest.mark.parametrize("kind", KINDS[3:])
def test_a_row_that_starts_its_sequence_beside_the_slots(kind):
    """A chunk from position 0 (``fresh``: its entry's state and conv tail
    read as zeros, whatever an earlier occupant of the first page left
    there) beside a dead chunk row and a dead slot among the riding ones:
    the one program against the chunk program and then the decode step,
    every plane (K and V pages, the state and the conv tail of EVERY entry,
    the row's and the slots')."""
    pool, _, state, rows = _scene(kind)
    a, row_a = rows[0][:2]
    # prompt a's 24 tokens are held: its entry is NOT zeros in the pool
    names = [n for n in pool if n not in ("k", "v", "moe_rows")]
    assert names and all(float(jnp.abs(pool[n][:, row_a[0]]).max()) > 0
                         for n in names)
    state = {**state, "live": np.asarray([True, False, True, True])}
    want, got = _both_ways(kind, state, [(a, row_a, 0, CHUNK, False), None])
    np.testing.assert_array_equal(got[1], want[1])
    for name in want[2]:
        _close(got[2][name], want[2][name], f"plane {name}")
    for i, name in enumerate(("tokens", "lengths", "live", "budgets"), 3):
        np.testing.assert_array_equal(got[i], want[i], err_msg=name)
    # and it is the state a pool that never held the sequence ends in
    chunk = _programs(kind)[0]
    empty = {n: jnp.zeros_like(p) for n, p in pool.items()}
    _, clean = chunk(_model(kind)[1], empty,
                     *_rows([(a, row_a, 0, CHUNK, False), None]))
    for n in names:
        _close(got[2][n][:, row_a[0]], clean[n][:, row_a[0]], f"entry {n}")


# -- a program's spare rows: the next chunks of the prompts in it (ISSUE 56) -----

LONE = _tokens(21, 4 * CHUNK + 9)       # five chunks, the last of 9 tokens


def _alone(eng, prompt=LONE, n=6, **kw):
    """``prompt`` served by itself; its greedy tokens."""
    req = eng.submit(list(map(int, prompt)), SamplingParams(
        max_new_tokens=n, temperature=0.0), **kw)
    while not req.done.is_set():
        eng.step()
    return list(req.output_tokens)


def _chunk_counts(eng):
    c = eng.counters()
    return tuple(c[f"prefill_{n}"] for n in (
        "programs_dispatched", "chunks_dispatched", "rows_ahead",
        "rows_dead"))


@functools.lru_cache(maxsize=None)
def _lone_recompute(kind: str, n: int = 6) -> tuple:
    """``LONE``'s greedy tokens by full recompute (``_recompute_behind``),
    which one prefill at a time, chunk by chunk, yields too."""
    want = _recompute_behind(kind, tuple(map(int, LONE)), n)
    assert _alone(_engine(kind, max_concurrent_prefills=1), n=n) == want
    return tuple(want)


def _odd_alone(kind: str) -> int:
    """Dead rows a prompt's odd last chunk leaves with NO slot live: none
    where the engine keeps the one-row program for it (ONE program whatever
    the bucket: per-head planes), one where that would be a program a
    bucket (the latent pool) and the chunk takes the program of the one
    width too (``engine._plan.rows_only``)."""
    return int(kind == "latent")


@pytest.mark.parametrize("kind", AHEAD)
def test_a_prompt_alone_goes_two_chunks_a_program(kind):
    """Five chunks sent alone: three programs (two of two consecutive
    chunks, no slot riding, and one for the odd last chunk), the tokens of
    the full recompute and of one prefill at a time."""
    eng = _engine(kind)
    assert eng._plan.ahead and eng._plan.rows == 2
    assert eng._plan.rows_only == (kind == "latent")
    assert _alone(eng) == list(_lone_recompute(kind))
    assert _chunk_counts(eng) == (3, 5, 2, _odd_alone(kind))
    c = eng.counters()
    assert c["prefill_row_programs_dispatched"] == 2 + _odd_alone(kind)
    assert c["prefill_tokens_dispatched"] == len(LONE)
    assert c["mixed_programs_dispatched"] == 0
    eng._allocator.assert_quiescent()


@pytest.mark.parametrize("lens,counts", [
    # as many chunks each: every program a row each, nothing ahead
    ((3 * CHUNK - 4, 2 * CHUNK + 7), (3, 6, 0, 0)),
    # the longer one is alone from its third chunk on: a chunk ahead, and
    # its odd last chunk beside a dead row, the first one's stream riding
    ((CHUNK + 5, 4 * CHUNK + 3), (4, 7, 1, 1)),
], ids=["as-long", "one-outlasts"])
def test_two_prompts_due_take_a_row_each(lens, counts):
    eng = _engine("dense")
    prompts = [_tokens(31 + i, n) for i, n in enumerate(lens)]
    sp = SamplingParams(max_new_tokens=20, temperature=0.0)
    reqs = [eng.submit(list(map(int, p)), sp) for p in prompts]
    eng.step()
    # the first program: a row each, whatever either has left
    assert _chunk_counts(eng) == (1, 2, 0, 0)
    assert [ch.pos for ch in eng._chunkings] == [CHUNK, CHUNK]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    assert _chunk_counts(eng) == counts
    one = _engine("dense", max_concurrent_prefills=1)
    assert [list(r.output_tokens) for r in reqs] == [
        _alone(one, p, n=20) for p in prompts]


def _beside_a_live_stream(eng, n=60):
    """A short prompt's stream, live for ``n`` tokens: what follows finds a
    slot riding."""
    first = eng.submit(list(map(int, _tokens(41, 10))), SamplingParams(
        max_new_tokens=n, temperature=0.0))
    while first.first_token_time is None:
        eng.step()
    return first


@pytest.mark.parametrize("kind", AHEAD)
def test_a_last_chunk_alone_leaves_its_row_dead(kind):
    """Three chunks beside a live stream: two in one program, then the last
    with nothing behind it, a dead row in the program of the one width."""
    eng = _engine(kind)
    first = _beside_a_live_stream(eng)
    assert _chunk_counts(eng) == (1, 1, 0, _odd_alone(kind))
    prompt = LONE[:2 * CHUNK + 9]
    got = _alone(eng, prompt)
    assert _chunk_counts(eng) == (3, 4, 1, 1 + _odd_alone(kind))
    assert eng.counters()["mixed_programs_dispatched"] == 2
    assert got == _alone(_engine(kind, max_concurrent_prefills=1), prompt)
    assert not first.done.is_set()


def test_a_chunk_ahead_without_pages_is_no_stall_and_goes_next_pass():
    """The pool has pages for the chunk that is due and none for the one
    behind it: the row stays dead, nobody stalls or is preempted, and the
    next pass sends that chunk as its due one (with the one behind IT)."""
    eng = _engine("dense")
    first = _beside_a_live_stream(eng)
    held = next(i for i, s in enumerate(eng.slots) if s is not None)
    tight, ensure = [True], eng._ensure_pages
    eng._ensure_pages = lambda slot, upto: (
        not (tight[0] and slot != held and upto > CHUNK)
        and ensure(slot, upto))
    req = eng.submit(list(map(int, LONE)), SamplingParams(
        max_new_tokens=6, temperature=0.0))
    eng.step()
    (ch,) = eng._chunkings
    assert (ch.pos, ch.stalls) == (CHUNK, 0)
    assert _chunk_counts(eng) == (2, 2, 0, 1)
    tight[0] = False
    eng.step()
    assert (ch.pos, ch.stalls) == (3 * CHUNK, 0)
    assert _chunk_counts(eng) == (3, 4, 1, 1)
    while not req.done.is_set():
        eng.step()
    assert _chunk_counts(eng) == (4, 6, 2, 1)
    assert list(req.output_tokens) == list(_lone_recompute("dense"))
    assert eng.metrics.preemptions == 0 and not first.done.is_set()


def test_an_abort_between_passes_finds_the_prefill_where_the_program_left_it():
    eng = _engine("dense")
    req = eng.submit(list(map(int, LONE)), SamplingParams(max_new_tokens=6))
    eng.step()
    (ch,) = eng._chunkings
    # two chunks written, their pages held and no more
    assert ch.pos == 2 * CHUNK
    assert len(eng._slot_pages[ch.slot]) == 2 * CHUNK // PAGE
    req.cancel()
    eng.step()
    assert not eng._chunkings and req.done.is_set()
    assert eng.kv_pages_in_use() == 0
    eng._allocator.assert_quiescent()
    # and the next prompt is served as ever
    assert _alone(eng) == list(_lone_recompute("dense"))


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_a_preemption_between_passes_registers_what_the_programs_wrote(kind):
    """A batch prompt goes alone, two chunks a program, until a second
    prompt joins (a row each); an interactive arrival then takes its lane:
    the prefix registered for it is every chunk a program carried, ahead or
    due, and its second prefill starts from there."""
    from kubeflow_tpu.core.serving import QoSSpec

    eng = _engine(kind, enable_prefix_caching=True,
                  qos=QoSSpec(preemption=True))
    sp = SamplingParams(max_new_tokens=6, temperature=0.0)
    prompts = [_tokens(51, 6 * CHUNK + 5), _tokens(52, 3 * CHUNK),
               _tokens(53, CHUNK + 3)]
    registered, register = [], eng._kv_register
    eng._kv_register = lambda toks, slot, n: (
        registered.append((len(toks), n)), register(toks, slot, n))[1]
    batch = eng.submit(list(map(int, prompts[0])), sp, qos="batch")
    eng.step()
    assert eng._chunkings[0].pos == 2 * CHUNK           # one chunk ahead
    other = eng.submit(list(map(int, prompts[1])), sp)
    eng.step()
    assert [ch.pos for ch in eng._chunkings] == [3 * CHUNK, CHUNK]
    urgent = eng.submit(list(map(int, prompts[2])), sp, qos="interactive")
    eng.step()
    assert eng.metrics.preemptions == 1
    assert registered[0] == (len(prompts[0]), 3 * CHUNK)
    assert batch not in [ch.request for ch in eng._chunkings]
    hits = eng.kv_tier_stats()["tokens_matched"]
    reqs = [batch, other, urgent]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    assert eng.kv_tier_stats()["tokens_matched"] >= hits + 3 * CHUNK
    one = _engine(kind, max_concurrent_prefills=1)
    assert [list(r.output_tokens) for r in reqs] == [
        _alone(one, p) for p in prompts]
    assert eng.kv_pages_in_use() == 0


@pytest.mark.parametrize("kind", AHEAD)
def test_a_prompt_alone_compiles_nothing_after_construction(kind):
    """The programs a prompt alone takes are the engine's own, run when it
    was built: the program of the one width with no slot riding and, where
    the engine keeps it for a prompt's odd last chunk, the one-row program
    under every bucket's name (``ChunkPrograms.warm``: ONE program; where
    it would be a program a bucket the engine's traffic never takes it).
    Once the first-token sampler has run, a second lone prompt compiles
    nothing, and neither jitted program has grown a variant."""
    eng = _engine(kind)
    one = getattr(eng._paged_chunk, "jitted", eng._paged_chunk)
    sizes = (eng._programs.mixed._cache_size(), one._cache_size())
    assert sizes == (1, 0 if eng._plan.rows_only else 1)
    assert {k for k in eng.start_programs() if k.startswith(
        "paged_chunk_prefill[1x")} == (set() if eng._plan.rows_only else {
            f"paged_chunk_prefill[1x{CHUNK},{b}]" for b in (2, 4, 8, 16)})
    _alone(eng)
    compiles = CompileCounter()
    compiles.start()
    _alone(eng, _tokens(22, 3 * CHUNK + 1))
    assert compiles.stop() == 0, compiles.names
    assert (eng._programs.mixed._cache_size(), one._cache_size()) == sizes
    assert _chunk_counts(eng) == (3 + 2, 5 + 4, 2 + 2, _odd_alone(kind))


def test_the_span_says_the_rows_filled_and_where_the_first_starts(
        monkeypatch):
    from test_serve_chunk_rows import record_spans

    eng = _engine("dispatch")
    first = _beside_a_live_stream(eng)
    seen = record_spans(monkeypatch)
    _alone(eng)
    chunks = [a for n, a in seen if n == "engine.prefill_dispatch"]
    assert [(a["pos"], a["chunks"]) for a in chunks] == [
        (0, 2), (2 * CHUNK, 2), (4 * CHUNK, 1)]
    assert len({a["slot"] for a in chunks}) == 1
    assert not first.done.is_set()
