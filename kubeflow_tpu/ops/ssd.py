"""The Mamba-2 mixer's recurrence (state-space duality, arXiv:2405.21060):
multi-head, ONE scalar decay a head and token, so that a block of positions
is a handful of matrix products. The operator of a "parallel" layer's second
branch (models/layers.py::ssd_block), in plain XLA forms and as two Pallas
TPU kernels.

Head ``j`` of ``H`` keeps a state ``S`` [N, P] in float32 (``N`` states, ``P``
values a head; ``P`` on the lanes). Its group ``g = j // (H / G)`` shares
``B_t`` and ``C_t`` [N]. A token with values ``x_t`` [P], step ``dt_t`` > 0
and ``a_t = exp(A dt_t)`` (``A`` < 0 a head) does

    S_t = a_t S_(t-1) + B_t (dt_t x_t)^T,      y_t = S_t^T C_t + D x_t.

**Over a chunk** (``ssd_chunk``): blocks of ``block`` positions, the state
carried block to block. With ``l_t`` the cumulative ``A dt`` inside a block
(inclusive, <= 0):

    y_t = sum_(s<=t) exp(l_t - l_s) (C_t . B_s) dt_s x_s
          + exp(l_t) S_0^T C_t + D x_t
    S_Q = exp(l_Q) S_0 + sum_s exp(l_Q - l_s) B_s (dt_s x_s)^T

The mask is applied to the exponent's ARGUMENT: every factor formed is
``exp(l_i - l_j)`` with ``i >= j``, at most 1 (``exp(-l_s)`` alone
overflows where a head forgets fast). A position whose ``dt`` is 0 (a
chunk's padded tail) passes the state through: no program needs a second
form for a tail.

Forms of the same sums:

- ``ssd_step_xla``: ONE token a row; ``ssd_scan_xla``: a ``lax.scan`` of it
  over positions: the CPU tests', ``decoder_forward``'s and the gathered
  chunk's, and what everything else here is tested against;
- ``ssd_blocks_xla``: the chunked form in XLA, a ``lax.scan`` over blocks of
  ``block_update``, the ONE statement of a block's products;
- ``ssd_chunk`` with ``impl="pallas"``: the kernel ``ssd_chunk``, a grid
  over (row, group, block): the group's ``C B^T`` once for its heads, each
  head's ``block_update`` with its state in VMEM across the blocks (in and
  out once a call), ``B`` and ``C`` read once a GROUP; operands to the
  matrix unit in the activation type, sums, decays and the state in float32;
  where rows FOLLOW one another (``follows``: consecutive chunks of one
  sequence as rows of one call) the grid is (group, row, block) and a row
  behind starts from the state the row in front ended in, kept in VMEM;
- ``ssd_step`` with ``impl="pallas"``: the kernel ``ssd_step``: one token a
  LIVE row, the row's ``[H, N, P]`` state fetched from and written back to
  its entry of the pool's plane (the plane aliased to the result); a dead
  row moves nothing.

**Heads narrower than the chip's 128 lanes share a tile in the pool's
plane** (``heads_a_tile``, ``pack_state`` / ``unpack_state``): an array
whose last axis is 64 is held padded to 128, so a plane ``[E, 128, 128, 64]``
takes twice its bytes and the chip's compiler copies it whole around the
step kernel's call (compiled for a described v5e, PR 61: 5.4 GB of
temporaries beside a plane of 2.7). Two heads of 64 of one group therefore
lie side by side, ``[E, H/2, N, 2P]``: to ``ssd_step`` a pair is ONE head of
128 values whose lanes decay at two rates; ``ssd_chunk`` takes and hands back
``[B, H, N, P]`` and its callers turn the few rows' states either way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import auto_interpret

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 128         # positions a block of the chunked form (mamba_chunk_size)
# What a grid step of ``ssd_chunk`` keeps in fast memory at 16 heads of [256,
# 128] a group: the state's blocks in and out, each twice (8 MiB), ``x dt``
# and ``y`` twice (3): over the compiler's default of 16 MiB with its own
# temporaries, a quarter of a v5e's 128.
CHUNK_VMEM_BYTES = 32 * 2 ** 20


def heads_a_tile(heads: int, groups: int, p: int) -> int:
    """Heads whose values lie side by side in one 128-lane tile of a state
    plane: ``128 // p`` where heads of ``p`` values are narrower than a tile
    and a group's heads go into such sets whole (they share ``B`` and
    ``C``); else 1, the plane is ``[.., H, N, P]``."""
    r = 128 // p if p < 128 and 128 % p == 0 else 1
    return r if (heads // groups) % r == 0 else 1


def pack_state(state, r: int):
    """``[.., H, N, P]`` as a plane holds it: ``[.., H/r, N, r P]``, head ``r
    j + i`` in lanes ``i P ..`` of packed head ``j``: the heads of a set
    concatenated on the lanes, and ``unpack_state`` lane slices stacked. A
    ``swapaxes`` between two reshapes says the same and costs a plane: the
    chip's compiler carries a transposition through a one-row program's
    dynamic slice to the program's parameter and lays the WHOLE plane out
    again on the way in and out (2.7 GB twice a lone chunk program of
    Nemotron-3-Super: PERF.md section 6, PR 62); a slice of lanes stays on
    the entry."""
    if r == 1:
        return state
    *lead, h, n, p = state.shape
    sets = state.reshape(*lead, h // r, r, n, p)
    return jnp.concatenate([sets[..., i, :, :] for i in range(r)], axis=-1)


def unpack_state(state, heads: int):
    """``pack_state``'s inverse, to ``heads`` heads."""
    *lead, hp, n, wide = state.shape
    r = heads // hp
    if r == 1:
        return state
    p = wide // r
    return jnp.stack([state[..., i * p:(i + 1) * p] for i in range(r)],
                     axis=-3).reshape(*lead, heads, n, p)


def _grouped(m, heads: int):
    """``B`` or ``C`` [.., G, N] as every head reads it: [.., H, N]."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


# -- one token, and position after position -----------------------------------

def ssd_step_xla(x, dt, a, bm, cm, d, state):
    """The recurrence for one token a row. x [B, H, P]; dt [B, H] (> 0; 0:
    the state passes through); a [H] (< 0); bm, cm [B, G, N]; d [H]; state
    [B, H, N, P] float32. Returns (y [B, H, P] float32, the state after)."""
    h = x.shape[1]
    x, dt, bm, cm = (v.astype(F32) for v in (x, dt, bm, cm))
    decay = jnp.exp(dt * a.astype(F32))
    state = decay[..., None, None] * state \
        + _grouped(bm, h)[..., None] * (dt[..., None] * x)[..., None, :]
    y = jnp.sum(state * _grouped(cm, h)[..., None], axis=-2)
    return y + d.astype(F32)[:, None] * x, state


def _rows_in_turn(chunk, x, dt, bm, cm, state, follows):
    """``chunk`` ((x, dt, bm, cm, state) -> (y, the state after), rows alike)
    ONE ROW BEHIND THE OTHER: row ``r`` starts from the state row ``r - 1``
    ended in where ``follows[r]`` ([B] bool; never row 0), else from
    ``state[r]``. What ``follows`` means to every form of a chunk here: the
    rows are consecutive chunks of ONE sequence, and the state handed on is
    the float32 one a plane would have held between two programs."""
    def row(carry, xs):
        *ops, s0, f = xs
        y, end = chunk(*(v[None] for v in ops), jnp.where(f, carry, s0)[None])
        return end[0], (y[0], end[0])

    state = state.astype(F32)
    _, (y, ends) = jax.lax.scan(row, jnp.zeros_like(state[0]),
                                (x, dt, bm, cm, state, follows))
    return y, ends


def ssd_scan_xla(x, dt, a, bm, cm, d, state, follows=None):
    """``S`` tokens a row from ``state``, TOKEN BY TOKEN: x [B, S, H, P];
    dt [B, S, H]; bm, cm [B, S, G, N]; ``follows``: ``_rows_in_turn``.
    Returns (y [B, S, H, P] float32, the state after the last token)."""
    if follows is not None:
        return _rows_in_turn(
            lambda x, dt, bm, cm, s: ssd_scan_xla(x, dt, a, bm, cm, d, s),
            x, dt, bm, cm, state, follows)

    def step(s, xs):
        xt, dtt, bt, ct = xs
        y, s = ssd_step_xla(xt, dtt, a, bt, ct, d, s)
        return s, y

    def seq(v):
        return jnp.swapaxes(v, 0, 1)

    state, y = jax.lax.scan(step, state.astype(F32),
                            (seq(x), seq(dt), seq(bm), seq(cm)))
    return seq(y), state


# -- a chunk: a block's products ----------------------------------------------

def _dot(x, y):
    """``x @ y`` on the matrix unit: float32 sums; float32 operands (the
    tests' tight path) at full precision."""
    return jnp.dot(x, y, preferred_element_type=F32,
                   precision=HIGHEST if x.dtype == F32 else None)


def block_update(g, xdt, l_row, bt, c, s, dtype):
    """ONE head through ONE block of ``Q`` positions: ``g`` [Q, Q] float32
    the group's ``C B^T``; ``xdt`` [Q, P] the values times their steps;
    ``l_row`` [1, Q] float32 the cumulative log-decay (inclusive); ``bt``
    [N, Q], ``c`` [Q, N]; ``s`` [N, P] float32 the state before. ``dtype``:
    what the matrix unit's operands are rounded to. Returns (y [Q, P]
    float32 WITHOUT the skip term, the state after). Plain ``jax.numpy`` on
    values: the XLA form maps it, the kernel's body calls it."""
    q = g.shape[0]
    lt = jnp.broadcast_to(l_row, (q, q))        # l_s along the lanes
    lc = lt.T                                   # l_t down the sublanes
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.exp(jnp.where(rows >= cols, lc - lt, -jnp.inf))
    y = _dot((g * decay).astype(dtype), xdt) \
        + jnp.exp(lc[:, :1]) * _dot(c, s.astype(dtype))
    l_end = l_row[:, q - 1:q]
    s = jnp.exp(l_end) * s \
        + _dot((bt.astype(F32) * jnp.exp(l_end - l_row)).astype(dtype), xdt)
    return y, s


def _chunk_operands(x, dt, a, bm, cm, block: int):
    """What the chunked form takes, heads-major and whole blocks: (xdt [B,
    H, S', P] in ``x``'s type, l [B, H, 1, S'] float32, bt [B, G, N, S'], c
    [B, G, S', N], the block), ``S'`` the length padded with positions that
    leave the state alone."""
    s = x.shape[1]
    block = min(block, -(-s // 8) * 8)
    pad = -s % block
    if pad:
        x, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for v in (x, bm, cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    b, sp, h, _ = x.shape
    dt = dt.astype(F32)
    xdt = jnp.swapaxes((x.astype(F32) * dt[..., None]).astype(x.dtype), 1, 2)
    la = (dt * a.astype(F32)).reshape(b, sp // block, block, h)
    l = jnp.cumsum(la, axis=2).reshape(b, sp, h)
    return (xdt, jnp.swapaxes(l, 1, 2)[:, :, None, :],
            jnp.transpose(bm, (0, 2, 3, 1)), jnp.swapaxes(cm, 1, 2), block)


def ssd_blocks_xla(x, dt, a, bm, cm, d, state, follows=None, *,
                   block: int = BLOCK):
    """The chunked form in XLA: ``block_update`` a (row, head), block after
    block. Same arguments and results as ``ssd_scan_xla``."""
    if follows is not None:
        return _rows_in_turn(
            lambda x, dt, bm, cm, s: ssd_blocks_xla(x, dt, a, bm, cm, d, s,
                                                    block=block),
            x, dt, bm, cm, state, follows)
    s, h = x.shape[1], x.shape[2]
    xdt, l, bt, c, block = _chunk_operands(x, dt, a, bm, cm, block)
    nb = xdt.shape[2] // block
    per = h // bt.shape[1]

    def blocks(v, axis):        # the positions' axis cut into blocks, first
        v = jnp.moveaxis(v, axis, 0)
        return v.reshape(nb, block, *v.shape[1:])

    def one(st, blk):
        xdt_b, l_b, bt_b, c_b = blk     # [Q,B,H,P] [Q,B,H,1] [Q,B,G,N] x2
        bt_b, c_b = (jnp.moveaxis(v, 0, 2) for v in (bt_b, c_b))  # [B,G,Q,N]
        g = jax.vmap(jax.vmap(lambda cc, bb: _dot(cc, bb.T)))(c_b, bt_b)
        head = jax.vmap(jax.vmap(
            lambda gg, xx, ll, bb, cc, ss: block_update(
                gg, xx, ll, bb.T, cc, ss, x.dtype)))
        y, st = head(jnp.repeat(g, per, axis=1),
                     jnp.moveaxis(xdt_b, 0, 2),
                     jnp.moveaxis(l_b, 0, 3),
                     jnp.repeat(bt_b, per, axis=1),
                     jnp.repeat(c_b, per, axis=1), st)
        return st, y

    state, y = jax.lax.scan(
        one, state.astype(F32),
        (blocks(xdt, 2), blocks(l, 3), blocks(bt, 3), blocks(c, 2)))
    y = jnp.moveaxis(y, 0, 2).reshape(*xdt.shape)           # [B,H,S',P]
    y = jnp.swapaxes(y, 1, 2)[:, :s]
    return y + d.astype(F32)[:, None] * x.astype(F32), state


# -- a chunk: the kernel ------------------------------------------------------

def _chunk_blocks(xdt_ref, l_ref, bt_ref, c_ref, y_ref, so_ref, heads: int):
    """One block of a (row, group): the group's ``C B^T`` once, then each of
    its heads' ``block_update`` on ``so_ref``, the state's result block,
    which stays in VMEM across the row's blocks and is the state they
    carry."""
    bt, c = bt_ref[0, 0], c_ref[0, 0]
    g = _dot(c, bt)

    def head(j, carry):
        y_ref[0, j], so_ref[0, j] = block_update(
            g, xdt_ref[0, j], l_ref[0, j], bt, c, so_ref[0, j], c.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _chunk_kernel(xdt_ref, l_ref, bt_ref, c_ref, s_ref, y_ref, so_ref, *,
                  heads: int):
    """One (row, group, block): every row from its own state."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        so_ref[...] = s_ref[...]

    _chunk_blocks(xdt_ref, l_ref, bt_ref, c_ref, y_ref, so_ref, heads)


def _chunk_rows_kernel(follows_ref, xdt_ref, l_ref, bt_ref, c_ref, s_ref,
                       y_ref, so_ref, end_ref, *, heads: int):
    """One (group, row, block), a group's rows ONE BEHIND THE OTHER: a row
    that follows (``follows_ref``, prefetched) starts from ``end_ref``, the
    state the row in front ended in, kept in VMEM from its last block; every
    other row from its own state."""
    r, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        @pl.when(follows_ref[r] == 0)
        def _():
            so_ref[...] = s_ref[...]

        @pl.when(follows_ref[r] != 0)
        def _():
            so_ref[...] = end_ref[...]

    _chunk_blocks(xdt_ref, l_ref, bt_ref, c_ref, y_ref, so_ref, heads)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        end_ref[...] = so_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _chunk_call(x, dt, a, bm, cm, state, follows=None, *, block: int,
                interpret: bool):
    """``ssd_chunk`` as ONE kernel call, reached through this one cached
    call, so a program traces the kernel's body once however many layers and
    rows call it. The grid is (row, group, block); with ``follows`` (group,
    row, block), the rows of a group in turn, and ``follows`` prefetched.
    Returns (y [B, S, H, P] float32 without the skip term, the state
    after)."""
    s, h = x.shape[1], x.shape[2]
    xdt, l, bt, c, block = _chunk_operands(x, dt, a, bm, cm, block)
    b, _, sp, p = xdt.shape
    groups, n = bt.shape[1], bt.shape[2]
    hb = h // groups
    in_turn = follows is not None

    def spec(shape, at):    # ``at``: (row, group, block) -> the block's index
        return pl.BlockSpec(shape, (lambda g, r, i, _: at(r, g, i))
                            if in_turn else at)

    in_specs = [
        spec((1, hb, block, p), lambda r, g, i: (r, g, i, 0)),
        spec((1, hb, 1, block), lambda r, g, i: (r, g, 0, i)),
        spec((1, 1, n, block), lambda r, g, i: (r, g, 0, i)),
        spec((1, 1, block, n), lambda r, g, i: (r, g, i, 0)),
        spec((1, hb, n, p), lambda r, g, i: (r, g, 0, 0))]
    out_specs = [
        spec((1, hb, block, p), lambda r, g, i: (r, g, i, 0)),
        spec((1, hb, n, p), lambda r, g, i: (r, g, 0, 0))]
    if in_turn:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, b, sp // block),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((1, hb, n, p), F32)]))
        kernel, order = _chunk_rows_kernel, ("parallel", "arbitrary",
                                             "arbitrary")
        operands = (follows.astype(jnp.int32),)
    else:
        grid = dict(grid=(b, groups, sp // block), in_specs=in_specs,
                    out_specs=out_specs)
        kernel, order = _chunk_kernel, ("parallel", "parallel", "arbitrary")
        operands = ()
    y, state = pl.pallas_call(
        functools.partial(kernel, heads=hb),
        name="ssd_chunk",
        **grid,
        out_shape=[jax.ShapeDtypeStruct((b, h, sp, p), F32),
                   jax.ShapeDtypeStruct((b, h, n, p), F32)],
        input_output_aliases={len(operands) + 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order,
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        interpret=interpret,
    )(*operands, xdt, l, bt, c, state.astype(F32))
    return jnp.swapaxes(y, 1, 2)[:, :s], state


def ssd_chunk(x, dt, a, bm, cm, d, state, *, follows=None,
              impl: str = "xla", block: int = BLOCK,
              interpret: Optional[bool] = None):
    """A chunk a row, from a state to a state. x [B, S, H, P] (the
    activation type: what the matrix unit's operands are rounded to); dt [B,
    S, H] float32 (0: a position that leaves the state alone); a, d [H];
    bm, cm [B, S, G, N]; state [B, H, N, P] float32; ``S`` any length.
    ``follows`` ([B] bool, or None: no row does): row ``r`` is the chunk
    behind row ``r - 1``'s and starts from the state that row ENDS in, not
    from ``state[r]`` (``_rows_in_turn``); every row's end state comes back.
    ``impl`` "pallas": the kernel ``ssd_chunk`` over blocks of ``block``
    positions; "xla": the recurrence token by token (``ssd_scan_xla``).
    Returns (y [B, S, H, P] float32, the state after)."""
    if impl == "xla":
        return ssd_scan_xla(x, dt, a, bm, cm, d, state, follows)
    if impl != "pallas":
        raise ValueError(f"unknown ssd impl {impl!r}; one of xla|pallas")
    y, end = _chunk_call(
        x, dt, a, bm, cm, state, follows, block=block,
        interpret=auto_interpret() if interpret is None else interpret)
    return y + d.astype(F32)[:, None] * x.astype(F32), end


# -- one token, the state where it lies in the pool ---------------------------

def _lane_tiles(p: int) -> int:
    """``p`` values in whole tiles of the chip's 128 lanes."""
    return -(-p // 128) * 128


def _step_kernel(idx_ref, n_ref, fresh_ref, cols_ref, rows_ref, s_ref,
                 so_ref, o_ref, *, heads: int, p: int):
    """``heads`` (packed) heads of ``p`` lanes a grid step: a lane's decay
    rides in the lane of the same place one tile on (``_step_call``)."""
    bi = pl.program_id(0)
    at = _lane_tiles(p)

    @pl.when(bi < n_ref[0])
    def _():
        keep = jnp.where(fresh_ref[bi] > 0, 0.0, 1.0).astype(F32)
        b_col, c_col = cols_ref[0, 0, :, 0:1], cols_ref[0, 0, :, 1:2]
        for h in range(heads):
            xdt = rows_ref[0, 0, h:h + 1, :p]                       # [1, P]
            decay = rows_ref[0, 0, h:h + 1, at:at + p]              # [1, P]
            s = s_ref[0, h] * (decay * keep) + b_col * xdt
            so_ref[0, h] = s
            o_ref[0, 0, h:h + 1, :] = jnp.sum(s * c_col, axis=0,
                                              keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(x, dt, a, bm, cm, plane, idx, fresh, live, *,
               interpret: bool):
    """``ssd_step`` as ONE kernel call over ``plane`` ([E, H/r, N, r P]:
    ``pack_state``), aliased to its first result: a grid step a (live row,
    group), the group's heads' state block fetched from and written back to
    the row's entry. What scales a state's ROWS (``B``, ``C``: [N]) rides as
    columns [N, 2], what scales its columns as rows: a packed head's ``dt
    x`` ([r P]: its ``r`` heads' side by side, as ``x`` lies anyway) and,
    from the next whole tile of 128 lanes on, each lane's decay (its own
    head's: a packed head is to the kernel ONE head whose lanes decay at
    ``r`` rates). Returns (y [B, H, P] float32 without the skip term, the
    plane)."""
    b, h, _ = x.shape
    groups, n = bm.shape[1], bm.shape[2]
    heads, p = plane.shape[1], plane.shape[3]   # as packed
    hb = heads // groups
    # Live rows first: the grid walks them and stays on the last one's
    # blocks for the rest (no fetch, no write: the body is skipped).
    order = jnp.argsort(~live, stable=True)
    n_live = jnp.sum(live, dtype=jnp.int32)
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))
    wide = _lane_tiles(p) + p
    xdt = x.astype(F32) * dt[..., None]
    rows = jnp.concatenate(
        [xdt.reshape(b, heads, p),
         *([jnp.zeros((b, heads, wide - 2 * p), F32)] if wide > 2 * p
           else []),
         jnp.broadcast_to(decay[..., None], xdt.shape).reshape(b, heads, p)],
        axis=-1)[order].reshape(b, groups, hb, wide)
    cols = jnp.stack([bm.astype(F32), cm.astype(F32)], axis=-1)[order]

    def at(bi, gi, n_ref):
        dead = bi >= n_ref[0]
        return (jnp.where(dead, jnp.maximum(n_ref[0] - 1, 0), bi),
                jnp.where(dead, groups - 1, gi))

    def row_map(bi, gi, idx_ref, n_ref, fresh_ref):
        return (*at(bi, gi, n_ref), 0, 0)

    def state_map(bi, gi, idx_ref, n_ref, fresh_ref):
        r, g = at(bi, gi, n_ref)
        return (idx_ref[r], g, 0, 0)

    call = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, p=p),
        name="ssd_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, groups),
            in_specs=[pl.BlockSpec((1, 1, n, 2), row_map),
                      pl.BlockSpec((1, 1, hb, wide), row_map),
                      pl.BlockSpec((1, hb, n, p), state_map)],
            out_specs=[pl.BlockSpec((1, hb, n, p), state_map),
                       pl.BlockSpec((1, 1, hb, p), row_map)]),
        out_shape=[jax.ShapeDtypeStruct(plane.shape, plane.dtype),
                   jax.ShapeDtypeStruct((b, groups, hb, p), F32)],
        input_output_aliases={5: 0},
        interpret=interpret,
    )
    idx_s = jnp.clip(idx[order], 0, plane.shape[0] - 1).astype(jnp.int32)
    plane, o = jax.lax.cond(
        n_live > 0,
        lambda pln: tuple(call(idx_s, n_live[None], fresh[order].astype(
            jnp.int32), cols, rows, pln)),
        lambda pln: (pln, jnp.zeros((b, groups, hb, p), F32)), plane)
    o = jnp.zeros_like(o).at[order].set(o).reshape(x.shape)
    return o, plane


def ssd_step(x, dt, a, bm, cm, d, plane, idx, fresh, live, *,
             impl: str = "xla", interpret: Optional[bool] = None):
    """One token a row against the state IN the pool. x [B, H, P]; dt [B,
    H]; a, d [H]; bm, cm [B, G, N]; ``plane`` [E, H/r, N, r P] float32
    (every entry of every layer, flat; ``r`` heads a lane tile:
    ``pack_state``); ``idx`` [B] the row's entry; ``fresh`` [B]:
    start from zeros (a sequence's first token); ``live`` [B]: a dead row
    reads and writes nothing and gets zeros. ``impl`` "pallas": the kernel
    ``ssd_step``, the plane aliased to the result; "xla": gather, the
    recurrence, scatter. Returns (y [B, H, P] float32, the plane)."""
    if impl == "pallas":
        y, written = _step_call(
            x, dt, a, bm, cm, plane, idx, fresh, live,
            interpret=auto_interpret() if interpret is None else interpret)
        y = y + d.astype(F32)[:, None] * x.astype(F32)
    elif impl == "xla":
        e, h = plane.shape[0], x.shape[1]
        state = unpack_state(plane[jnp.clip(idx, 0, e - 1)], h)
        state = jnp.where((fresh | ~live)[:, None, None, None], 0.0, state)
        y, state = ssd_step_xla(x, dt, a, bm, cm, d, state)
        written = plane.at[jnp.where(live, idx, e)].set(
            pack_state(state, h // plane.shape[1]), mode="drop")
    else:
        raise ValueError(f"unknown ssd impl {impl!r}; one of xla|pallas")
    return jnp.where(live[:, None, None], y, 0.0), written
