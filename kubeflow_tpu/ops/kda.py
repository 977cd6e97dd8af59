"""Gated delta-rule linear attention (Kimi Delta Attention) with a decay a
CHANNEL: the operator of a "linear" layer (models/layers.py::kda_block), in
a plain XLA form and as three Pallas TPU kernels.

A head keeps a matrix ``S`` [dk, dv] in float32. A token with query ``q``,
key ``k`` (both L2-normalised, ``q`` scaled), value ``v``, log-decay ``g``
[dk] (<= 0, ``a = exp(g)``) and step ``beta`` in (0, 2) does

    S <- (I - beta k k^T) Diag(a) S + beta k v^T,      o = S^T q.

**Over a chunk** (``kda_chunk``): blocks of ``block`` positions, the state
carried block to block. With ``G`` the cumulative log-decay inside a block
(inclusive) and ``u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t)``:

    (I + A) U = beta * (V - (K e^G) S_0),  A[t,j] = beta_t sum_c k_t k_j e^(G_t - G_j), j < t
    O = (Q e^G) S_0 + B U,                 B[t,j] = sum_c q_t k_j e^(G_t - G_j), j <= t
    S_T = Diag(e^(G_T)) S_0 + (K e^(G_T - G))^T U

so ``U = U~ - W S_0`` with ``U~ = (I + A)^-1 beta V`` and ``W = (I +
A)^-1 beta K e^G``, neither of which reads the state: they are computed for
every block at once (``block_operands``; on a TPU the kernel
``kda_operands``, which keeps a block's differences, products and solve in
VMEM and writes the six arrays the scan takes, as it takes them), and what
runs block after block is four matrix products (``_scan_blocks_xla``; on a
TPU the kernel ``kda_chunk``, which keeps ``S`` in VMEM across the blocks of
a chunk). The two kernels stay two: the benchmark counts the scan's
operands as bytes it reads from HBM.

**Decays a channel make the usual ``k / e^G`` overflow** (a channel may lose
a factor e^30 a token). Every factor formed here is ``e^(G_i - G_j)`` with
``i >= j``, at most 1: inside a SUB-block of ``sub`` positions from the
differences themselves ([sub, sub, dk] of them), across sub-blocks of one
block through a reference between the two, ``e^(G_t - R) e^(R - G_j)`` with
``R`` the cumulative decay at the end of the sub-block before ``t``'s, both
factors at most 1 (a product that underflows is a term that is zero in
float32 anyway). ``(I + A)^-1`` is forward substitution: row by row inside a
sub-block's diagonal block (the kernel: diagonal by diagonal, every
sub-block at once), sub-block by sub-block across them.

A position with ``beta = 0`` and ``g = 0`` (a last chunk's padding) leaves
the state as it was: no program needs a second form for a tail.

**One token** (``kda_step``): the recurrence itself. On a TPU the kernel
reads a live stream's [H, dk, dv] state from its entry of the pool's plane
and writes it back in place (the plane is aliased to the result); a dead row
reads and writes nothing.
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
BLOCK = 64          # positions a block of the chunked form
SUB = 16            # positions a sub-block (factors by differences)
STEP_HEADS = 32     # heads one grid step of ``kda_step`` holds


def _ein(spec: str, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST,
                      preferred_element_type=F32)


# -- one token -------------------------------------------------------------------

def kda_step_xla(q, k, v, g, beta, state):
    """The recurrence for one token a row. q, k, v, g [B, H, dk]; beta [B,
    H]; state [B, H, dk, dv] float32. Returns (o [B, H, dv], the state
    after)."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    s1 = state * jnp.exp(g)[..., None]
    u = jnp.sum(s1 * k[..., None], axis=-2)                       # [B,H,dv]
    s2 = s1 + (beta[..., None] * k)[..., None] * (v - u)[..., None, :]
    return jnp.sum(s2 * q[..., None], axis=-2), s2


# -- a chunk: what does not read the state -----------------------------------------

def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower-triangular ``a`` [..., n, n], by forward
    substitution, a row at a time."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=F32)
    rows = []
    for t in range(n):
        r = jnp.broadcast_to(eye[t], a.shape[:-2] + (n,))
        if t:
            r = r - _ein("...j,...jm->...m", a[..., t, :t],
                         jnp.stack(rows, axis=-2))
        rows.append(r)
    return jnp.stack(rows, axis=-2)


def block_operands(q, k, v, g, beta, block: int = BLOCK, sub: int = SUB):
    """Everything the chunked form computes WITHOUT the state, for every
    block at once. q, k, v, g [B, H, S, dk] float32 (``S`` whole blocks);
    beta [B, H, S]. Returns a dict of [B, H, nb, ...] arrays: ``qg`` = Q e^G
    [T, dk], ``w`` [T, dk], ``ut`` [T, dv], ``bm`` [T, T], ``kdt`` = (K
    e^(G_T - G))^T [dk, T], ``gt`` = e^(G_T) [dk]."""
    b, h, s, dk = q.shape
    t = block
    nb, ns = s // t, t // sub
    q, k, v, g = (x.astype(F32).reshape(b, h, nb, t, -1)
                  for x in (q, k, v, g))
    beta = beta.astype(F32).reshape(b, h, nb, t)
    big_g = jnp.cumsum(g, axis=3)                                 # inclusive
    gs, ks, qs = (x.reshape(b, h, nb, ns, sub, dk) for x in (big_g, k, q))
    # inside a sub-block: the differences themselves
    diff = gs[..., :, None, :] - gs[..., None, :, :]       # [.., sub, sub, dk]
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    e = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    kk_d = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * e, axis=-1)
    qk_d = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * e, axis=-1)
    # across sub-blocks: through R, the decay up to the end of the sub-block
    # before the row's own (both factors at most 1)
    r = jnp.concatenate([jnp.zeros_like(gs[..., :1, 0, :]),
                         gs[..., :-1, sub - 1, :]], axis=-2)  # [.., ns, dk]
    row_f = jnp.exp(gs - r[..., None, :])                   # [.., ns, sub, dk]
    before = (jnp.arange(t)[None, :] // sub) < jnp.arange(ns)[:, None]
    col_f = jnp.exp(jnp.where(
        before[..., None],
        r[..., :, None, :] - big_g[..., None, :, :], -jnp.inf))  # [.., ns, T, dk]
    kl = k[..., None, :, :] * col_f
    kk_o = _ein("...itc,...ijc->...itj", ks * row_f, kl).reshape(
        b, h, nb, t, t)
    qk_o = _ein("...itc,...ijc->...itj", qs * row_f, kl).reshape(
        b, h, nb, t, t)
    eye = jnp.eye(ns, dtype=F32)

    def whole(diag, off):       # [.., ns, sub, sub] on the diagonal of [T, T]
        d = diag[..., :, :, None, :] * eye[:, None, :, None]
        return d.reshape(b, h, nb, t, t) + off

    strict = jnp.tril(jnp.ones((t, t), F32), -1)
    a = whole(kk_d, kk_o) * strict * beta[..., None]
    bm = whole(qk_d, qk_o)
    eg = jnp.exp(big_g)
    g_end = big_g[..., -1:, :]
    rhs = beta[..., None] * jnp.concatenate([v, k * eg], axis=-1)
    # (I + A)^-1 rhs: a sub-block's rows from those before it, then its own
    # diagonal block's inverse
    a4 = a.reshape(b, h, nb, ns, sub, ns, sub)
    rhs4 = rhs.reshape(b, h, nb, ns, sub, -1)
    d_inv = _unit_lower_inverse(
        jnp.stack([a4[..., i, :, i, :] for i in range(ns)], axis=3))
    xs = []
    for i in range(ns):
        y = rhs4[..., i, :, :]
        for j in range(i):
            y = y - _ein("...tj,...jm->...tm", a4[..., i, :, j, :], xs[j])
        xs.append(_ein("...tj,...jm->...tm", d_inv[..., i, :, :], y))
    x = jnp.concatenate(xs, axis=-2)                        # [.., T, dv + dk]
    dv = v.shape[-1]
    return {"qg": q * eg, "w": x[..., dv:], "ut": x[..., :dv], "bm": bm,
            "kdt": jnp.swapaxes(k * jnp.exp(g_end - big_g), -1, -2),
            "gt": jnp.exp(g_end[..., 0, :])}


# -- a chunk: the same operands from ONE kernel ------------------------------------

def _operands_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, qg_ref, w_ref,
                     ut_ref, bm_ref, kdt_ref, gt_ref, rows, *, nb: int,
                     t: int, sub: int, lw: int):
    """``block_operands`` for one (row, head), nothing of it leaving VMEM,
    ``lw`` positions (whole blocks) a loop turn.

    Inside sub-blocks the positions are taken TRANSPOSED, a channel a
    sublane and a position a lane, and a [sub, sub] block of a matrix by its
    DIAGONALS, one row of positions an offset ``d``: the factor of a pair
    ``(t, t - d)`` is ``e^(G_t - G_(t-d))``, a lane roll by ``d`` and a
    difference, and the sum over channels is a sum over sublanes, so that no
    [sub, sub, dk] array and no lane reduction is formed. The diagonal
    blocks' ``(I + A)^-1 = N`` is forward substitution on those rows,
    ``N_d = -sum_e A_e roll(N_(d-e), e)``, all sub-blocks at once. The
    diagonals (``rows``: an offset a row, reversed, N from row 0 and qk from
    row T) are then turned back, a position a row, and rolled by the row's
    own number, which puts each where it belongs in [T, T]. The rest is a
    block as it lies: the products across sub-blocks through the reference
    ``R``, ``B``, and the forward substitution sub-block by sub-block."""
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]
    ns, wide = t // sub, dv + dk

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=F32, precision=HIGHEST)

    def dot_t(x, y):                                # x @ y^T
        return jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), preferred_element_type=F32,
            precision=HIGHEST)

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

    def f32(ref, at):
        return ref[0, 0, at, :].astype(F32)

    pr, pc = iota((lw, lw), 0), iota((lw, lw), 1)
    cum = ((pc <= pr) & (pc // t == pr // t)).astype(F32)
    in_sub = iota((1, lw), 1) % sub
    which = iota((dk, nb), 1)
    row = iota((t, 1), 0)
    rows[...] = jnp.zeros(rows.shape, F32)

    def block(at, g, beta, dg):
        q, k, v = f32(q_ref, at), f32(k_ref, at), f32(v_ref, at)
        eg = jnp.exp(g)
        qg_ref[0, 0, at, :] = q * eg
        # across sub-blocks: through R, the decay at the end of the one before
        kk, qk = [jnp.zeros((sub, t), F32)], [jnp.zeros((sub, t), F32)]
        for s in range(1, ns):
            own = slice(s * sub, (s + 1) * sub)
            r = g[s * sub - 1:s * sub, :]
            row_f = jnp.exp(g[own] - r)
            col = k * jnp.exp(jnp.where(row < s * sub, r - g, -jnp.inf))
            o = dot_t(jnp.concatenate([k[own] * row_f, q[own] * row_f]), col)
            kk.append(o[:sub])
            qk.append(o[sub:])
        # (I + A)^-1 [beta V | beta K e^G]: the diagonal blocks' inverses
        # first (on A's other blocks too), then a sub-block's rows from
        # those before it
        x = jnp.concatenate([beta * v, beta * (k * eg),
                             beta * jnp.concatenate(kk)], axis=1)
        x = dot(dg[:, :t], x)
        a_off = x[:, wide:]
        xs = [x[:sub, :wide]]
        for s in range(1, ns):
            own = slice(s * sub, (s + 1) * sub)
            xs.append(x[own, :wide] - dot(a_off[own, :s * sub],
                                          jnp.concatenate(xs)))
        x = jnp.concatenate(xs)
        return jnp.concatenate(qk) + dg[:, t:], x[:, :dv], x[:, dv:]

    def tile(i, gt):
        at = pl.ds(pl.multiple_of(i * lw, lw), lw)
        g_blk = dot(cum, f32(g_ref, at))        # inclusive, a block at a time
        g_t, k_t, q_t = g_blk.T, f32(k_ref, at).T, f32(q_ref, at).T
        beta = beta_ref[0, 0, :, at].astype(F32)                    # [1, lw]
        rows[t + sub - 1:t + sub, :] = jnp.sum(q_t * k_t, axis=0,
                                               keepdims=True)
        a, n = [None], [jnp.ones((1, lw), F32)]     # A's diagonals inside
        for d in range(1, sub):
            e = jnp.exp(jnp.where(in_sub >= d,
                                  g_t - pltpu.roll(g_t, d, 1), -jnp.inf))
            ke = pltpu.roll(k_t, d, 1) * e
            a.append(beta * jnp.sum(k_t * ke, axis=0, keepdims=True))
            rows[t + sub - 1 - d:t + sub - d, :] = jnp.sum(
                q_t * ke, axis=0, keepdims=True)
            # ... and (I + A)^-1's: the newest diagonal enters last
            acc = a[d]
            for m in range(1, d):
                acc = acc + a[d - m] * pltpu.roll(n[m], d - m, 1)
            n.append(-acc)
        for d, n_d in enumerate(n):
            rows[sub - 1 - d:sub - d, :] = n_d
        diag = rows[...].T                                          # [lw, 2 T]
        beta_col = jnp.broadcast_to(beta, (8, lw)).T[:, :1]         # [lw, 1]
        for j in range(lw // t):
            own = slice(j * t, (j + 1) * t)
            m, at_j = i * (lw // t) + j, pl.ds(
                pl.multiple_of(i * lw + j * t, t), t)
            end = g_t[:, (j + 1) * t - 1:(j + 1) * t]               # [dk, 1]
            kdt_ref[0, 0, m] = k_t[:, own] * jnp.exp(end - g_t[:, own])
            gt = jnp.where(which == m, jnp.exp(end), gt)
            # row t's diagonals: lane (sub - 1 - d) -> lane (t - d)
            dg = pltpu.roll(diag[own], 2 * t - (sub - 1), 1, stride=1,
                            stride_axis=0)
            bm_ref[0, 0, m], ut_ref[0, 0, at_j, :], w_ref[0, 0, at_j, :] = \
                block(at_j, g_blk[own], beta_col[own], dg)
        return gt

    gt_ref[0, 0] = jax.lax.fori_loop(0, nb * t // lw, tile,
                                     jnp.zeros((dk, nb), F32))


@functools.partial(jax.jit, static_argnames=("block", "sub", "interpret"))
def kda_operands(q, k, v, g, beta, *, block: int = BLOCK, sub: int = SUB,
                 interpret: bool):
    """``block_operands`` as ONE kernel call, ``kda_operands``: q, k, v, g
    [B, S, H, dk] and beta [B, S, H], ``S`` whole blocks; a grid step a
    (row, head). The operands are read heads-major, which is how the
    compiler lays the projections' results out in a chunk program (so the
    ``swapaxes`` here is no copy there; [B, S, H dk] is NOT the same tiles:
    a tile of [.., H, dk] holds 8 heads, one of [.., S, H dk] 8 positions),
    and the six arrays are written as ``_scan_blocks_call`` hands them to
    its kernel (its own ``swapaxes`` of ``gt`` undoes the one below).
    Reached through this one cached call, as that one is. Returns
    ``block_operands``' dict."""
    b, c, h, dk = q.shape
    dv = v.shape[-1]
    t, nb = block, c // block
    # positions a transposed walk: whole blocks, 128 lanes where they divide
    lw = max(n * t for n in range(1, nb + 1)
             if nb % n == 0 and n * t <= max(128, t))

    def heads_major(x):     # where the projections' fusions leave them
        return jnp.swapaxes(x, 1, 2)

    def spec(*shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda bi, hi: (bi, hi) + (0,) * len(shape))

    qg, w, ut, bm, kdt, gt = pl.pallas_call(
        functools.partial(_operands_kernel, nb=nb, t=t, sub=sub, lw=lw),
        name="kda_operands",
        grid=(b, h),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dv), spec(c, dk),
                  spec(1, c)],
        out_specs=[spec(c, dk), spec(c, dk), spec(c, dv), spec(nb, t, t),
                   spec(nb, dk, t), spec(dk, nb)],
        out_shape=[jax.ShapeDtypeStruct((b, h) + s, F32) for s in (
            (c, dk), (c, dk), (c, dv), (nb, t, t), (nb, dk, t), (dk, nb))],
        scratch_shapes=[pltpu.VMEM((2 * t, lw), F32)],
        interpret=interpret,
    )(*(heads_major(x) for x in (q, k, v, g)),
      heads_major(beta)[:, :, None, :])

    def blocks(x):          # [B,H,C,n] -> [B,H,nb,T,n]
        return x.reshape(b, h, nb, t, x.shape[-1])

    return {"qg": blocks(qg), "w": blocks(w), "ut": blocks(ut), "bm": bm,
            "kdt": kdt, "gt": jnp.swapaxes(gt, -1, -2)}


def _scan_blocks_xla(ops: dict, state):
    """The state through the blocks of a chunk. Returns (o [B, H, S, dv],
    the state after)."""
    def one(s, blk):
        u = blk["ut"] - _ein("bhtk,bhkv->bhtv", blk["w"], s)
        o = _ein("bhtk,bhkv->bhtv", blk["qg"], s) \
            + _ein("bhtj,bhjv->bhtv", blk["bm"], u)
        s = blk["gt"][..., None] * s + _ein("bhkt,bhtv->bhkv", blk["kdt"], u)
        return s, o

    state, o = jax.lax.scan(
        one, state.astype(F32),
        jax.tree.map(lambda x: jnp.moveaxis(x, 2, 0), ops))
    o = jnp.moveaxis(o, 0, 2)                               # [B,H,nb,T,dv]
    return o.reshape(*o.shape[:2], -1, o.shape[-1]), state


def _scan_blocks_kernel(qg_ref, w_ref, ut_ref, bm_ref, kdt_ref, gt_ref,
                        s_ref, o_ref, so_ref, *, nb: int, t: int):
    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=F32, precision=HIGHEST)

    s = s_ref[0, 0]
    for i in range(nb):
        at = pl.ds(i * t, t)
        u = ut_ref[0, 0, at, :] - dot(w_ref[0, 0, at, :], s)
        o_ref[0, 0, at, :] = dot(qg_ref[0, 0, at, :], s) \
            + dot(bm_ref[0, 0, i], u)
        s = gt_ref[0, 0, :, i:i + 1] * s + dot(kdt_ref[0, 0, i], u)
    so_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_blocks_call(ops: dict, state, *, interpret: bool):
    """``_scan_blocks_xla`` as ONE kernel call: a grid step a (row, head),
    the state in VMEM across the blocks. Reached through this one cached
    call, so a program traces the kernel's body once however many layers and
    rows call it."""
    b, h, nb, t, dk = ops["qg"].shape
    dv = ops["ut"].shape[-1]
    c = nb * t

    def rows(x):            # [B,H,nb,T,n] -> [B,H,C,n]
        return x.reshape(b, h, c, x.shape[-1])

    def spec(*shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda bi, hi: (bi, hi) + (0,) * len(shape))

    o, s = pl.pallas_call(
        functools.partial(_scan_blocks_kernel, nb=nb, t=t),
        name="kda_chunk",
        grid=(b, h),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dv), spec(nb, t, t),
                  spec(nb, dk, t), spec(dk, nb), spec(dk, dv)],
        out_specs=[spec(c, dv), spec(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, c, dv), F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        interpret=interpret,
    )(rows(ops["qg"]), rows(ops["w"]), rows(ops["ut"]), ops["bm"],
      ops["kdt"], jnp.swapaxes(ops["gt"], -1, -2), state.astype(F32))
    return o, s


def whole_blocks(q, k, v, g, beta, block: int = BLOCK, sub: int = SUB):
    """A chunk of any length as whole blocks: a short one is one block (of
    whole sub-blocks), a ragged one is padded with positions that leave the
    state alone (``beta = 0``, ``g = 0``). Returns ((q, k, v, g, beta),
    block, sub)."""
    s = q.shape[1]
    block = min(block, -(-s // sub) * sub)
    pad = -s % block
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    return (q, k, v, g, beta), block, min(sub, block)


def kda_chunk(q, k, v, g, beta, state, *, impl: str = "xla",
              block: int = BLOCK, sub: int = SUB,
              interpret: Optional[bool] = None):
    """A chunk a row, from a state to a state. q, k, v, g [B, S, H, dk]
    (``g`` the log-decay, <= 0); beta [B, S, H]; state [B, H, dk, dv]
    float32; ``S`` any length (padded here to whole blocks with positions
    that leave the state alone). ``impl`` "pallas": the kernel
    ``kda_operands`` computes what does not read the state and the kernel
    ``kda_chunk`` runs the blocks; "xla": ``block_operands`` and a
    ``lax.scan``. Returns (o [B, S, H, dv] float32, the state after)."""
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown kda impl {impl!r}; one of xla|pallas")
    s = q.shape[1]
    (q, k, v, g, beta), block, sub = whole_blocks(q, k, v, g, beta, block,
                                                  sub)
    if impl == "pallas":
        interpret = auto_interpret() if interpret is None else interpret
        o, end = _scan_blocks_call(
            kda_operands(q, k, v, g, beta, block=block, sub=sub,
                         interpret=interpret), state, interpret=interpret)
    else:
        hm = [jnp.swapaxes(x.astype(F32), 1, 2) for x in (q, k, v, g, beta)]
        o, end = _scan_blocks_xla(block_operands(*hm, block, sub), state)
    return jnp.swapaxes(o[:, :, :s], 1, 2), end


# -- one token, the state where it lies in the pool -----------------------------------

def _step_kernel(idx_ref, n_ref, fresh_ref, cols_ref, v_ref, s_ref, so_ref,
                 o_ref, *, heads: int):
    bi = pl.program_id(0)

    @pl.when(bi < n_ref[0])
    def _():
        keep = jnp.where(fresh_ref[bi] > 0, 0.0, 1.0).astype(F32)
        for h in range(heads):
            a, k, kb, q = (cols_ref[0, 0, :, 4 * h + j:4 * h + j + 1]
                           for j in range(4))                       # [dk, 1]
            s1 = s_ref[0, h] * (a * keep)
            u = jnp.sum(s1 * k, axis=0, keepdims=True)              # [1, dv]
            s2 = s1 + kb * (v_ref[0, 0, h:h + 1, :] - u)
            so_ref[0, h] = s2
            o_ref[0, 0, h:h + 1, :] = jnp.sum(s2 * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, plane, idx, fresh, live, *, interpret: bool):
    """``kda_step`` as ONE kernel call over ``plane``, aliased to its first
    result: a grid step a (live row, block of ``STEP_HEADS`` heads), the
    row's state block fetched from and written back to its entry; the four
    vectors that scale a state's ROWS (decay, k, beta k, q) ride transposed,
    [dk, 4 a head], so that each is a column as it lies."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = min(h, STEP_HEADS)
    nh = h // hb
    # Live rows first: the grid walks them and stays on the last one's
    # blocks for the rest (no fetch, no write: the body is skipped).
    order = jnp.argsort(~live, stable=True)
    n_live = jnp.sum(live, dtype=jnp.int32)
    a = jnp.exp(g.astype(F32))
    kf = k.astype(F32)
    cols = jnp.stack([a, kf, beta.astype(F32)[..., None] * kf,
                      q.astype(F32)], axis=-1)[order]           # [B,H,dk,4]
    cols = cols.reshape(b, nh, hb, dk, 4).transpose(0, 1, 3, 2, 4).reshape(
        b, nh, dk, 4 * hb)
    vs = v.astype(F32)[order].reshape(b, nh, hb, dv)

    def at(bi, hi, n_ref):
        dead = bi >= n_ref[0]
        return (jnp.where(dead, jnp.maximum(n_ref[0] - 1, 0), bi),
                jnp.where(dead, nh - 1, hi))

    def row_map(bi, hi, idx_ref, n_ref, fresh_ref):
        return (*at(bi, hi, n_ref), 0, 0)

    def state_map(bi, hi, idx_ref, n_ref, fresh_ref):
        r, hh = at(bi, hi, n_ref)
        return (idx_ref[r], hh, 0, 0)

    call = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        name="kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nh),
            in_specs=[pl.BlockSpec((1, 1, dk, 4 * hb), row_map),
                      pl.BlockSpec((1, 1, hb, dv), row_map),
                      pl.BlockSpec((1, hb, dk, dv), state_map)],
            out_specs=[pl.BlockSpec((1, hb, dk, dv), state_map),
                       pl.BlockSpec((1, 1, hb, dv), row_map)]),
        out_shape=[jax.ShapeDtypeStruct(plane.shape, plane.dtype),
                   jax.ShapeDtypeStruct((b, nh, hb, dv), F32)],
        input_output_aliases={5: 0},
        interpret=interpret,
    )
    idx_s = jnp.clip(idx[order], 0, plane.shape[0] - 1).astype(jnp.int32)
    plane, o = jax.lax.cond(
        n_live > 0,
        lambda pln: tuple(call(idx_s, n_live[None], fresh[order].astype(
            jnp.int32), cols, vs, pln)),
        lambda pln: (pln, jnp.zeros((b, nh, hb, dv), F32)), plane)
    o = jnp.zeros_like(o).at[order].set(o).reshape(b, h, dv)
    return jnp.where(live[:, None, None], o, 0.0), plane


def kda_step(q, k, v, g, beta, plane, idx, fresh, live, *,
             impl: str = "xla", interpret: Optional[bool] = None):
    """One token a row against the state IN the pool. q, k, v, g [B, H, dk];
    beta [B, H]; ``plane`` [N, H, dk, dv] float32 (every entry of every
    layer, flat); ``idx`` [B] the row's entry; ``fresh`` [B]: start from
    zeros (a sequence's first token); ``live`` [B]: a dead row reads and
    writes nothing and gets zeros. ``impl`` "pallas": the kernel
    ``kda_step``, the plane aliased to the result; "xla": gather, the
    recurrence, scatter. Returns (o [B, H, dv] float32, the plane)."""
    if impl == "pallas":
        return _step_call(
            q, k, v, g, beta, plane, idx, fresh, live,
            interpret=auto_interpret() if interpret is None else interpret)
    if impl != "xla":
        raise ValueError(f"unknown kda impl {impl!r}; one of xla|pallas")
    n = plane.shape[0]
    state = plane[jnp.clip(idx, 0, n - 1)]
    state = jnp.where((fresh | ~live)[:, None, None, None], 0.0, state)
    o, state = kda_step_xla(q, k, v, g, beta, state)
    plane = plane.at[jnp.where(live, idx, n)].set(state, mode="drop")
    return jnp.where(live[:, None, None], o, 0.0), plane
