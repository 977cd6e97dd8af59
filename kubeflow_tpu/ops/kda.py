"""Gated delta-rule linear attention (Kimi Delta Attention) with a decay a
CHANNEL: the operator of a "linear" layer (models/layers.py::kda_block), in
a plain XLA form and as two Pallas TPU kernels.

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
every block at once (``block_operands``), and what runs block after block is
four matrix products (``_scan_blocks_xla``; on a TPU the kernel
``kda_chunk``, which keeps ``S`` in VMEM across the blocks of a chunk).

**Decays a channel make the usual ``k / e^G`` overflow** (a channel may lose
a factor e^30 a token). Every factor formed here is ``e^(G_i - G_j)`` with
``i >= j``, at most 1: inside a SUB-block of ``sub`` positions from the
differences themselves ([sub, sub, dk] of them), across sub-blocks of one
block through a reference between the two, ``e^(G_t - R) e^(R - G_j)`` with
``R`` the cumulative decay at the end of the sub-block before ``t``'s, both
factors at most 1 (a product that underflows is a term that is zero in
float32 anyway). ``(I + A)^-1`` is forward substitution: row by row inside a
sub-block's diagonal block, sub-block by sub-block across them.

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


def kda_chunk(q, k, v, g, beta, state, *, impl: str = "xla",
              block: int = BLOCK, sub: int = SUB,
              interpret: Optional[bool] = None):
    """A chunk a row, from a state to a state. q, k, v, g [B, S, H, dk]
    (``g`` the log-decay, <= 0); beta [B, S, H]; state [B, H, dk, dv]
    float32; ``S`` any length (padded here to whole blocks with positions
    that leave the state alone). ``impl`` "xla" | "pallas" (the kernel
    ``kda_chunk`` runs the blocks; what does not read the state is XLA's in
    both). Returns (o [B, S, H, dv] float32, the state after)."""
    s = q.shape[1]
    block = min(block, -(-s // sub) * sub)      # a short chunk: one block
    sub = min(sub, block)
    pad = -s % block
    hm = [jnp.swapaxes(x.astype(F32), 1, 2) for x in (q, k, v, g)]
    bh = jnp.swapaxes(beta.astype(F32), 1, 2)
    if pad:
        hm = [jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in hm]
        bh = jnp.pad(bh, ((0, 0), (0, 0), (0, pad)))
    ops = block_operands(*hm, bh, block, sub)
    if impl == "pallas":
        o, end = _scan_blocks_call(
            ops, state,
            interpret=auto_interpret() if interpret is None else interpret)
    elif impl == "xla":
        o, end = _scan_blocks_xla(ops, state)
    else:
        raise ValueError(f"unknown kda impl {impl!r}; one of xla|pallas")
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
