"""Pallas TPU fused blockwise softmax-cross-entropy — the loss path that
never materializes ``[B, S, vocab]`` logits in HBM.

The classic LLM-training memory hog: the output projection emits a
``[B, S, V]`` float32 logits tensor (1.3 GB at the headline
batch-5/seq-2048/vocab-32k shape), log-softmax reads and writes it again,
and the backward rebuilds the whole thing once more. The sequence-chunked
CE (models/decoder.py::_chunked_ce) caps the liveness at ``[B, chunk, V]``
but still round-trips every chunk's logits through HBM.

This kernel removes the tensor entirely, flash-attention style:

- **forward** streams *vocab tiles*: each grid step computes one
  ``[rows, bv]`` logits tile ``hidden @ head[:, tile]`` on the MXU
  (float32 accumulation), folds it into running max / logsumexp / picked-
  target / argmax accumulators in VMEM, and drops the tile. Only the
  per-token ``nll`` (= lse - picked), ``lse`` and ``correct`` leave the
  kernel — O(T) outputs for an O(T·V) computation.
- **backward** is a custom VJP that recomputes tiles from the saved lse
  (exact: ``p = exp(s - lse)``) and contracts them in place — one kernel
  accumulates ``d_hidden`` across the vocab sweep, a second accumulates
  ``d_head`` across the row sweep. ``d_logits`` never exists in HBM
  either.

Gemma-2 style tanh softcap is folded into both passes. ``interpret=``
resolves automatically off-TPU (CPU tests run the same kernels through
the Pallas interpreter), mirroring ops/flash_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import VMEM_BUDGET_BYTES, auto_interpret

# Tile preferences; fitted down to divisors of the actual dims. The row
# block bounds the fp32 accumulators ([rows, 1] stats + [rows, bv] tile);
# the vocab block bounds the resident head slice ([D, bv]).
DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_VOCAB = 512


def _fit_dim(n: int, pref: int, align: int) -> int:
    """Largest divisor of ``n`` <= ``pref`` that is a multiple of
    ``align`` when one exists, else the largest divisor <= pref, else n.
    Static (trace-time) search: n is a model dimension, not data."""
    best = 0
    for cand in range(min(pref, n), 0, -1):
        if n % cand == 0:
            if cand % align == 0:
                return cand
            best = best or cand
    return best or n


def supported(rows: int, hidden: int, vocab: int,
              interpret: Optional[bool] = None, *,
              dtype=jnp.bfloat16, head_dtype=None) -> bool:
    """Whether the fused kernel can serve this (T, D, V) shape with
    ``dtype`` activations and a ``head_dtype`` head (default: the same).
    On real TPU the lane/sublane tiling needs 128-aligned hidden/vocab
    and 8-aligned rows, and some aligned tile has to fit the kernels'
    fast memory (``_fit_vmem``); the interpreter takes anything."""
    interp = interpret if interpret is not None else auto_interpret()
    if interp:
        return True
    if hidden % 128 or vocab % 128 or rows % 8:
        return False
    return _fit_vmem(rows, hidden, vocab, jnp.dtype(dtype).itemsize,
                     jnp.dtype(head_dtype or dtype).itemsize) is not None


def _vmem_bytes(br: int, bv: int, d: int, hb: int, wb: int) -> int:
    """Fast memory the hungriest of the three kernels keeps resident for
    a ``(br, bv)`` tile: in/out blocks double-buffered plus the fp32
    accumulator. ``hb``/``wb``: activation / head bytes per element."""
    h, w = br * d * hb, d * bv * wb
    fwd = 2 * (h + w)
    bwd_dh = 2 * (h + w + h) + br * d * 4          # dh out + fp32 dh_acc
    bwd_dw = 2 * (h + w + w) + d * bv * 4          # dw out + fp32 dw_acc
    return max(fwd, bwd_dh, bwd_dw)


def _fit_vmem(rows: int, d: int, vocab: int, hb: int, wb: int,
              ) -> Optional[tuple[int, int]]:
    """The largest aligned ``(br, bv)`` tile (by area, then by vocab
    width) not over the defaults whose kernels fit ``VMEM_BUDGET_BYTES``;
    None when none does. Static (trace-time) search over divisors."""
    fits = [(br * bv, bv, br)
            for br in range(8, min(DEFAULT_BLOCK_ROWS, rows) + 1, 8)
            if rows % br == 0
            for bv in range(128, min(DEFAULT_BLOCK_VOCAB, vocab) + 1, 128)
            if vocab % bv == 0
            and _vmem_bytes(br, bv, d, hb, wb) <= VMEM_BUDGET_BYTES]
    if not fits:
        return None
    _, bv, br = max(fits)
    return br, bv


def _blocks(rows: int, d: int, vocab: int, hb: int, wb: int,
            block_rows: Optional[int], block_vocab: Optional[int],
            interpret: bool) -> tuple[int, int]:
    """Tile sizes: the caller's, else the largest aligned divisors under
    the defaults — shrunk, when compiling for the chip, until the kernels
    fit fast memory (a float32 head at vocab 256128 wanted 17 MB of the
    16 MB scoped limit in the d_head backward at the default tile)."""
    br = block_rows or _fit_dim(rows, DEFAULT_BLOCK_ROWS, 8)
    bv = block_vocab or _fit_dim(vocab, DEFAULT_BLOCK_VOCAB, 128)
    if (not interpret and block_rows is None and block_vocab is None
            and _vmem_bytes(br, bv, d, hb, wb) > VMEM_BUDGET_BYTES):
        fit = _fit_vmem(rows, d, vocab, hb, wb)
        if fit is None:
            raise ValueError(
                f"no aligned tile of (rows={rows}, hidden={d}, "
                f"vocab={vocab}) fits the kernels' fast memory; check "
                "fused_xent.supported() first")
        br, bv = fit
    if rows % br or vocab % bv:
        raise ValueError(
            f"block sizes ({br}, {bv}) must divide (rows={rows}, "
            f"vocab={vocab})")
    return br, bv


def _capped(s: jax.Array, softcap: Optional[float]) -> jax.Array:
    return jnp.tanh(s / softcap) * softcap if softcap is not None else s


def _fwd_kernel(h_ref, w_ref, t_ref, nll_ref, lse_ref, corr_ref,
                m_ref, l_ref, picked_ref, bestv_ref, besti_ref, *,
                softcap: Optional[float], block_vocab: int,
                num_vocab_blocks: int, vocab: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        picked_ref[:] = jnp.zeros_like(picked_ref)
        bestv_ref[:] = jnp.full_like(bestv_ref, -jnp.inf)
        besti_ref[:] = jnp.zeros_like(besti_ref)

    h = h_ref[...]                                   # [br, D] native dtype
    w = w_ref[...]                                   # [D, bv]
    s = _capped(jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), softcap)  # [br, bv] fp32

    br = s.shape[0]
    cols = vi * block_vocab + jax.lax.broadcasted_iota(
        jnp.int32, (br, block_vocab), 1)
    tgt = t_ref[...]                                 # [br, 1] int32
    picked_ref[:] += jnp.sum(jnp.where(cols == tgt, s, 0.0),
                             axis=1, keepdims=True)

    m_prev = m_ref[:]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    l_ref[:] = l_ref[:] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True)
    m_ref[:] = m_new

    # Running argmax without an argmax lowering: min column index holding
    # the tile max; strict > across tiles keeps the earliest tie, matching
    # jnp.argmax's first-occurrence rule globally.
    tile_arg = jnp.min(jnp.where(s >= m_cur, cols, vocab),
                       axis=1, keepdims=True)
    upd = m_cur > bestv_ref[:]
    besti_ref[:] = jnp.where(upd, tile_arg, besti_ref[:])
    bestv_ref[:] = jnp.where(upd, m_cur, bestv_ref[:])

    @pl.when(vi == num_vocab_blocks - 1)
    def _finalize():
        lse = m_ref[:] + jnp.log(l_ref[:])
        lse_ref[...] = lse
        nll_ref[...] = lse - picked_ref[:]
        corr_ref[...] = (besti_ref[:] == t_ref[...]).astype(jnp.float32)


def _xent_fwd(h, w, t, softcap, br, bv, interpret):
    rows, d = h.shape
    vocab = w.shape[1]
    nt, nv = rows // br, vocab // bv
    kernel = functools.partial(
        _fwd_kernel, softcap=softcap, block_vocab=bv, num_vocab_blocks=nv,
        vocab=vocab)
    return pl.pallas_call(
        kernel,
        name="fused_xent_fwd",
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((br, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, bv), lambda ti, vi: (0, vi)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=(
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),    # running max
            pltpu.VMEM((br, 1), jnp.float32),    # running sumexp
            pltpu.VMEM((br, 1), jnp.float32),    # picked target logit
            pltpu.VMEM((br, 1), jnp.float32),    # best value (argmax)
            pltpu.VMEM((br, 1), jnp.int32),      # best index (argmax)
        ],
        out_shape=(
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),   # nll
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),   # lse
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),   # correct
        ),
        interpret=interpret,
    )(h, w, t)


def _dlogits(h, w, tgt, lse, g, cols, softcap):
    """One recomputed ``[br, bv]`` tile of d_logits (fp32): the softmax-CE
    gradient ``(p - onehot) * g`` chained through the optional softcap."""
    raw = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    s = _capped(raw, softcap)
    p = jnp.exp(s - lse)
    dl = (p - jnp.where(cols == tgt, 1.0, 0.0)) * g
    if softcap is not None:
        dl = dl * (1.0 - (s / softcap) ** 2)
    return dl


def _bwd_dh_kernel(h_ref, w_ref, t_ref, lse_ref, g_ref, dh_ref, dh_acc, *,
                   softcap: Optional[float], block_vocab: int,
                   num_vocab_blocks: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    h = h_ref[...]
    w = w_ref[...]                                   # [D, bv]
    br = h.shape[0]
    cols = vi * block_vocab + jax.lax.broadcasted_iota(
        jnp.int32, (br, block_vocab), 1)
    dl = _dlogits(h, w, t_ref[...], lse_ref[...], g_ref[...], cols, softcap)
    dh_acc[:] += jax.lax.dot_general(
        dl.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [br, D]

    @pl.when(vi == num_vocab_blocks - 1)
    def _flush():
        dh_ref[...] = dh_acc[:].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, t_ref, lse_ref, g_ref, dw_ref, dw_acc, *,
                   softcap: Optional[float], block_vocab: int,
                   num_row_blocks: int):
    vi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    h = h_ref[...]
    w = w_ref[...]
    br = h.shape[0]
    cols = vi * block_vocab + jax.lax.broadcasted_iota(
        jnp.int32, (br, block_vocab), 1)
    dl = _dlogits(h, w, t_ref[...], lse_ref[...], g_ref[...], cols, softcap)
    dw_acc[:] += jax.lax.dot_general(
        h, dl.astype(h.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [D, bv]

    @pl.when(ti == num_row_blocks - 1)
    def _flush():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)


def _xent_bwd(h, w, t, lse, g, softcap, br, bv, interpret):
    rows, d = h.shape
    vocab = w.shape[1]
    nt, nv = rows // br, vocab // bv

    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, softcap=softcap, block_vocab=bv,
                          num_vocab_blocks=nv),
        name="fused_xent_bwd_dh",
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((br, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, bv), lambda ti, vi: (0, vi)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((br, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda ti, vi: (ti, 0)),
        scratch_shapes=[pltpu.VMEM((br, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((rows, d), h.dtype),
        interpret=interpret,
    )(h, w, t, lse, g)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, softcap=softcap, block_vocab=bv,
                          num_row_blocks=nt),
        name="fused_xent_bwd_dw",
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((br, d), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((d, bv), lambda vi, ti: (0, vi)),
            pl.BlockSpec((br, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((br, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((br, 1), lambda vi, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((d, bv), lambda vi, ti: (0, vi)),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((d, vocab), w.dtype),
        interpret=interpret,
    )(h, w, t, lse, g)
    return dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_ce(h, w, t, softcap, br, bv, interpret):
    nll, _, correct = _xent_fwd(h, w, t, softcap, br, bv, interpret)
    return nll, correct


def _fused_ce_vjp_fwd(h, w, t, softcap, br, bv, interpret):
    nll, lse, correct = _xent_fwd(h, w, t, softcap, br, bv, interpret)
    return (nll, correct), (h, w, t, lse)


def _fused_ce_vjp_bwd(softcap, br, bv, interpret, res, cts):
    h, w, t, lse = res
    dnll, _ = cts     # `correct` is argmax-derived: no gradient
    dh, dw = _xent_bwd(h, w, t, lse, dnll, softcap, br, bv, interpret)
    # Integer targets carry no cotangent (float0 is jax's "no tangent
    # space" dtype for int primals).
    return dh, dw, np.zeros(t.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_vjp_fwd, _fused_ce_vjp_bwd)


def fused_cross_entropy(
    hidden: jax.Array,                # [..., D] (typically [B, S, D])
    head: jax.Array,                  # [D, V]
    targets: jax.Array,               # [...] int32, same leading shape
    *,
    logits_softcap: Optional[float] = None,
    block_rows: Optional[int] = None,
    block_vocab: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused output-projection + log-softmax + NLL. Returns
    ``(nll, correct)`` — both float32 with ``targets``' shape — without
    ever materializing the ``[..., V]`` logits. Differentiable in
    ``hidden`` and ``head`` (custom VJP recomputes tiles blockwise and
    emits d_hidden/d_head directly); ``correct`` (argmax == target) has
    no gradient."""
    d = hidden.shape[-1]
    if head.shape[0] != d:
        raise ValueError(f"head {head.shape} does not match hidden dim {d}")
    h2 = hidden.reshape(-1, d)
    t2 = targets.reshape(-1, 1).astype(jnp.int32)
    rows, vocab = h2.shape[0], head.shape[1]
    interp = interpret if interpret is not None else auto_interpret()
    br, bv = _blocks(rows, d, vocab, h2.dtype.itemsize, head.dtype.itemsize,
                     block_rows, block_vocab, interp)
    nll, correct = _fused_ce(h2, head, t2, logits_softcap, br, bv, interp)
    return (nll.reshape(targets.shape), correct.reshape(targets.shape))


def reference_cross_entropy(hidden, head, targets, *, logits_softcap=None):
    """The unfused oracle (materializes logits): numerics the kernel is
    pinned against in tests."""
    logits = jnp.einsum("td,dv->tv", hidden.reshape(-1, hidden.shape[-1]),
                        head, preferred_element_type=jnp.float32)
    if logits_softcap is not None:
        logits = jnp.tanh(logits / logits_softcap) * logits_softcap
    t2 = targets.reshape(-1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, t2[:, None], axis=-1)[..., 0]
    correct = (logits.argmax(-1) == t2).astype(jnp.float32)
    return ((logz - picked).reshape(targets.shape),
            correct.reshape(targets.shape))
