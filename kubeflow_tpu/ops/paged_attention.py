"""Pallas TPU paged-attention decode kernel.

The paged engine's XLA path reads KV twice per step: a gather materializes
each slot's pages into the [B, S, K, D] layout, then attention reads the
gathered buffer — 2× the HBM traffic of the contiguous cache (serve/paged.py
module notes). This kernel reads pages DIRECTLY: the page table rides in as
a scalar-prefetch operand and the kv BlockSpec index map looks the page id
up per grid step, so each page is DMA'd from the pool exactly once and the
online softmax accumulates across pages in VMEM — the TPU form of vLLM's
PagedAttention (same role as the public jax pallas paged kernels; written
against this repo's pool/table layout and GQA grouping).

Grid (batch, page), page innermost so the m/l/acc scratch carries across a
slot's pages. Each step loads one FULL page ``[page, K, D]`` (Mosaic needs
the block's trailing dims tile-aligned, so the kv-head dim stays whole) and
computes every query head against it: GQA grouping happens in-register via
a K-batched dot ([K, g, D] x [K, page, D] -> [K, g, page]). Unmapped (-1)
and beyond-length pages are predicated off with ``pl.when`` (their index map
clamps to page 0 — the DMA is wasted but never read).

The pool operand is whatever ``[P, page, K, D]`` array the table's ids index.
The decode step (serve/paged.py) hands in the WHOLE pool viewed flat
``[L*P, page, K, D]`` with the layer's table offset by ``l*P``, so no
per-layer slab is ever sliced out for the kernel: blocks are DMA'd from
where the pages lie.

int8 pools (``kv_cache_dtype="int8"``) ride the same grid with two extra
per-page operands: the per-token-per-head scale planes ``[P, page, K]``
(f32, ops/quantization.quantize_kv layout). The kernel dequantizes in
VMEM — ``k_f32 = k_int8 * ks[..., None]`` — right before the QK/PV dots,
so the HBM read per decode step is the int8 page plus a 4/Dh-sized scale
row instead of a full-dtype page: the capacity win and the bandwidth win
come from the same bytes."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import auto_interpret
from kubeflow_tpu.ops.attention import NEG_INF


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            page_size: int, sm_scale: float, num_pages_per_slot: int,
            num_kv_heads: int, group: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    h = num_kv_heads * group
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = len_ref[b]                 # position being decoded (inclusive)
    needed = jnp.logical_and(j * page_size <= length, table_ref[b, j] >= 0)

    @pl.when(needed)
    def _compute():
        qg = q_ref[0, 0].astype(jnp.float32).reshape(
            num_kv_heads, group, d)                  # [K, g, d]
        k = k_ref[0].astype(jnp.float32)             # [pg, K, d]
        if quantized:
            # int8 page → f32 operand in VMEM: per-token-per-head scale
            # broadcast over head_dim (quantize_kv's axis=-1 layout).
            k = k * ks_ref[0][:, :, None]            # [pg, K, 1]
        kt = jnp.swapaxes(k, 0, 1)                   # [K, pg, d]
        s = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale   # [K, g, pg]
        s = s.reshape(h, page_size)
        kv_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        s = jnp.where(kv_pos <= length, s, NEG_INF)

        m_prev = m_ref[:]                            # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [h, pg]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)             # [pg, K, d]
        if quantized:
            v = v * vs_ref[0][:, :, None]
        vt = jnp.swapaxes(v, 0, 1)                   # [K, pg, d]
        pv = jax.lax.dot_general(
            p.reshape(num_kv_heads, group, page_size), vt,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # [K, g, d]
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(h, d)
        m_ref[:] = m_new

    @pl.when(j == num_pages_per_slot - 1)
    def _finalize():
        # Dead rows (live=False upstream: length masks everything) keep
        # l == 0: emit zeros, the host discards them anyway.
        l = l_ref[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,                 # [B, 1, H, D] — one decode token per slot
    pool_k: jax.Array,            # [P, page, K, D]
    pool_v: jax.Array,            # [P, page, K, D]
    table: jax.Array,             # [B, mpp] int32 page ids (-1 = unmapped)
    lengths: jax.Array,           # [B] position being decoded (attend <=)
    *,
    pool_ks: Optional[jax.Array] = None,   # [P, page, K] f32 (int8 pools)
    pool_vs: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact decode attention over the page pool; returns [B, 1, H, D].

    If ``pool_ks``/``pool_vs`` are given, ``pool_k``/``pool_v`` hold int8
    pages and the kernel dequantizes in VMEM (per-token-per-head scales)."""
    b, one, h, d = q.shape
    if one != 1:
        raise ValueError("paged decode attention takes one token per slot")
    p_total, page, kh, _ = pool_k.shape
    if h % kh:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kh}")
    if (pool_ks is None) != (pool_vs is None):
        raise ValueError("pool_ks and pool_vs must be given together")
    quantized = pool_ks is not None
    g = h // kh
    mpp = table.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5

    kernel = functools.partial(
        _kernel, page_size=page, sm_scale=scale, num_pages_per_slot=mpp,
        num_kv_heads=kh, group=g, quantized=quantized)

    def q_map(bi, ji, table_ref, len_ref):
        return (bi, 0, 0, 0)

    def kv_map(bi, ji, table_ref, len_ref):
        # Unmapped pages clamp to page 0: the DMA happens but the compute
        # predicate never reads it.
        return (jnp.maximum(table_ref[bi, ji], 0), 0, 0, 0)

    def scale_map(bi, ji, table_ref, len_ref):
        return (jnp.maximum(table_ref[bi, ji], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, h, d), q_map),
        pl.BlockSpec((1, page, kh, d), kv_map),
        pl.BlockSpec((1, page, kh, d), kv_map),
    ]
    operands = [q, pool_k, pool_v]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page, kh), scale_map),
            pl.BlockSpec((1, page, kh), scale_map),
        ]
        operands += [pool_ks.astype(jnp.float32),
                     pool_vs.astype(jnp.float32)]

    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, mpp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, h, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),   # running max m
                pltpu.VMEM((h, 1), jnp.float32),   # running denom l
                pltpu.VMEM((h, d), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        interpret=interpret if interpret is not None else auto_interpret(),
    )(table, lengths, *operands)
    return out
