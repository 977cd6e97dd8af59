"""Pallas TPU flash attention — blockwise online-softmax kernel.

The data-plane hot op (SURVEY.md §2.6: the reference orchestrates frameworks
that bring their own fused attention; TPU-natively the kernel is ours).
Design per the Pallas TPU guide: grid (batch, q_head, q_block, kv_block) with
the kv dimension innermost so VMEM scratch accumulators (m, l, acc) carry
across kv steps; causal blocks fully above the diagonal are skipped with
``pl.when``; logits accumulate on the MXU in float32
(``preferred_element_type``); GQA maps q-head → kv-head in the BlockSpec
index maps so each kv block is DMA'd once per group.

Backward runs as a custom VJP that recomputes attention blockwise per kv
block (flash-style: O(S) memory, no S×S materialization) using the same
kernel family.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import auto_interpret
from kubeflow_tpu.ops.attention import NEG_INF

# Tuned on v5e at B=4/H=32/KH=8/S=2048/d=64 (the headline train shape):
# the kernel is grid-overhead-bound at this size — (128, 128) blocks mean
# 32k grid steps and lose to XLA's fused S×S path; (1024, 1024) cuts the
# grid 64× and wins (isolated: fwd 15.0 vs 17.3 ms, recompute-train 22.9
# vs 39.8 ms; full train step 349 vs 486 ms). Shapes the defaults don't
# divide fall back to the largest 128-aligned divisor (_fit_block); lengths
# >= 128 with no 128-aligned divisor raise rather than reach Mosaic with a
# tile-misaligned block.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_KV = 1024


def _fit_block(pref: int, s: int) -> int:
    """Largest power-of-two block <= pref that divides s, not going below
    the 128-lane tile (a sub-128 block would violate Mosaic tiling and
    explode the grid). s < 128 uses s itself when it divides."""
    b = min(pref, s)
    while b >= 128 and (s % b or b % 128):
        b //= 2
    if s % b or (s >= 128 and b % 128):
        # Covers both the no-divisor case and s in [128, 1024) that is not
        # itself 128-aligned (e.g. 136): such an s used to slip through as a
        # single full-size block and die inside Mosaic lowering with an
        # opaque tile-misalignment error.
        raise ValueError(
            f"no default block size >= 128 divides sequence length {s}; "
            "pass block_q/block_kv explicitly")
    return b


def _one_block(pref: Optional[int], s: int, name: str) -> int:
    if pref is None:
        return _fit_block(DEFAULT_BLOCK_Q if name == "q" else
                          DEFAULT_BLOCK_KV, s)
    b = min(pref, s)
    if s % b:
        raise ValueError(
            f"{name} seq length {s} must be a multiple of block size {b}")
    return b


def _block_sizes(sq: int, skv: int, bq: Optional[int], bkv: Optional[int]):
    return _one_block(bq, sq, "q"), _one_block(bkv, skv, "kv")


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *,
                causal: bool, sm_scale: float, softcap: Optional[float],
                q_offset: int, block_q: int, block_kv: int,
                num_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + qi * block_q + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    kv_pos = ki * block_kv + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    # Causal skip: the whole kv block is in the future of every q position.
    block_needed = jnp.logical_or(
        jnp.logical_not(causal),
        ki * block_kv <= q_offset + (qi + 1) * block_q - 1)

    @pl.when(block_needed)
    def _compute():
        # Dot inputs stay in the NATIVE dtype (bf16): the MXU runs bf16
        # inputs with fp32 accumulation at full rate — upcasting first
        # quarters the matmul throughput (measured: the fp32-input kernel
        # lost to XLA at S=2048). Softmax statistics stay fp32.
        q = q_ref[0, 0]                              # [bq, d]
        k = k_ref[0, 0]                              # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[:]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bkv] fp32
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]                              # [bkv, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new
        l_ref[:] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        # Fully-masked rows (decode padding) have l == 0: emit zeros.
        l = l_ref[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe).astype(o_ref.dtype)
        # Log-sum-exp per row: the softmax stats the backward needs (saving
        # it here is what makes the VJP a single sweep).
        lse_ref[0, 0] = m_ref[:] + jnp.log(safe)


def _flash_fwd(q, k, v, *, causal, sm_scale, softcap, q_offset,
               block_q, block_kv, interpret):
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    n_rep = h // kh
    bq, bkv = _block_sizes(sq, skv, block_q, block_kv)
    nq, nkv = sq // bq, skv // bkv

    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale, softcap=softcap,
        q_offset=q_offset, block_q=bq, block_kv=bkv, num_kv_blocks=nkv)

    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # Trailing singleton keeps the (sublane, lane) tiling legal:
            # (bq, 1) with last dim == full array dim.
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        out_shape=(
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ),
        interpret=interpret if interpret is not None else auto_interpret(),
    )(q, k, v)
    return o, lse[..., 0]


def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *,
                     causal: bool, sm_scale: float, softcap: Optional[float],
                     q_offset: int, block_q: int, block_kv: int,
                     num_q_blocks: int, num_groups: int):
    """dK/dV: grid (batch, kv_head, kv_block, group, q_block) — the q sweep
    is innermost so the [bkv, d] accumulators carry across every query block
    (and every GQA group head) that attends to this kv block."""
    ki = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Causal skip: no query in this block sits at-or-after the kv block.
    block_needed = jnp.logical_or(
        jnp.logical_not(causal),
        q_offset + (qi + 1) * block_q - 1 >= ki * block_kv)

    @pl.when(block_needed)
    def _compute():
        # Native-dtype (bf16) dot inputs, fp32 accumulation — see _fwd_kernel.
        q = q_ref[0, 0]                              # [bq, d]
        k = k_ref[0, 0]                              # [bkv, d]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                          # [bq, 1]
        delta = delta_ref[0, 0]                      # [bq, 1]
        s_raw = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.tanh(s_raw / softcap) * softcap if softcap is not None else s_raw
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                         # exact: saved normalizer
        # Fully-masked rows have lse == NEG_INF: exp(0) would be 1.
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bkv, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, bkv]
        ds = p * (dp - delta)
        if softcap is not None:
            ds = ds * (1.0 - jnp.tanh(s_raw / softcap) ** 2)
        ds = ds * sm_scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bkv, d]

    @pl.when((gi == num_groups - 1) & (qi == num_q_blocks - 1))
    def _flush():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dq_ref, dq_acc, *,
                   causal: bool, sm_scale: float, softcap: Optional[float],
                   q_offset: int, block_q: int, block_kv: int,
                   num_kv_blocks: int):
    """dQ: grid (batch, q_head, q_block, kv_block) — kv innermost so the
    [bq, d] accumulator carries across the kv sweep, mirroring the forward."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    block_needed = jnp.logical_or(
        jnp.logical_not(causal),
        ki * block_kv <= q_offset + (qi + 1) * block_q - 1)

    @pl.when(block_needed)
    def _compute():
        # Native-dtype (bf16) dot inputs, fp32 accumulation — see _fwd_kernel.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s_raw = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.tanh(s_raw / softcap) * softcap if softcap is not None else s_raw
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if softcap is not None:
            ds = ds * (1.0 - jnp.tanh(s_raw / softcap) ** 2)
        ds = ds * sm_scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, d]

    @pl.when(ki == num_kv_blocks - 1)
    def _flush():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, *, causal, sm_scale, softcap,
                      q_offset, block_q, block_kv, interpret):
    """Pallas flash backward: recompute attention blockwise from the saved
    LSE (never materializing S×S), accumulating dK/dV per kv block and dQ
    per q block in VMEM. K/V gradients stay at their GQA size."""
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    n_rep = h // kh
    bq, bkv = _block_sizes(sq, skv, block_q, block_kv)
    nq, nkv = sq // bq, skv // bkv
    interp = interpret if interpret is not None else auto_interpret()

    # Rowsum(dO · O): the softmax-backward correction term, cheap in XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)           # [B,H,Sq,1]
    lse4 = lse[..., None]                             # [B,H,Sq,1]

    dkdv = functools.partial(
        _bwd_dkdv_kernel, causal=causal, sm_scale=sm_scale, softcap=softcap,
        q_offset=q_offset, block_q=bq, block_kv=bkv,
        num_q_blocks=nq, num_groups=n_rep)
    dk, dv = pl.pallas_call(
        dkdv,
        name="flash_attention_bwd_dkdv",
        grid=(b, kh, nkv, n_rep, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, khi, ki, gi, qi, n_rep=n_rep:
                         (bi, khi * n_rep + gi, qi, 0)),   # q
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, khi, ki, gi, qi, n_rep=n_rep:
                         (bi, khi * n_rep + gi, qi, 0)),   # do
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, khi, ki, gi, qi, n_rep=n_rep:
                         (bi, khi * n_rep + gi, qi, 0)),   # lse
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, khi, ki, gi, qi, n_rep=n_rep:
                         (bi, khi * n_rep + gi, qi, 0)),   # delta
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, khi, ki, gi, qi: (bi, khi, ki, 0)),  # k
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, khi, ki, gi, qi: (bi, khi, ki, 0)),  # v
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, khi, ki, gi, qi: (bi, khi, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, khi, ki, gi, qi: (bi, khi, ki, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((b, kh, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, kh, skv, d), v.dtype),
        ),
        interpret=interp,
    )(q, do, lse4, delta, k, v)

    dqk = functools.partial(
        _bwd_dq_kernel, causal=causal, sm_scale=sm_scale, softcap=softcap,
        q_offset=q_offset, block_q=bq, block_kv=bkv, num_kv_blocks=nkv)
    dq = pl.pallas_call(
        dqk,
        name="flash_attention_bwd_dq",
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),        # q
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),        # do
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),        # lse
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),        # delta
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),                      # k
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, n_rep=n_rep:
                         (bi, hi // n_rep, ki, 0)),                      # v
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interp,
    )(q, do, lse4, delta, k, v)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, sm_scale, softcap, q_offset, block_q, block_kv,
           interpret, bwd_impl):
    o, _ = _flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                      softcap=softcap, q_offset=q_offset, block_q=block_q,
                      block_kv=block_kv, interpret=interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, sm_scale, softcap, q_offset, block_q,
                   block_kv, interpret, bwd_impl):
    o, lse = _flash_fwd(q, k, v, causal=causal, sm_scale=sm_scale,
                        softcap=softcap, q_offset=q_offset, block_q=block_q,
                        block_kv=block_kv, interpret=interpret)
    # Named so a remat policy can SAVE the kernel outputs: under
    # dots_no_batch a pallas_call is neither a dot nor named, so the
    # backward replays the whole forward kernel just to rebuild these
    # residuals. "dots_flash" (models/decoder.py::_remat) saves them and
    # the replayed kernel DCEs away — measured on-chip (headline config,
    # seq2048, one session): +2.4% at per-chip batch 5 (24,072 -> 24,640
    # tok/s/chip) and +2.6% at batch 4; at batch 6 the extra [B,H,S,D]
    # per layer tips HBM pressure and dots_no_batch wins instead.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, sm_scale, softcap, q_offset, block_q, block_kv,
                   interpret, bwd_impl, res, do):
    """Backward dispatch: ``bwd_impl="pallas"`` runs the blockwise Pallas
    kernels (dK/dV + dQ, no S×S materialization — the training hot path);
    ``"xla"`` keeps the einsum/scan sweep as oracle and fallback."""
    q, k, v, o, lse = res
    if bwd_impl == "pallas":
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, sm_scale=sm_scale,
            softcap=softcap, q_offset=q_offset, block_q=block_q,
            block_kv=block_kv, interpret=interpret)
        return dq, dk, dv
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    n_rep = h // kh
    g = n_rep
    qg = q.astype(jnp.float32).reshape(b, kh, g, sq, d)
    dog = do.astype(jnp.float32).reshape(b, kh, g, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lse_g = lse.reshape(b, kh, g, sq)
    delta_g = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                      axis=-1).reshape(b, kh, g, sq)      # rowsum(dO·O)
    _, bkv = _block_sizes(sq, skv, block_q, block_kv)
    nkv = skv // bkv
    q_pos = (jnp.arange(sq) + q_offset)[:, None]

    def grad_step(dq_acc, ki):
        kb = jax.lax.dynamic_slice_in_dim(kf, ki * bkv, bkv, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vf, ki * bkv, bkv, axis=2)
        s_raw = jnp.einsum("bkgqd,bkmd->bkgqm", qg, kb,
                           preferred_element_type=jnp.float32) * sm_scale
        s = s_raw
        if softcap is not None:
            s = jnp.tanh(s_raw / softcap) * softcap
        if causal:
            kv_pos = (ki * bkv + jnp.arange(bkv))[None, :]
            s = jnp.where((kv_pos <= q_pos)[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse_g[..., None])   # exact: kernel-saved normalizer
        # Fully-masked rows have lse == NEG_INF too: exp(0) would be 1.
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dv_b = jnp.einsum("bkgqm,bkgqd->bkmd", p, dog)
        dp = jnp.einsum("bkgqd,bkmd->bkgqm", dog, vb)
        ds = p * (dp - delta_g[..., None])
        if softcap is not None:
            ds = ds * (1.0 - jnp.tanh(s_raw / softcap) ** 2)
        ds = ds * sm_scale
        dq_acc = dq_acc + jnp.einsum("bkgqm,bkmd->bkgqd", ds, kb)
        dk_b = jnp.einsum("bkgqm,bkgqd->bkmd", ds, qg)
        return dq_acc, (dk_b, dv_b)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        grad_step, jnp.zeros_like(qg), jnp.arange(nkv))
    dq = dq.reshape(b, h, sq, d)
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, kh, skv, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, kh, skv, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh, *,
    causal: bool = True, logits_softcap: Optional[float] = None,
) -> Optional[jax.Array]:
    """Flash attention under a multi-device GSPMD mesh.

    Mosaic kernels cannot be auto-partitioned by GSPMD (XLA raises at
    lowering — caught by the 8B AOT validation, scripts/aot_validate_8b.py),
    so the kernel runs inside a shard_map over the batch (dcn/data/fsdp)
    and head (model) axes. Attention is block-diagonal over batch AND heads
    — every shard computes its slice independently, no collectives, and the
    custom VJP differentiates per-shard exactly (no replicated operands, so
    no psum-transpose corrections are needed). Sequence-sharded meshes
    belong to ring/Ulysses attention, not here.

    Returns None when the shape doesn't shard cleanly (caller falls back to
    the XLA path): batch not divisible by the data degree, q/kv heads not
    divisible by the model degree, or a seq-sharded mesh."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    batch_axes = tuple(a for a in ("dcn", "data", "fsdp")
                       if shape.get(a, 1) > 1)
    bdeg = 1
    for a in batch_axes:
        bdeg *= shape[a]
    tp = shape.get("model", 1)
    b, _, h, _ = q.shape
    kh = k.shape[2]
    if (shape.get("seq", 1) > 1 or b % bdeg
            or (tp > 1 and (h % tp or kh % tp))):
        return None
    bspec = batch_axes if batch_axes else None
    model_ax = "model" if tp > 1 else None
    spec = P(bspec, None, model_ax, None)
    fn = jax.shard_map(
        _ft.partial(flash_attention, causal=causal,
                    logits_softcap=logits_softcap),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def flash_sharded_or_xla(q, k, v, mesh, *, causal: bool = True,
                         logits_softcap: Optional[float] = None):
    """Flash per-shard under a multi-device mesh, XLA attention when the
    shape doesn't shard cleanly — the one fallback rule shared by the
    training no-cache path and the serving prefill path (layers.py)."""
    out = flash_attention_sharded(q, k, v, mesh, causal=causal,
                                  logits_softcap=logits_softcap)
    if out is None:
        from kubeflow_tpu.ops.attention import multi_head_attention

        out = multi_head_attention(q, k, v, causal=causal,
                                   logits_softcap=logits_softcap,
                                   impl="xla")
    return out


def flash_attention(
    q: jax.Array,                     # [B, Sq, H, D]
    k: jax.Array,                     # [B, Skv, K, D]
    v: jax.Array,                     # [B, Skv, K, D]
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    logits_softcap: Optional[float] = None,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
    bwd_impl: str = "pallas",
) -> jax.Array:
    """Flash attention with GQA; layout-compatible with ops.attention
    (returns [B, Sq, H, D]). ``q_offset`` must be a static int here (the
    prefill path); traced-offset decode goes through the XLA impl, which is
    the right tool for single-token queries anyway. ``bwd_impl`` picks the
    gradient path: "pallas" blockwise kernels (default), "xla" oracle."""
    if isinstance(q_offset, jax.Array):
        raise ValueError(
            "flash_attention needs a static q_offset; use impl='xla' for "
            "decode with a traced cache offset")
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    # [B,S,H,D] -> [B,H,S,D] (contiguous per-head blocks for the kernel)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _flash(qt, kt, vt, causal, scale, logits_softcap,
               int(q_offset), block_q, block_kv, interpret, bwd_impl)
    return jnp.swapaxes(o, 1, 2)
