"""Sequence/context parallelism: ring attention + Ulysses head-swap.

The reference platform has NO long-context support — sequence length is the
workload's problem (SURVEY.md §5 'Long-context / sequence parallelism:
absent'). Here it is first-class (§2.6 rows SP/CP/ring/Ulysses):

- **Ring attention** (`ring_attention`): Q/K/V sharded on the sequence dim
  over the ``seq`` mesh axis; each step computes blockwise attention against
  the resident KV shard while `lax.ppermute` rotates KV around the ICI ring,
  accumulating the exact softmax online (m/l/acc rescaling — the blockwise
  attention recurrence). XLA overlaps the ppermute with the block compute;
  memory per chip stays O(S/n · S/n) per step instead of O(S²).
- **Ulysses** (`ulysses_attention`): `lax.all_to_all` swaps the sequence
  sharding for a head sharding, runs ordinary full attention locally (any
  impl, incl. the Pallas flash kernel), and swaps back — cheaper at moderate
  S when heads ≥ ring size.

The ring's per-step block math has two impls: ``impl="pallas"`` runs the
tuned flash kernels per KV shard (the S=2048-headline retune — bf16 MXU
inputs, fp32 softmax stats — applied at ring scale, where long-context
actually lives) under a hand-written custom_vjp whose backward is a second
ring rotating dK/dV accumulators with the KV shards; ``impl="xla"`` keeps
the einsum/scan online-softmax as the anywhere-runnable numerics oracle.
The traced ring offset never reaches a kernel: for causal attention the
(q_shard, kv_shard) relation is one of three STATIC cases — fully visible
(past shards), the causal diagonal, fully masked (future) — picked by
``lax.switch``, so each branch calls the kernel with a static causal flag
and q_offset=0, and the masked branch skips the matmul entirely.

Both schedules are differentiable (the XLA path by construction —
scan/ppermute/all_to_all have transposes — and the Pallas path via its
custom ring VJP), so the same code serves training and inference. Call them
inside ``shard_map`` (the model does), or use the ``*_sharded`` wrappers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.attention import NEG_INF, _repeat_kv


def _block_attn_step(q, k, v, m, l, acc, *, q_start, kv_start, causal,
                     sm_scale, softcap):
    """One online-softmax accumulation step of local Q against one KV shard.

    q: [B,Sq,H,D]; k/v: [B,Skv,H,D]; m/l: [B,H,Sq]; acc: [B,Sq,H,D] (f32).
    ``q_start``/``kv_start`` are global offsets (traced OK)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    sq, skv = q.shape[1], k.shape[1]
    if causal:
        q_pos = q_start + jnp.arange(sq)[:, None]
        kv_pos = kv_start + jnp.arange(skv)[None, :]
        mask = kv_pos <= q_pos                     # [Sq, Skv]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)                    # [B,H,Sq]
    m_new = jnp.maximum(m, m_cur)
    # exp(NEG_INF - NEG_INF) would be 1: zero fully-masked entries explicitly.
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m - m_new)                     # [B,H,Sq]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    acc_new = acc * jnp.transpose(alpha, (0, 2, 1))[..., None] + pv
    return m_new, l_new, acc_new


def _ring_merge(o_acc, lse_acc, o_t, lse_t):
    """Merge a new normalized partial (o_t, lse_t) into the running one.

    Both partials are softmax-normalized over their own key sets; the
    unnormalized sums are exp(lse)·o, so the merge is the usual max-rescaled
    combine. A fully-masked partial carries lse = NEG_INF and contributes
    exp(NEG_INF − m) = 0; when BOTH sides are masked the denominator is 2
    with zero numerators — still exact zeros, no special case."""
    m = jnp.maximum(lse_acc, lse_t)
    a = jnp.exp(lse_acc - m)                       # [B,H,Sq]
    b = jnp.exp(lse_t - m)
    denom = a + b
    o_new = (a[..., None] * o_acc
             + b[..., None] * o_t.astype(jnp.float32)) / denom[..., None]
    return o_new, m + jnp.log(denom)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, sm_scale, softcap, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                  softcap, interpret)
    return out


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale, softcap,
                         interpret):
    from kubeflow_tpu.ops.flash_attention import _flash_fwd

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    qt = jnp.swapaxes(q, 1, 2)                     # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2)                     # [B,KH,Skv,D] (raw GQA)
    vt = jnp.swapaxes(v, 1, 2)
    b, h, sq, d = qt.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def visible(args):                             # past shard: no mask
        k_c, v_c = args
        return _flash_fwd(qt, k_c, v_c, causal=False, sm_scale=sm_scale,
                          softcap=softcap, q_offset=0, block_q=None,
                          block_kv=None, interpret=interpret)

    def diagonal(args):                            # own shard: square causal
        k_c, v_c = args
        return _flash_fwd(qt, k_c, v_c, causal=True, sm_scale=sm_scale,
                          softcap=softcap, q_offset=0, block_q=None,
                          block_kv=None, interpret=interpret)

    def masked(args):                              # future shard: skip
        return (jnp.zeros((b, h, sq, d), qt.dtype),
                jnp.full((b, h, sq), NEG_INF, jnp.float32))

    def step(carry, t):
        k_c, v_c, o_acc, lse_acc = carry
        shard = (idx - t) % n
        if causal:
            case = jnp.where(shard == idx, 1, jnp.where(shard < idx, 0, 2))
            o_t, lse_t = jax.lax.switch(case, [visible, diagonal, masked],
                                        (k_c, v_c))
        else:
            o_t, lse_t = visible((k_c, v_c))
        o_acc, lse_acc = _ring_merge(o_acc, lse_acc, o_t, lse_t)
        k_nxt = jax.lax.ppermute(k_c, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_c, axis_name, perm)
        return (k_nxt, v_nxt, o_acc, lse_acc), None

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    (_, _, o_acc, lse), _ = jax.lax.scan(step, (kt, vt, o0, lse0),
                                         jnp.arange(n))
    return jnp.swapaxes(o_acc.astype(q.dtype), 1, 2), lse


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, sm_scale, softcap,
                        interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                    softcap, interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, sm_scale, softcap, interpret,
                        res, do):
    """The backward ring: dK/dV accumulators travel WITH their KV shard (n
    rotations return both to the home device), dQ accumulates locally. Each
    step calls the flash backward kernels with the GLOBAL lse/delta, which
    makes per-shard contributions exact — the same property that lets the
    single-chip VJP be one recompute sweep."""
    from kubeflow_tpu.ops.flash_attention import _flash_bwd_pallas

    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = jnp.swapaxes(out, 1, 2)
    dot_ = jnp.swapaxes(do, 1, 2)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def grads(k_c, v_c, diag):
        return _flash_bwd_pallas(
            qt, k_c, v_c, ot, lse, dot_, causal=diag, sm_scale=sm_scale,
            softcap=softcap, q_offset=0, block_q=None, block_kv=None,
            interpret=interpret)

    def visible(args):
        return grads(args[0], args[1], False)

    def diagonal(args):
        return grads(args[0], args[1], True)

    def masked(args):
        k_c, v_c = args
        return (jnp.zeros_like(qt), jnp.zeros_like(k_c),
                jnp.zeros_like(v_c))

    def step(carry, t):
        k_c, v_c, dk_c, dv_c, dq_acc = carry
        shard = (idx - t) % n
        if causal:
            case = jnp.where(shard == idx, 1, jnp.where(shard < idx, 0, 2))
            dq_t, dk_t, dv_t = jax.lax.switch(
                case, [visible, diagonal, masked], (k_c, v_c))
        else:
            dq_t, dk_t, dv_t = visible((k_c, v_c))
        dq_acc = dq_acc + dq_t.astype(jnp.float32)
        dk_c = dk_c + dk_t.astype(jnp.float32)
        dv_c = dv_c + dv_t.astype(jnp.float32)
        # Rotate the shard and its gradient accumulator together; fp32
        # accumulators double the backward's ring traffic vs the bf16 KV —
        # the price of exact accumulation across n partial sums.
        k_c, v_c, dk_c, dv_c = (jax.lax.ppermute(x, axis_name, perm)
                                for x in (k_c, v_c, dk_c, dv_c))
        return (k_c, v_c, dk_c, dv_c, dq_acc), None

    dk0 = jnp.zeros(kt.shape, jnp.float32)
    dv0 = jnp.zeros(vt.shape, jnp.float32)
    dq0 = jnp.zeros(qt.shape, jnp.float32)
    (_, _, dk, dv, dq), _ = jax.lax.scan(
        step, (kt, vt, dk0, dv0, dq0), jnp.arange(n))
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(
    q: jax.Array,                     # [B, S_local, H, D] (seq shard)
    k: jax.Array,                     # [B, S_local, K, D]
    v: jax.Array,                     # [B, S_local, K, D]
    *,
    axis_name: str = "seq",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logits_softcap: Optional[float] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact attention over the full (ring-distributed) sequence. Must run
    inside shard_map with q/k/v sharded on dim 1 over ``axis_name``.

    ``impl``: "pallas" runs the tuned flash kernels per KV shard (custom
    ring VJP); "xla" is the einsum/scan oracle; "auto" picks pallas on TPU.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
        return _ring_flash(q, k, v, axis_name, causal, scale,
                           logits_softcap, interpret)
    if impl != "xla":
        raise ValueError(f"unknown ring attention impl {impl!r}")
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    # GQA expansion happens per-step inside _block_attn_step: the ring
    # rotates the RAW [B,S,K,D] shards, so ppermute traffic and the scan
    # carry stay 1/n_rep the size of the expanded heads.
    n_rep = h // k.shape[2]
    scale = sm_scale if sm_scale is not None else d ** -0.5

    q_start = idx * s_local
    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, s_local, h, d), jnp.float32)

    # Ring schedule: at step t this device holds KV shard (idx - t) mod n and
    # passes it on to rank+1 afterwards, so every device sees every shard.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        k_cur, v_cur, m, l, acc = carry
        kv_shard = (idx - t) % n
        m, l, acc = _block_attn_step(
            q, _repeat_kv(k_cur, n_rep), _repeat_kv(v_cur, n_rep), m, l, acc,
            q_start=q_start, kv_start=kv_shard * s_local,
            causal=causal, sm_scale=scale, softcap=logits_softcap)
        # Rotate KV for the next step (skipped result after the last one is
        # harmless; XLA overlaps this transfer with the next block compute).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    (_, _, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n))
    l_t = jnp.transpose(l, (0, 2, 1))[..., None]   # [B,Sq,H,1]
    out = acc / jnp.where(l_t == 0.0, 1.0, l_t)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,                     # [B, S_local, H, D]
    k: jax.Array,                     # [B, S_local, K, D]
    v: jax.Array,                     # [B, S_local, K, D]
    *,
    axis_name: str = "seq",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logits_softcap: Optional[float] = None,
    impl: str = "xla",
) -> jax.Array:
    """All-to-all swap seq-sharding → head-sharding, local full attention,
    swap back (the DeepSpeed-Ulysses schedule, TPU-natively over ICI)."""
    from kubeflow_tpu.ops.attention import multi_head_attention

    n = jax.lax.axis_size(axis_name)
    h, kh = q.shape[2], k.shape[2]
    if h % n or kh % n:
        raise ValueError(
            f"ulysses needs heads divisible by the seq axis: H={h}, K={kh}, "
            f"axis={n} (use ring attention otherwise)")
    # [B, S/n, H, D] -> [B, S, H/n, D]
    qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kh_ = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                             tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    out = multi_head_attention(qh, kh_, vh, causal=causal,
                               logits_softcap=logits_softcap, impl=impl)
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _sharded(fn, mesh: Mesh, axis_name: str, batch_axes):
    spec = P(batch_axes, axis_name, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def ring_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, *,
    axis_name: str = "seq", batch_axes=("dcn", "data", "fsdp"),
    causal: bool = True, sm_scale: Optional[float] = None,
    logits_softcap: Optional[float] = None,
    impl: str = "auto", interpret: Optional[bool] = None,
) -> jax.Array:
    """Convenience wrapper: applies shard_map over the mesh (batch sharded on
    the data axes, sequence on ``axis_name``)."""
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                           sm_scale=sm_scale, logits_softcap=logits_softcap,
                           impl=impl, interpret=interpret)
    return _sharded(fn, mesh, axis_name, batch)(q, k, v)


def ulysses_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, *,
    axis_name: str = "seq", batch_axes=("dcn", "data", "fsdp"),
    causal: bool = True, sm_scale: Optional[float] = None,
    logits_softcap: Optional[float] = None, impl: str = "xla",
) -> jax.Array:
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale,
                           logits_softcap=logits_softcap, impl=impl)
    return _sharded(fn, mesh, axis_name, batch)(q, k, v)
