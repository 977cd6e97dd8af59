"""Attention ops: XLA reference implementation + impl dispatch.

The XLA path is the numerics oracle; `impl="pallas"` dispatches to the Pallas
flash kernel (ops/flash_attention.py) on TPU, and sequence-parallel ring
attention lives in parallel/ring_attention.py. Softmax runs in float32
regardless of activation dtype (bf16 softmax loses too much precision at long
sequence lengths).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B,S,K,D] -> [B,S,K*n_rep,D] for GQA (each kv head serves n_rep q heads)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def causal_mask(q_len: int, kv_len: int, *, q_offset: jax.Array | int = 0,
                window: int = 0) -> jax.Array:
    """[q_len, kv_len] boolean mask; True = attend. ``q_offset`` is the
    absolute position of query 0 (for decode with a KV cache). ``window``
    > 0: a query sees its last ``window`` keys only, itself among them."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    if window:
        return (kv_pos <= q_pos) & (kv_pos > q_pos - window)
    return kv_pos <= q_pos


def multi_head_attention(
    q: jax.Array,                     # [B, Sq, H, D]
    k: jax.Array,                     # [B, Skv, K, D]
    v: jax.Array,                     # [B, Skv, K, D]
    *,
    mask: Optional[jax.Array] = None,  # broadcastable to [B, H, Sq, Skv]; True=attend
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    logits_softcap: Optional[float] = None,
    impl: str = "xla",
    window: int = 0,
) -> jax.Array:
    """Scaled dot-product attention with GQA. Returns [B, Sq, H, D].
    ``window`` > 0 (with ``causal``): query ``i`` sees keys ``i - window <
    j <= i``. The flash kernel has no lower bound on the keys, so a window
    takes the XLA path whatever ``impl`` says."""
    if window and not causal:
        raise ValueError("an attention window needs causal attention")
    if impl == "pallas" and not window:
        try:
            from kubeflow_tpu.ops.flash_attention import flash_attention
        except ImportError as exc:
            raise ValueError(
                "attn impl 'pallas' requires kubeflow_tpu.ops.flash_attention "
                "(TPU-only); use impl='xla' on CPU") from exc

        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               logits_softcap=logits_softcap)
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}")

    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    n_rep = h // kh
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if logits_softcap is not None:
        logits = jnp.tanh(logits / logits_softcap) * logits_softcap
    if causal:
        cmask = causal_mask(sq, skv, q_offset=q_offset, window=window)
        logits = jnp.where(cmask[None, None, :, :], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out
