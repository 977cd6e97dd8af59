"""What every plain reference shares: the pieces of a decoder's forward pass
that are the same arithmetic whatever the architecture, in straightforward
``jax.numpy`` and float32. No kernels, no cache, no batching, and nothing
imported from ``kubeflow_tpu``. A model's own equations (its layer, its
feed-forward or expert block, its head and loss) are the architecture's:
``benchmark/architectures/<name>/reference.py``.

Every caller traces a reference under
``jax.default_matmul_precision("highest")``; on a TPU a float32 product
otherwise runs in bfloat16 passes.

Attention takes its queries in blocks against the whole context: a departure
for memory and none for arithmetic.

``quant`` is the control's hook, not part of a model: a reference applies it
to both operands of every matrix product (``benchmark/correctness.py`` passes
``fp8_round_trip`` to show that the comparison fails one precision step
down).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def same(x):
    return x


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x [S, H, Dh]: rotate the two halves of each head (the Hugging Face
    ``rotate_half`` convention), angle = position * theta^(-2i/Dh)."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block: int):
    """Causal grouped-query attention. q [S, H, Dh]; k, v [S, KV, Dh].
    Query blocks of ``q_block`` against all keys, so the score matrix alive
    at once is [H, q_block, S]."""
    s, h, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(s, kv, g, dh)
    kpos = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, q_block, 0)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k) / jnp.sqrt(F32(dh))
        qpos = start + jnp.arange(q_block)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, v)

    # Rematerialised, so a backward pass keeps a block's inputs and not its
    # [H, q_block, S] probabilities for every block at once.
    starts = jnp.arange(0, s, q_block)
    out = jax.lax.map(jax.checkpoint(block), starts)  # [S/qb, qb, KV, G, Dh]
    return out.reshape(s, h, dh)


def q_block_for(s: int) -> int:
    for qb in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if s % qb == 0:
            return qb
    return 1


def fp8_round_trip(x):
    """The control's precision: float8 (e4m3), one step below bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)
