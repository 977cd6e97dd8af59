"""Test-only: the plain reference of the rehearsal's Gemma-shaped decoder,
from the published description (Gemma Team, "Gemma: Open Models Based on
Gemini Research and Technology", arXiv:2403.08295; the soft-cap from "Gemma
2", arXiv:2408.00118; the Hugging Face ``modeling_gemma`` equations):
embeddings scaled by sqrt(hidden), RMSNorm weights applied as (1 + w), a
GeGLU feed-forward (tanh-approximated GELU), the output head tied to the
embedding, and logits capped by ``cap * tanh(logits / cap)``. The norm's
core, the rotary embedding and the blocked attention are every reference's.
Nothing is imported from ``kubeflow_tpu``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import (
    F32, attention, q_block_for, rmsnorm, rope, same,
)


def _norm(x, w, c):
    return rmsnorm(x, 1.0 + w.astype(F32), c["rms_norm_eps"])


def layer(p, x, positions, c: dict, qb: int, quant):
    a, theta = p["attn"], c["rope_theta"]
    y = quant(_norm(x, p["ln1"], c))
    q = jnp.einsum("sd,dhk->shk", y, quant(a["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", y, quant(a["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", y, quant(a["wv"].astype(F32)))
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    o = attention(quant(q), quant(k), quant(v), qb)
    x = x + jnp.einsum("shk,hkd->sd", quant(o), quant(a["wo"].astype(F32)))
    y = quant(_norm(x, p["ln2"], c))
    m = p["mlp"]
    gate = jax.nn.gelu(y @ quant(m["gate"].astype(F32)), approximate=True)
    up = y @ quant(m["up"].astype(F32))
    return x + quant(gate * up) @ quant(m["down"].astype(F32))


def _logits(params, tokens, c: dict, quant, remat: bool):
    positions = jnp.arange(tokens.shape[0])
    embed = params["embed"].astype(F32)
    x = embed[tokens] * jnp.sqrt(F32(c["hidden_size"]))
    qb = q_block_for(tokens.shape[0])

    def body(x, p):
        return layer(p, x, positions, c, qb, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm(x, params["final_norm"], c), embed


def _capped(x, embed, c: dict, quant):
    lg = quant(x) @ quant(embed.T)
    cap = c.get("final_logit_softcapping")
    return lg if cap is None else cap * jnp.tanh(lg / cap)


def logits(params, tokens, c: dict, quant=same, last: int | None = None):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x, embed = _logits(params, tokens, c, quant, remat=False)
    return _capped(x if last is None else x[-last:], embed, c, quant)


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood."""
    x, embed = _logits(params, tokens[:-1], c, quant, remat=remat)
    logp = jax.nn.log_softmax(_capped(x, embed, c, quant), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
