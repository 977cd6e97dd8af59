"""The plain reference: a Mistral / Mixtral decoder's forward pass and
next-token loss in straightforward ``jax.numpy`` and float32, written from
the published description (Jiang et al., "Mistral 7B", arXiv:2310.06825;
"Mixtral of Experts", arXiv:2401.04088; the Hugging Face ``modeling_mistral``
and ``modeling_mixtral`` equations). No kernels, no cache, no batching, and
nothing imported from ``kubeflow_tpu``: it reads the same weight arrays the
program was handed and the model's own ``config.json`` keys. The norm, the
rotary embedding and the blocked causal attention are every reference's
(``benchmark/reference.py``).

Every caller traces it under ``jax.default_matmul_precision("highest")``; on a
TPU a float32 product otherwise runs in bfloat16 passes.

Departures from the published code, each for memory and none for arithmetic:
layers are walked with ``lax.scan`` and upcast one at a time (the weights are
stored in the served type); an MoE layer walks its experts one at a time and
computes every expert for every token, weighting by the routing (zero for an
expert a token was not routed to), which is the dense form of the same sum;
attention takes its queries in blocks against the whole context.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import (
    F32, attention, q_block_for, rmsnorm, rope, same,
)


def dense_mlp(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def moe_mlp(p, x, top_k: int, quant):
    """Mixtral's sparse block: softmax over the router's logits, the top-k
    experts per token, their weights renormalised to sum to one."""
    logits = quant(x) @ quant(p["router"].astype(F32))           # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_e = logits.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(top_i, n_e, dtype=F32)
                     * top_p[..., None], axis=1)                 # [S, E]

    def one(acc, xs):
        w_e, pe = xs
        y = dense_mlp(pe, x, quant)
        return acc + w_e[:, None] * y, None

    experts = {k: p[k] for k in ("gate", "up", "down")}
    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (weight.T, experts))
    return out


def layer(p, x, positions, c: dict, q_block: int, quant):
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = p["attn"]
    y = quant(rmsnorm(x, p["ln1"].astype(F32), eps))
    q = jnp.einsum("sd,dhk->shk", y, quant(a["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", y, quant(a["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", y, quant(a["wv"].astype(F32)))
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    o = attention(quant(q), quant(k), quant(v), q_block)
    x = x + jnp.einsum("shk,hkd->sd", quant(o), quant(a["wo"].astype(F32)))
    y = rmsnorm(x, p["ln2"].astype(F32), eps)
    if c.get("num_local_experts", 0):
        return x + moe_mlp(p["mlp"], y, c["num_experts_per_tok"], quant)
    return x + dense_mlp(p["mlp"], y, quant)


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(s)

    def body(x, p):
        return layer(p, x, positions, c, qb, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def _head(params, c: dict):
    if c.get("tie_word_embeddings", False):
        return params["embed"].astype(F32).T
    return params["lm_head"].astype(F32)


def logits(params, tokens, c: dict, quant=same, last: int | None = None):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant)
    if last is not None:
        x = x[-last:]
    return quant(x) @ quant(_head(params, c))


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets."""
    x = hidden_states(params, tokens[:-1], c, quant, remat=remat)
    lg = quant(x) @ quant(_head(params, c))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
