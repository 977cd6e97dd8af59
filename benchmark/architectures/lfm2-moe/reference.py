"""The plain reference: an LFM2-MoE decoder's forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, written from the model's
own ``config.json`` (``model_type`` ``lfm2_moe``) and the equations of its
family (LiquidAI's LFM2: a gated short convolution in most layers, grouped-
query attention with per-head q/k norms in the others; DeepSeek-V3,
arXiv:2412.19437, section 2.1.2 for sigmoid scores chosen with a bias and
weighted without it). No kernels, no cache, no batching, and nothing
imported from ``kubeflow_tpu``: it reads the same weight arrays the program
was handed.

Per layer, ``x`` its input and every norm an RMSNorm:
``h = x + Op(norm1(x))``, ``y = h + FFN(norm2(h))``.

- Conv operator (``layer_types_held[l] == "conv"``): ``[B | C | u] =
  norm1(x) Win`` (three hidden-wide parts in that order), ``z = B * u``,
  ``c_t = taps[0] z_{t-2} + taps[1] z_{t-1} + taps[2] z_t`` (causal,
  depthwise, zeros before the sequence's start, no bias), ``out = (C * c)
  Wout``.
- Attention operator (``"full_attention"``): ``q = norm_head(x Wq)``,
  ``k = norm_head(x Wk)`` (an RMSNorm over each head's values), ``v = x
  Wv``; RoPE on q and k AFTER the norm, over the whole head; causal softmax
  attention at ``head_dim ** -0.5``; ``out = concat(o) Wo``. No biases.
- FFN of the first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``. Of every later layer: ``s = sigmoid(x Wg)``; the
  top-k of ``s + expert_bias`` are chosen; their weights are ``s`` WITHOUT
  the bias, over their sum ``+ 1e-6`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum w_i E_i(x)``, each expert a SwiGLU of
  ``moe_intermediate_size``. No shared expert; no token is dropped.
- Embedding, the layers, a final RMSNorm (the checkpoint's
  ``embedding_norm``), the head, which is the embedding (tied).

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
a layer's weights are upcast where it uses them (they are stored in the
served type); an expert layer walks its experts one at a time and computes
every expert for every token, weighting by the routing (zero for an expert a
token was not routed to), the dense form of the same sum; attention takes
its queries in blocks against the whole context, so a long prompt is
computed in blocks. ASSUMED (the configuration file says so): RoPE pairs a
head's two halves (the ``rotate_half`` convention of
``benchmark/reference.py``), the head is tied.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import (
    F32, attention, q_block_for, rmsnorm, rope, same,
)

ROUTER_NORM_EPS = 1e-6


def swiglu(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def expert_layer(mlp, i: int, x, c: dict, quant):
    """Layer ``i`` of a group's stacked expert leaves ``mlp`` on ``x`` [S,
    D]. One expert of one layer is taken out of the stack at a time."""
    scores = jax.nn.sigmoid(quant(x) @ quant(mlp["router"][i].astype(F32)))
    _, chosen = jax.lax.top_k(scores + mlp["router_bias"][i].astype(F32),
                              c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)            # without b
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    w = w * c["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(chosen, c["num_experts"], dtype=F32)
                     * w[..., None], axis=1)                    # [S, E]

    def one(acc, xs):
        w_e, e = xs
        pe = {k: mlp[k][i, e] for k in ("gate", "up", "down")}
        return acc + w_e[:, None] * swiglu(pe, x, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (weight.T, jnp.arange(c["num_experts"])))
    return routed


def conv_operator(p, y, quant):
    """The gated short convolution over ``y`` [S, D]."""
    s = y.shape[0]
    bcu = jnp.einsum("sd,dgk->sgk", quant(y), quant(p["win"].astype(F32)))
    z = bcu[:, 0] * bcu[:, 2]
    taps = p["taps"].astype(F32)                                # [taps, D]
    k = taps.shape[0]
    before = jnp.concatenate([jnp.zeros((k - 1, z.shape[1]), F32), z])
    conv = sum(taps[j] * before[j:j + s] for j in range(k))
    return quant(bcu[:, 1] * conv) @ quant(p["wout"].astype(F32))


def attention_operator(p, y, positions, c: dict, q_block: int, quant):
    eps, theta = c["norm_eps"], c["rope_parameters"]["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wv"].astype(F32)))
    q = rope(rmsnorm(q, p["q_norm"].astype(F32), eps), positions, theta)
    k = rope(rmsnorm(k, p["k_norm"].astype(F32), eps), positions, theta)
    o = attention(quant(q), quant(k), quant(v), q_block)
    return jnp.einsum("shk,hkd->sd", quant(o), quant(p["wo"].astype(F32)))


def layer_of(group: dict, kinds: list, i: int) -> dict:
    """Layer ``i`` of a stacked group, its feed-forward left in the stack:
    its norms at ``i``, its operator at its place among the group's layers
    of its kind."""
    name = "conv" if kinds[i] == "conv" else "attn"
    at = kinds[:i].count(kinds[i])
    return {"ln1": group["ln1"][i], "ln2": group["ln2"][i],
            name: jax.tree.map(lambda a: a[at], group[name])}


def layer(p, x, positions, c: dict, q_block: int, quant, ffn):
    eps = c["norm_eps"]
    y = rmsnorm(x, p["ln1"].astype(F32), eps)
    if "conv" in p:
        x = x + conv_operator(p["conv"], y, quant)
    else:
        x = x + attention_operator(p["attn"], y, positions, c, q_block,
                                   quant)
    return x + ffn(rmsnorm(x, p["ln2"].astype(F32), eps))


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(s)
    kinds, n_dense = c["layer_types_held"], c["num_dense_layers"]
    groups = (
        ("dense_layers", kinds[:n_dense], lambda mlp, i, y: swiglu(
            jax.tree.map(lambda a: a[i], mlp), y, quant)),
        ("layers", kinds[n_dense:],
         lambda mlp, i, y: expert_layer(mlp, i, y, c, quant)))
    for name, group_kinds, ffn in groups:
        mlp = params[name]["mlp"]
        for i in range(len(group_kinds)):
            def body(x, p, i=i, ffn=ffn, mlp=mlp):
                return layer(p, x, positions, c, qb, quant,
                             lambda y: ffn(mlp, i, y))

            if remat:
                body = jax.checkpoint(body)
            x = body(x, layer_of(params[name], group_kinds, i))
    return rmsnorm(x, params["final_norm"].astype(F32), c["norm_eps"])


def _head(params, c: dict):
    if c["tie_word_embeddings"]:
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
