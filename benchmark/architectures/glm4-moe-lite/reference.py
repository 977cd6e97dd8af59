"""The plain reference: a GLM-4.7-Flash decoder's forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, written from the model's
own ``config.json`` (``model_type`` ``glm4_moe_lite``) and the equations of
its family (DeepSeek-V2, arXiv:2405.04434, section 2.1 for latent attention;
DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for sigmoid scores chosen with a
correction bias and weighted without it). No kernels, no cache, no batching,
and nothing imported from ``kubeflow_tpu``: it reads the same weight arrays
the program was handed.

Per layer, ``x`` its input and every norm an RMSNorm: ``x += Attn(norm1(x))``,
``x += FFN(norm2(x))``; the first ``first_k_dense_replace`` layers' FFN is a
SwiGLU of ``intermediate_size``, every later layer's the expert layer.

- Attention, EXPANDED (a cache and the absorbed form are the program's
  business): ``cq = norm(x Wqa)``; per head ``[q_nope | q_rope] = cq Wqb``;
  ``[ckv | k_rope] = x Wkva``, ``ckv = norm(ckv)``, one ``k_rope`` for all
  heads; RoPE on ``q_rope`` and ``k_rope``; per head ``[k_nope | v] = ckv
  Wkvb``; scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``,
  causal softmax, ``o = sum p v``; output ``concat(o) Wo``.
- Experts (``topk_method`` ``noaux_tc``, ``n_group`` 1: no group limit):
  ``s = sigmoid(x Wr)``; the top-k of ``s + b`` are chosen; their weights are
  ``s`` WITHOUT ``b``, over their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum w_i E_i(x) + E_shared(x)``. No token
  is dropped: there is no capacity.

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
layers are walked with ``lax.scan`` and upcast one at a time (the weights are
stored in the served type); an expert layer walks its experts one at a time
and computes every expert for every token, weighting by the routing (zero for
an expert a token was not routed to), the dense form of the same sum;
attention takes its queries in blocks against the whole context. ASSUMED (the
configuration file says so): RoPE pairs a head's two halves (the
``rotate_half`` convention of ``benchmark/reference.py``) where the published
code interleaves: a fixed permutation of ``Wqb`` / ``Wkva`` columns, which
seeded weights cannot tell apart. The multi-token-prediction module is not
part of the served forward pass and has no code here.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, q_block_for, rmsnorm, rope, same


def swiglu(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def expert_layer(p, x, c: dict, quant):
    scores = jax.nn.sigmoid(quant(x) @ quant(p["router"].astype(F32)))
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)            # without b
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(chosen, c["n_routed_experts"], dtype=F32)
                     * w[..., None], axis=1)                    # [S, E]

    def one(acc, xs):
        w_e, pe = xs
        return acc + w_e[:, None] * swiglu(pe, x, quant), None

    experts = {k: p[k] for k in ("gate", "up", "down")}
    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (weight.T, experts))
    return routed + swiglu(p["shared"], x, quant)


def causal_attention(q, k, v, q_block: int):
    """q, k [S, H, Dqk]; v [S, H, Dv]. Query blocks of ``q_block`` against
    all keys, so the score matrix alive at once is [H, q_block, S]."""
    s, h, dqk = q.shape
    kpos = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(dqk))
        mask = kpos[None, :] <= (start + jnp.arange(q_block))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    # Rematerialised, so a backward pass keeps a block's inputs and not its
    # probabilities for every block at once.
    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    return out.reshape(s, h, v.shape[-1])


def latent_attention(a, y, positions, c: dict, q_block: int, quant):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    r, nope = c["kv_lora_rank"], c["qk_nope_head_dim"]
    cq = rmsnorm(quant(y) @ quant(a["wqa"].astype(F32)),
                 a["q_norm"].astype(F32), eps)
    q = jnp.einsum("sq,qhk->shk", quant(cq), quant(a["wqb"].astype(F32)))
    kva = quant(y) @ quant(a["wkva"].astype(F32))
    ckv = rmsnorm(kva[:, :r], a["kv_norm"].astype(F32), eps)
    kv = jnp.einsum("sr,rhk->shk", quant(ckv), quant(a["wkvb"].astype(F32)))
    q_rope = rope(q[..., nope:], positions, theta)
    k_rope = rope(kva[:, None, r:], positions, theta)           # [S, 1, rope]
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    o = causal_attention(quant(q), quant(k), quant(kv[..., nope:]), q_block)
    return jnp.einsum("shk,hkd->sd", quant(o), quant(a["wo"].astype(F32)))


def layer(p, x, positions, c: dict, q_block: int, quant, ffn):
    eps = c["rms_norm_eps"]
    y = rmsnorm(x, p["ln1"].astype(F32), eps)
    x = x + latent_attention(p["attn"], y, positions, c, q_block, quant)
    return x + ffn(p["mlp"], rmsnorm(x, p["ln2"].astype(F32), eps))


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(s)
    groups = (("dense_layers", lambda p, y: swiglu(p, y, quant)),
              ("layers", lambda p, y: expert_layer(p, y, c, quant)))
    for name, ffn in groups:
        def body(x, p, ffn=ffn):
            return layer(p, x, positions, c, qb, quant, ffn), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params[name])
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
