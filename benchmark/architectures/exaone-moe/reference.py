"""The plain reference: an EXAONE-MoE decoder's forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, written from the model's
own ``config.json`` (``model_type`` ``exaone_moe``) and the equations of its
family (LG AI Research's EXAONE 4.0 hybrid attention: window layers that
rotate beside global layers that carry no position, per-head q/k RMSNorm;
DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for sigmoid scores chosen with a
bias and weighted without it, beside a shared expert). No kernels, no cache,
no batching, and nothing imported from ``kubeflow_tpu``: it reads the same
weight arrays the program was handed.

Per layer, ``x`` its input and every norm an RMSNorm BEFORE its sublayer:
``h = x + Attn(norm1(x))``, ``y = h + FFN(norm2(h))``.

- Attention: ``q = norm_head(x Wq)``, ``k = norm_head(x Wk)`` (an RMSNorm
  over each head's values), ``v = x Wv``; 64 query heads share 8 K/V heads;
  softmax in float32 at ``head_dim ** -0.5``; ``out = concat(o) Wo``. No
  biases. A WINDOW layer (``layer_types_held[l] == "sliding_attention"``)
  rotates q and k (RoPE over the whole head, after the norm) and its query
  ``i`` sees keys ``i - sliding_window < j <= i`` (``sliding_window`` keys,
  itself among them: the Hugging Face convention). A GLOBAL layer
  (``"full_attention"``) does not rotate and sees every key ``j <= i``.
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``. Of every later layer: ``s = sigmoid(x Wr)`` over
  ALL ``num_experts_routed``; the ``num_experts_per_tok`` with the largest ``s + b``
  are chosen (``n_group`` 1: no group limit); their weights are ``s``
  WITHOUT ``b`` over the chosen ones' sum ``+ 1e-20`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``y = sum w_e E_e(x) + E_shared(x)``,
  every ``E`` a SwiGLU of ``moe_intermediate_size``.
- **The share.** The weights handed in hold ``num_experts`` experts, the
  published experts ``expert_offset ..`` of ``num_experts_routed``: one chip of the
  expert-parallel group that shares each layer. The sum over chosen experts
  runs over those of them that are held; what the others would have added
  lies on the group's other chips and is left out HERE as in the program,
  and that partial result goes on to the next layer. The router, the choice
  and the normalisation are over all ``num_experts_routed``; the shared expert
  is whole. The vocabulary's rows held are ``vocab_size``.
- Embedding, the layers, a final RMSNorm, the head (a matrix of its own).

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
a layer's weights are upcast where it uses them (they are stored in the
served type); an expert layer walks its held experts one at a time and
computes each for every token, weighting by the routing (zero for a token
that did not choose it), the dense form of the same sum; attention takes its
queries in blocks (a window layer's against the keys its block can see, a
global layer's against the whole context), so a 9k prompt is computed in
blocks. ASSUMED (the configuration file says so): per-head q/k norms before
RoPE and rotation on window layers only, norms before their sublayers, the
correction bias present, RoPE pairing a head's two halves (the
``rotate_half`` convention of ``benchmark/reference.py``).

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import (
    F32, attention, q_block_for, rmsnorm, rope, same,
)

ROUTER_NORM_EPS = 1e-20
OPERATOR = {"sliding_attention": "window", "full_attention": "attn"}


def swiglu(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def routing(mlp, i: int, x, c: dict, quant):
    """Layer ``i``'s weight of every expert for every token, [S, E] (zero
    where a token did not choose the expert)."""
    scores = jax.nn.sigmoid(quant(x) @ quant(mlp["router"][i].astype(F32)))
    _, chosen = jax.lax.top_k(scores + mlp["router_bias"][i].astype(F32),
                              c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)            # without b
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    w = w * c["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, c["num_experts_routed"], dtype=F32)
                   * w[..., None], axis=1)


def expert_layer(mlp, i: int, x, c: dict, quant):
    """Layer ``i`` of a group's stacked expert leaves ``mlp`` on ``x`` [S,
    D]: the held experts' part of the routed sum and the shared expert. One
    expert of one layer is taken out of the stack at a time."""
    held, first = c["num_experts"], c["expert_offset"]
    weight = routing(mlp, i, x, c, quant)[:, first:first + held]   # [S, held]

    def one(acc, xs):
        w_e, e = xs
        pe = {k: mlp[k][i, e] for k in ("gate", "up", "down")}
        return acc + w_e[:, None] * swiglu(pe, x, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (weight.T, jnp.arange(held)))
    shared = jax.tree.map(lambda a: a[i], mlp["shared"])
    return routed + swiglu(shared, x, quant)


def window_attention(q, k, v, q_block: int, window: int):
    """Causal grouped-query attention in which query ``i`` sees keys ``i -
    window < j <= i``. q [S, H, Dh]; k, v [S, KV, Dh]. A block of queries is
    scored against the ``q_block + window`` keys from ``window`` before its
    first query on (zeros before the sequence's start, masked)."""
    s, h, dh = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, dh)
    pad = jnp.zeros((window, kv, dh), F32)
    kp, vp = jnp.concatenate([pad, k]), jnp.concatenate([pad, v])

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, q_block, 0)
        kb = jax.lax.dynamic_slice_in_dim(kp, start, q_block + window, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, q_block + window, 0)
        scores = jnp.einsum("qngd,knd->ngqk", qb, kb) / jnp.sqrt(F32(dh))
        qpos = start + jnp.arange(q_block)
        kpos = start - window + jnp.arange(q_block + window)
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window) & (kpos[None, :] >= 0)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, vb)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    return out.reshape(s, h, dh)


def attention_operator(p, y, positions, c: dict, q_block: int, quant,
                       window: int):
    eps, theta = c["rms_norm_eps"], c["rope_parameters"]["rope_theta"]
    q = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wv"].astype(F32)))
    q = rmsnorm(q, p["q_norm"].astype(F32), eps)
    k = rmsnorm(k, p["k_norm"].astype(F32), eps)
    if window:          # only the window layers carry positions
        q, k = rope(q, positions, theta), rope(k, positions, theta)
        o = window_attention(quant(q), quant(k), quant(v), q_block, window)
    else:
        o = attention(quant(q), quant(k), quant(v), q_block)
    return jnp.einsum("shk,hkd->sd", quant(o), quant(p["wo"].astype(F32)))


def layer_of(group: dict, kinds: list, i: int) -> dict:
    """Layer ``i`` of a stacked group, its feed-forward left in the stack:
    its norms at ``i``, its operator at its place among the group's layers
    of its kind."""
    name = OPERATOR[kinds[i]]
    at = kinds[:i].count(kinds[i])
    return {"ln1": group["ln1"][i], "ln2": group["ln2"][i],
            name: jax.tree.map(lambda a: a[at], group[name])}


def layer(p, x, positions, c: dict, q_block: int, quant, ffn):
    eps = c["rms_norm_eps"]
    y = rmsnorm(x, p["ln1"].astype(F32), eps)
    if "window" in p:
        x = x + attention_operator(p["window"], y, positions, c, q_block,
                                   quant, c["sliding_window"])
    else:
        x = x + attention_operator(p["attn"], y, positions, c, q_block,
                                   quant, 0)
    return x + ffn(rmsnorm(x, p["ln2"].astype(F32), eps))


def groups_of(params, c: dict) -> list:
    """(the group's key in the tree, its layers' published kinds), in layer
    order: the leading dense layers, the expert layers, and what the tree
    holds behind their whole periods."""
    kinds, n_dense = c["layer_types_held"], c["first_k_dense_replace"]
    whole = len(params["layers"]["ln1"])
    out = [("dense_layers", kinds[:n_dense]),
           ("layers", kinds[n_dense:n_dense + whole])]
    if "layers_rest" in params:
        out.append(("layers_rest", kinds[n_dense + whole:]))
    return out


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(s)
    for name, group_kinds in groups_of(params, c):
        mlp = params[name]["mlp"]
        if name == "dense_layers":
            def ffn(mlp, i, y):
                return swiglu(jax.tree.map(lambda a: a[i], mlp), y, quant)
        else:
            def ffn(mlp, i, y):
                return expert_layer(mlp, i, y, c, quant)
        for i in range(len(group_kinds)):
            def body(x, p, i=i, ffn=ffn, mlp=mlp):
                return layer(p, x, positions, c, qb, quant,
                             lambda y: ffn(mlp, i, y))

            if remat:
                body = jax.checkpoint(body)
            x = body(x, layer_of(params[name], group_kinds, i))
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


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
