"""The plain reference: a Solar-Open2 decoder's forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, written from the model's
own ``config.json`` (``model_type`` ``solar_open2``) and the equations its
keys name: Kimi Delta Attention (``linear_attn_config``, ``kda_use_full_proj``,
``kda_allow_neg_eigval``: the gated delta rule with a decay a channel, Kimi
Linear, arXiv:2510.26692, after Gated DeltaNet, arXiv:2412.06464) beside
softmax GQA without position and with an output gate, every layer
DeepSeek-V3's router (arXiv:2412.19437, section 2.1.2: sigmoid scores chosen
with a bias and weighted without it) beside a shared expert. No kernels, no
cache, no batching, and nothing imported from ``kubeflow_tpu``: it reads the
same weight arrays the program was handed.

Per layer, ``x`` its input and every norm an RMSNorm BEFORE its sublayer:
``h = x + Op(norm1(x))``, ``y = h + FFN(norm2(h))``.

- A KDA layer (``l`` not in ``gqa_layers``), per head of ``linear_attn_config.
  num_heads`` with ``dk = dv = head_dim``, **token by token**:
  ``q, k, v = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))``, causal
  depthwise convolutions of ``short_conv_kernel_size`` taps over time; q and
  k L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), q times ``dk ** -0.5``;
  ``g_t = -exp(A_log[h]) softplus((x Wf1 Wf2)_t + dt_bias)`` a channel
  (``kda_use_full_proj`` false: rank 128), ``a_t = exp(g_t)``; ``beta_t = 2
  sigmoid(x wb)`` (``kda_allow_neg_eigval``); with ``S`` [dk, dv] zero at the
  sequence's start, ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t
  k_t v_t^T`` and ``o_t = S_t^T q_t``: a ``lax.scan`` over POSITIONS that
  carries ``S`` (NOT the chunked form the program computes, so that the
  program's chunking is what is tested); ``y_t = (RMSNorm_head(o_t) *
  sigmoid((x Wg1 Wg2)_t)) Wo``.
- A GQA layer (``l`` in ``gqa_layers``): ``q = x Wq`` (64 heads of 128), ``k,
  v`` (8 heads), no rotation (``use_rope`` false), causal softmax attention
  over the whole context in float32 at ``head_dim ** -0.5``, ``y = (attn *
  sigmoid(x Wgate)) Wo`` (``use_gqa_gate``, elementwise).
- FFN of every layer (``first_k_dense_replace`` 0): ``s = sigmoid(x Wr)`` over
  ALL ``n_routed_experts_routed``; the ``num_experts_per_tok`` with the
  largest ``s + b`` are chosen; their weights are ``s`` WITHOUT ``b`` over the
  chosen ones' sum ``+ 1e-20`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum w_e E_e(x) + E_shared(x)``, every ``E``
  a SwiGLU of ``moe_intermediate_size``.
- **The share.** The weights handed in hold ``n_routed_experts`` experts, the
  published experts ``expert_offset ..`` of ``n_routed_experts_routed``: one
  chip of the expert-parallel group that shares each layer. The sum over
  chosen experts runs over those of them that are held; what the others would
  have added lies on the group's other chips and is left out HERE as in the
  program. The router, the choice and the normalisation are over all of
  them; the shared expert is whole. The vocabulary's rows held are
  ``vocab_size``.
- Embedding, the layers, a final RMSNorm, the head (a matrix of its own).

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
a layer's weights are upcast where it uses them; an expert layer walks its
held experts one at a time (the dense form of the same sum); attention takes
its queries in blocks against the whole context; a KDA layer walks the
sequence in BLOCKS of positions (``kda_block_for``): a block's projections
and convolutions are computed together, its positions then go through the
recurrence one by one, and the state and the convolutions' last inputs are
carried to the next block, so a 17k prompt's per-position operands are never
alive at once.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product with a weight (the recurrence's own sums
stay in float32, the state's stated precision).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, attention, q_block_for, rmsnorm, same

ROUTER_NORM_EPS = 1e-20
L2_EPS = 1e-6


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
    return jnp.sum(
        jax.nn.one_hot(chosen, c["n_routed_experts_routed"], dtype=F32)
        * w[..., None], axis=1)


def expert_layer(mlp, i: int, x, c: dict, quant):
    """Layer ``i`` of the stacked expert leaves ``mlp`` on ``x`` [S, D]: the
    held experts' part of the routed sum and the shared expert."""
    held, first = c["n_routed_experts"], c["expert_offset"]
    weight = routing(mlp, i, x, c, quant)[:, first:first + held]

    def one(acc, xs):
        w_e, e = xs
        pe = {k: mlp[k][i, e] for k in ("gate", "up", "down")}
        return acc + w_e[:, None] * swiglu(pe, x, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (weight.T, jnp.arange(held)))
    shared = jax.tree.map(lambda a: a[i], mlp["shared"])
    return routed + swiglu(shared, x, quant)


def gqa_operator(p, y, c: dict, q_block: int, quant):
    """Softmax GQA without position, its output gated elementwise."""
    q = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", quant(y), quant(p["wv"].astype(F32)))
    o = attention(quant(q), quant(k), quant(v), q_block)
    gate = jax.nn.sigmoid(jnp.einsum(
        "sd,dhk->shk", quant(y), quant(p["wgate"].astype(F32))))
    return jnp.einsum("shk,hkd->sd", quant(o * gate),
                      quant(p["wo"].astype(F32)))


def kda_block_for(s: int) -> int:
    """Positions a block of the KDA layer's walk: the largest divisor of
    ``s`` up to 1024."""
    return next(n for n in range(min(s, 1024), 0, -1) if s % n == 0)


def kda_token(state, xs):
    """ONE token of one layer, every head: state [H, dk, dv]; q, k, g [H,
    dk], v [H, dv], beta [H]. The recurrence as written."""
    q, k, v, g, beta = xs
    decayed = jnp.exp(g)[..., None] * state                 # Diag(a) S
    seen = jnp.einsum("hk,hkv->hv", k, decayed)             # (Diag(a) S)^T k
    state = decayed + (beta[:, None] * k)[..., None] * (v - seen)[:, None, :]
    return state, jnp.einsum("hk,hkv->hv", q, state)        # S_t^T q_t


def kda_operator(p, y, c: dict, quant):
    """The KDA layer on ``y`` [S, D], token by token."""
    lin = c["linear_attn_config"]
    h, dk, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    s = y.shape[0]
    blk = kda_block_for(s)

    def project(yb, w):
        return jnp.einsum("sd,dhk->shk", quant(yb), quant(w.astype(F32)))

    def low_rank(yb, w1, w2):
        mid = quant(yb) @ quant(w1.astype(F32))
        return jnp.einsum("sr,rhk->shk", quant(mid), quant(w2.astype(F32)))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    def one_block(carry, yb):
        state, tails = carry                    # [H,dk,dv], {n: [taps-1,H,dk]}
        conv, new_tails = {}, {}
        for n in ("q", "k", "v"):
            xs = jnp.concatenate([tails[n], project(yb, p["w" + n])])
            w = p["conv_" + n].astype(F32)
            conv[n] = jax.nn.silu(sum(w[j] * xs[j:j + blk]
                                      for j in range(taps)))
            new_tails[n] = xs[blk:]
        q = unit(conv["q"]) * dk ** -0.5
        k = unit(conv["k"])
        g = -jnp.exp(p["a_log"].astype(F32))[:, None] * jax.nn.softplus(
            low_rank(yb, p["wf1"], p["wf2"]) + p["dt_bias"].astype(F32))
        beta = 2.0 * jax.nn.sigmoid(quant(yb) @ quant(p["wb"].astype(F32)))
        state, o = jax.lax.scan(kda_token, state, (q, k, conv["v"], g, beta))
        o = rmsnorm(o, p["o_norm"].astype(F32), c["rms_norm_eps"])
        gate = jax.nn.sigmoid(low_rank(yb, p["wg1"], p["wg2"]))
        out = jnp.einsum("shk,hkd->sd", quant(o * gate),
                         quant(p["wo"].astype(F32)))
        return (state, new_tails), out

    zero = (jnp.zeros((h, dk, dk), F32),
            {n: jnp.zeros((taps - 1, h, dk), F32) for n in ("q", "k", "v")})
    _, out = jax.lax.scan(one_block, zero, y.reshape(s // blk, blk, -1))
    return out.reshape(s, -1)


def layer_of(group: dict, gqa: list, i: int) -> dict:
    """Layer ``i`` of the stacked group, its feed-forward left in the stack:
    its norms at ``i``, its operator at its place among the layers of its
    kind."""
    name = "attn" if i in gqa else "linear"
    at = sum((j in gqa) == (i in gqa) for j in range(i))
    return {"ln1": group["ln1"][i], "ln2": group["ln2"][i],
            name: jax.tree.map(lambda a: a[at], group[name])}


def layer(p, x, c: dict, q_block: int, quant, ffn):
    eps = c["rms_norm_eps"]
    y = rmsnorm(x, p["ln1"].astype(F32), eps)
    if "attn" in p:
        x = x + gqa_operator(p["attn"], y, c, q_block, quant)
    else:
        x = x + kda_operator(p["linear"], y, c, quant)
    return x + ffn(rmsnorm(x, p["ln2"].astype(F32), eps))


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(tokens.shape[0])
    group, mlp = params["layers"], params["layers"]["mlp"]
    for i in range(c["num_hidden_layers"]):
        def body(x, p, i=i):
            return layer(p, x, c, qb, quant,
                         lambda y: expert_layer(mlp, i, y, c, quant))

        if remat:
            body = jax.checkpoint(body)
        x = body(x, layer_of(group, c["gqa_layers_held"], i))
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def logits(params, tokens, c: dict, quant=same, last: int | None = None):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant)
    if last is not None:
        x = x[-last:]
    return quant(x) @ quant(params["lm_head"].astype(F32))


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets. (No cell trains this architecture.)"""
    x = hidden_states(params, tokens[:-1], c, quant, remat=remat)
    lg = quant(x) @ quant(params["lm_head"].astype(F32))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
