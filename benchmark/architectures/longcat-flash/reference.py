"""The plain reference: the forward pass and next-token loss of
LongCat-Flash-Omni's LANGUAGE MODEL in straightforward ``jax.numpy`` and
float32, written from the model's own ``config.json`` and the equations of
its public ``modeling_longcat_flash.py`` (``LongcatFlashDecoderLayer``,
``LongcatFlashMLA``, ``LongcatFlashTopkRouter``, ``LongcatFlashMoE``) and of
the LongCat-Flash technical report (the shortcut-connected expert layer, the
zero-computation experts). No kernels, no cache, no batching, and nothing
imported from ``kubeflow_tpu``: it reads the same weight arrays the program
was handed. The audio and vision encoders and the codec decoder are not part
of it: a position is an id of the vocabulary.

One PUBLISHED layer, ``x`` its input, every ``N`` an RMSNorm of its own
(eps ``rms_norm_eps``), both attentions and both MLPs with their own
parameters::

    a = x + MLA_0(N1_0(x))                  # first attention
    h = N2_0(a)
    e = MoE(h)                              # the shortcut: started here ...
    b = a + MLP_0(h)                        # dense SwiGLU of ffn_hidden_size
    c = b + MLA_1(N1_1(b))                  # second attention
    y = c + MLP_1(N2_1(c)) + e              # ... and joined here

- ``MLA(u)``, EXPANDED (a cache and the absorbed form are the program's
  business): ``cq = norm(u Wqa)``; per head ``[q_nope | q_rope] = s_q (cq
  Wqb)`` with ``s_q = sqrt(hidden / q_lora_rank)`` (``mla_scale_q_lora``);
  ``[ckv | k_r] = u Wkva``, ``ckv = norm(ckv)``, ``k_rope = RoPE(k_r)`` (not
  scaled, one for all heads); per head ``[k_nope | v] = (s_kv ckv) Wkvb``
  with ``s_kv = sqrt(hidden / kv_lora_rank)`` (``mla_scale_kv_lora``: applied
  to the normed latent in front of its expansion, where the published code
  applies it); scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
  rope)``, causal softmax, the heads' values through ``Wo``. No bias.
- ``MoE(h)``: ``s = softmax(h Wr)`` over ALL ``n_routed_experts_published +
  zero_expert_num`` outputs; the ``moe_topk`` largest of ``s + b`` are chosen
  (``b``: the correction bias, the choice's alone); a chosen ``j`` weighs
  ``routed_scaling_factor * s_j``, NOT divided by the chosen's sum; ``e =
  sum_{chosen j < published} w_j Expert_j(h) + (sum_{chosen j >= published}
  w_j) h``: the zero experts are the identity. Of the experts with weights
  those HELD (``n_routed_experts`` from ``expert_offset`` on: one chip's
  share) are computed, what the others would add is left out; the zero
  experts' term is whole. The vocabulary's rows held are ``vocab_size``.

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
a published layer's two blocks are the tree's blocks ``2l`` and ``2l + 1``
(the program's layout), walked a published layer at a time and upcast as
they are used; an expert layer walks its held experts one at a time and
computes each for every token, weighting by the routing (zero for an expert
a token did not choose); attention takes its queries in blocks against the
whole context. ASSUMED (the configuration file says so): RoPE pairs a head's
two halves (the ``rotate_half`` convention of ``benchmark/reference.py``)
where the published code interleaves.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product. ``variant`` is the other controls' hook:
"model" is the model; "no_experts" leaves the expert layer out, "no_zero"
the zero experts' term, "joined_early" adds the expert layer's result a
sublayer early (to ``b``, in front of the second attention), and
"no_rank_factors" leaves both ``s_q`` and ``s_kv`` out: what the comparison
reads beside a program that got that part of the block wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, q_block_for, rmsnorm, rope, same

VARIANTS = ("model", "no_experts", "no_zero", "joined_early",
            "no_rank_factors")


def swiglu(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def routing(moe, h, c: dict, quant):
    """The weight of every output of the router for every token, [S,
    published + zero] (zero where a token did not choose the output)."""
    scores = jax.nn.softmax(quant(h) @ quant(moe["router"].astype(F32)),
                            axis=-1)
    _, chosen = jax.lax.top_k(scores + moe["router_bias"].astype(F32),
                              c["moe_topk"])
    w = jnp.take_along_axis(scores, chosen, axis=-1) \
        * c["routed_scaling_factor"]                # without b, not normalised
    width = c["n_routed_experts_published"] + c["zero_expert_num"]
    return jnp.sum(jax.nn.one_hot(chosen, width, dtype=F32) * w[..., None],
                   axis=1)


def expert_layer(moe, h, c: dict, quant, variant: str = "model"):
    """The held experts' part of the routed sum and the zero experts'."""
    held, first = c["n_routed_experts"], c["expert_offset"]
    weight = routing(moe, h, c, quant)

    def one(acc, xs):
        w_e, pe = xs
        return acc + w_e[:, None] * swiglu(pe, h, quant), None

    experts = {k: moe[k] for k in ("gate", "up", "down")}
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (weight[:, first:first + held].T, experts))
    if variant == "no_zero":
        return routed
    zero = jnp.sum(weight[:, c["n_routed_experts_published"]:], axis=-1)
    return routed + zero[:, None] * h


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

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    return out.reshape(s, h, v.shape[-1])


def latent_attention(a, u, positions, c: dict, q_block: int, quant,
                     variant: str = "model"):
    eps, theta, d = c["rms_norm_eps"], c["rope_theta"], c["hidden_size"]
    r, nope = c["kv_lora_rank"], c["qk_nope_head_dim"]
    scaled = variant != "no_rank_factors"
    s_q = (d / c["q_lora_rank"]) ** 0.5 \
        if c["mla_scale_q_lora"] and scaled else 1.0
    s_kv = (d / r) ** 0.5 if c["mla_scale_kv_lora"] and scaled else 1.0
    cq = rmsnorm(quant(u) @ quant(a["wqa"].astype(F32)),
                 a["q_norm"].astype(F32), eps)
    q = s_q * jnp.einsum("sq,qhk->shk", quant(cq),
                         quant(a["wqb"].astype(F32)))
    kva = quant(u) @ quant(a["wkva"].astype(F32))
    ckv = s_kv * rmsnorm(kva[:, :r], a["kv_norm"].astype(F32), eps)
    kv = jnp.einsum("sr,rhk->shk", quant(ckv), quant(a["wkvb"].astype(F32)))
    q_rope = rope(q[..., nope:], positions, theta)
    k_rope = rope(kva[:, None, r:], positions, theta)           # [S, 1, rope]
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    o = causal_attention(quant(q), quant(k), quant(kv[..., nope:]), q_block)
    return jnp.einsum("shk,hkd->sd", quant(o), quant(a["wo"].astype(F32)))


def published_layer(p, x, positions, c: dict, q_block: int, quant,
                    variant: str = "model"):
    """``p``: the pair's two blocks (every leaf ``[2, ...]``) and its ONE
    expert layer ``p["moe"]``."""
    eps = c["rms_norm_eps"]

    def sub(i):
        return jax.tree.map(lambda w: w[i],
                            {k: v for k, v in p.items() if k != "moe"})

    def attend(b, x):
        return x + latent_attention(
            b["attn"], rmsnorm(x, b["ln1"].astype(F32), eps), positions, c,
            q_block, quant, variant)

    first, second = sub(0), sub(1)
    a = attend(first, x)
    h = rmsnorm(a, first["ln2"].astype(F32), eps)
    e = jnp.zeros_like(h) if variant == "no_experts" \
        else expert_layer(p["moe"], h, c, quant, variant)
    b = a + swiglu(first["mlp"], h, quant)
    if variant == "joined_early":
        b, e = b + e, jnp.zeros_like(e)
    cc = attend(second, b)
    return cc + swiglu(second["mlp"],
                       rmsnorm(cc, second["ln2"].astype(F32), eps), quant) + e


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False,
                  variant: str = "model"):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    s, n = tokens.shape[0], c["num_layers"]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(s)
    stack = params["layers"]
    # blocks 2l and 2l + 1 are published layer l's; its expert layer is l
    pairs = {**jax.tree.map(lambda w: w.reshape(n, 2, *w.shape[1:]),
                            {k: v for k, v in stack.items() if k != "moe"}),
             "moe": stack["moe"]}

    def body(x, p):
        return published_layer(p, x, positions, c, qb, quant, variant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, pairs)
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def logits(params, tokens, c: dict, quant=same, last: int | None = None,
           variant: str = "model"):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant, variant=variant)
    if last is not None:
        x = x[-last:]
    return quant(x) @ quant(params["lm_head"].astype(F32))


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets."""
    x = hidden_states(params, tokens[:-1], c, quant, remat=remat)
    lg = quant(x) @ quant(params["lm_head"].astype(F32))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
