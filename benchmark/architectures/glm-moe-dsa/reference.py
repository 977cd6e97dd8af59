"""The plain reference: a GLM-5 decoder's forward pass and next-token loss in
straightforward ``jax.numpy`` and float32, written from the model's own
``config.json`` (``model_type`` ``glm_moe_dsa``) and the equations of its
family (DeepSeek-V2, arXiv:2405.04434, section 2.1 for latent attention;
DeepSeek-V3, arXiv:2412.19437, section 2.1.2 for sigmoid scores chosen with a
correction bias and weighted without it; the DeepSeek-V3.2-Exp report and its
public ``inference/model.py`` for the indexer and its selection). No kernels,
no cache, no batching, and nothing imported from ``kubeflow_tpu``: it reads
the same weight arrays the program was handed.

Per layer, ``x`` its input, ``h = RMSNorm(x)``: ``x += Attn(h)``, ``x +=
FFN(RMSNorm(x))``; the first ``first_k_dense_replace`` layers' FFN is a
SwiGLU of ``intermediate_size``, every later layer's the expert layer.

- Latent attention, EXPANDED (a cache and the absorbed form are the
  program's business): ``cq = norm(h Wqa)``; per head ``[q_nope | q_rope] =
  cq Wqb``; ``[ckv | k_rope] = h Wkva``, ``ckv = norm(ckv)``, one ``k_rope``
  for all heads; RoPE on ``q_rope`` and ``k_rope``; per head ``[k_nope | v] =
  ckv Wkvb``; scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
  rope)``.
- The indexer (every layer): ``qI = cq WqI`` (``index_n_heads`` heads of
  ``index_head_dim``), ``kI = LayerNorm(h WkI)`` (weight, bias, eps 1e-6; ONE
  key for all heads), RoPE on the first ``qk_rope_head_dim`` values of each;
  ``w = (h Ww) / sqrt(heads) / sqrt(head_dim)``; ``I(t, s) = sum_j w[t, j]
  ReLU(qI[t, j] . kI[s])`` for ``s <= t``, the whole ``[S, S]`` of it.
- The selection: a SORT a query. Query ``t`` keeps the ``min(index_topk, t +
  1)`` positions of largest ``I(t, s)``, a tie going to the lower position
  (a stable sort of the negated scores). ONE softmax over the kept set, ``o =
  sum p v``; output ``concat(o) Wo``.
- Experts (``topk_method`` ``noaux_tc``, ``n_group`` 1: no group limit):
  ``s = sigmoid(x Wr)`` over the PUBLISHED experts
  (``n_routed_experts_published``); the top-k of ``s + b`` are chosen; their
  weights are ``s`` WITHOUT ``b``, over their sum (``norm_topk_prob``),
  times ``routed_scaling_factor``; of the chosen experts those HELD
  (``n_routed_experts`` from ``expert_offset`` on: one chip's share) are
  computed, what the others would add is left out; the shared expert is
  whole. The vocabulary's rows held are ``vocab_size``.

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published code, each for memory and none for arithmetic:
layers are walked one at a time and upcast as they are used (the weights are
stored in the served type); an expert layer walks its held experts one at a
time and computes each for every token, weighting by the routing (zero for
an expert a token did not choose); a layer's indexer, selection and
attention take their queries in blocks of ``Q_BLOCK`` against the whole
context and attention its heads ``HEAD_GROUP`` at a time (the selection,
which all heads share, is made once a layer and kept as an ``[S, S]`` mask),
so that 12,288 tokens fit beside the weights and the engine's pool. ASSUMED (the
configuration file says so): RoPE pairs a head's two halves (the
``rotate_half`` convention of ``benchmark/reference.py``) where the published
code interleaves; the index keys are not rotated by a Hadamard matrix and not
kept in float8 (the rotation leaves every ``qI . kI`` as it was). The
multi-token-prediction module is not part of the served forward pass and has
no code here.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product. ``selection`` is the other controls' hook:
"indexer" is the model; "recent" keeps each query's most recent
``index_topk`` positions and "keys_zeroed" scores against index keys of
zeros (every score a tie: the lowest positions), what the comparison reads
beside a program whose selection is wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, q_block_for, rmsnorm, rope, same

ROUTER_NORM_EPS = 1e-20
INDEX_NORM_EPS = 1e-6
Q_BLOCK = 256
HEAD_GROUP = 16


def swiglu(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def routing(mlp, x, c: dict, quant):
    """The weight of every PUBLISHED expert for every token, [S, E] (zero
    where a token did not choose the expert)."""
    scores = jax.nn.sigmoid(quant(x) @ quant(mlp["router"].astype(F32)))
    _, chosen = jax.lax.top_k(scores + mlp["router_bias"].astype(F32),
                              c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)            # without b
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    w = w * c["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(chosen, c["n_routed_experts_published"],
                                  dtype=F32) * w[..., None], axis=1)


def expert_layer(mlp, x, c: dict, quant):
    """The held experts' part of the routed sum and the shared expert."""
    held, first = c["n_routed_experts"], c["expert_offset"]
    weight = routing(mlp, x, c, quant)[:, first:first + held]   # [S, held]

    def one(acc, xs):
        w_e, pe = xs
        return acc + w_e[:, None] * swiglu(pe, x, quant), None

    experts = {k: mlp[k] for k in ("gate", "up", "down")}
    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (weight.T, experts))
    return routed + swiglu(mlp["shared"], x, quant)


def layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w + b


def rope_first(x, positions, theta, n: int):
    """x [S, H, D]: RoPE on each head's first ``n`` values."""
    return jnp.concatenate([rope(x[..., :n], positions, theta), x[..., n:]],
                           axis=-1)


def index_scores(q_idx, w_idx, k_idx, q_pos, quant):
    """q_idx [Q, Hi, Di], w_idx [Q, Hi], k_idx [S, Di] -> I [Q, S], ``-inf``
    where key ``s`` lies behind query ``t``."""
    dots = jnp.einsum("qhd,sd->qhs", quant(q_idx), quant(k_idx))
    total = jnp.sum(w_idx[:, :, None] * jax.nn.relu(dots), axis=1)
    seen = jnp.arange(k_idx.shape[0])[None, :] <= q_pos[:, None]
    return jnp.where(seen, total, -jnp.inf)


def selected_keys(scores, topk: int):
    """scores [Q, S] (``-inf``: not visible) -> the mask of each query's
    ``topk`` largest visible scores, by a stable sort: a tie goes to the
    lower position."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)          # a key's place in the order
    return (rank < topk) & (scores > -jnp.inf)


def kept_keys(q_idx, w_idx, k_idx, c: dict, q_block: int, quant,
              selection: str):
    """The WHOLE selection of a layer, [S, S] bool: the indexer's scores of
    every query against every key it can see and a sort a query, a block of
    queries at a time. q_idx [S, Hi, Di], w_idx [S, Hi], k_idx [S, Di]."""
    s, topk = k_idx.shape[0], c["index_topk"]
    kpos = jnp.arange(s)

    def block(start):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, start, q_block, 0)

        q_pos = start + jnp.arange(q_block)
        if selection == "recent":
            return (kpos[None, :] <= q_pos[:, None]) \
                & (kpos[None, :] > q_pos[:, None] - topk)
        return selected_keys(index_scores(
            cut(q_idx), cut(w_idx), k_idx, q_pos, quant), topk)

    return jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(s, s)


def selected_attention(q, k, v, kept, q_block: int):
    """q, k [S, H, Dqk]; v [S, H, Dv]; kept [S, S]. A block of queries at a
    time, ONE softmax over each query's kept keys."""
    s, h, dqk = q.shape

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        mask = jax.lax.dynamic_slice_in_dim(kept, start, q_block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(dqk))
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, s, q_block))
    return out.reshape(s, h, v.shape[-1])


def latent_attention(a, y, positions, c: dict, q_block: int, quant,
                     selection: str):
    eps, theta = c["rms_norm_eps"], c["rope_parameters"]["rope_theta"]
    r, nope, rd = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"])
    cq = rmsnorm(quant(y) @ quant(a["wqa"].astype(F32)),
                 a["q_norm"].astype(F32), eps)
    kva = quant(y) @ quant(a["wkva"].astype(F32))
    ckv = rmsnorm(kva[:, :r], a["kv_norm"].astype(F32), eps)
    k_rope = rope(kva[:, None, r:], positions, theta)           # [S, 1, rope]
    # the indexer and its selection
    hi, di = c["index_n_heads"], c["index_head_dim"]
    q_idx = (quant(cq) @ quant(a["wq_idx"].astype(F32).T)).reshape(
        -1, hi, di)
    q_idx = rope_first(q_idx, positions, theta, rd)
    k_idx = layernorm(quant(y) @ quant(a["wk_idx"].astype(F32)),
                      a["k_idx_norm"].astype(F32),
                      a["k_idx_bias"].astype(F32), INDEX_NORM_EPS)
    k_idx = rope_first(k_idx[:, None, :], positions, theta, rd)[:, 0]
    if selection == "keys_zeroed":
        k_idx = jnp.zeros_like(k_idx)
    w_idx = (quant(y) @ quant(a["w_idx"].astype(F32))) \
        * (hi ** -0.5 * di ** -0.5)
    kept = kept_keys(q_idx, w_idx, k_idx, c, q_block, quant, selection)

    def heads(weights):
        """``HEAD_GROUP`` heads at a time: their queries, keys and values
        made a head from the latents (nothing absorbed), attention over the
        kept keys, their part of the output projection."""
        wqb, wkvb, wo = weights
        q = jnp.einsum("sq,qhk->shk", quant(cq), quant(wqb.astype(F32)))
        kv = jnp.einsum("sr,rhk->shk", quant(ckv), quant(wkvb.astype(F32)))
        q_rope = rope(q[..., nope:], positions, theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)],
            axis=-1)
        o = selected_attention(quant(q), quant(k), quant(kv[..., nope:]),
                               kept, q_block)
        return jnp.einsum("shk,hkd->sd", quant(o), quant(wo.astype(F32)))

    h = c["num_attention_heads"]
    g = HEAD_GROUP if h % HEAD_GROUP == 0 else h

    def grouped(w, axis):
        """[.., H, ..] -> [H / g, .., g, ..]."""
        shape = w.shape[:axis] + (h // g, g) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    parts = jax.lax.map(heads, (grouped(a["wqb"], 1), grouped(a["wkvb"], 1),
                                grouped(a["wo"], 0)))
    return jnp.sum(parts, axis=0)


def layer(p, x, positions, c: dict, q_block: int, quant, ffn, selection):
    eps = c["rms_norm_eps"]
    y = rmsnorm(x, p["ln1"].astype(F32), eps)
    x = x + latent_attention(p["attn"], y, positions, c, q_block, quant,
                             selection)
    return x + ffn(p["mlp"], rmsnorm(x, p["ln2"].astype(F32), eps))


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False,
                  selection: str = "indexer"):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    qb = min(q_block_for(s), Q_BLOCK)
    groups = (("dense_layers", lambda p, y: swiglu(p, y, quant)),
              ("layers", lambda p, y: expert_layer(p, y, c, quant)))
    for name, ffn in groups:
        def body(x, p, ffn=ffn):
            return layer(p, x, positions, c, qb, quant, ffn, selection), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params[name])
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def logits(params, tokens, c: dict, quant=same, last: int | None = None,
           selection: str = "indexer"):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant, selection=selection)
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


def selected_sets(params, tokens, c: dict, layer_index: int = 0):
    """The mask [S, S] the indexer of one layer selects on the INPUT of that
    layer as this reference computes it (tests hold the program's sets to
    it). ``layer_index`` counts through the dense layers, then the expert
    layers."""
    # (written for the tests' tiny sizes: every layer in front is run whole)
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = params["embed"].astype(F32)[tokens]
    blocks = []
    for name, ffn in (("dense_layers", lambda p, y: swiglu(p, y, same)),
                      ("layers", lambda p, y: expert_layer(p, y, c, same))):
        n = jax.tree.leaves(params[name])[0].shape[0]
        blocks += [(jax.tree.map(lambda a, i=i: a[i], params[name]), ffn)
                   for i in range(n)]
    for p, ffn in blocks[:layer_index]:
        x = layer(p, x, positions, c, s, same, ffn, "indexer")
    a = blocks[layer_index][0]["attn"]
    eps, theta = c["rms_norm_eps"], c["rope_parameters"]["rope_theta"]
    rd = c["qk_rope_head_dim"]
    y = rmsnorm(x, blocks[layer_index][0]["ln1"].astype(F32), eps)
    cq = rmsnorm(y @ a["wqa"].astype(F32), a["q_norm"].astype(F32), eps)
    q_idx = rope_first((cq @ a["wq_idx"].astype(F32).T).reshape(
        s, c["index_n_heads"], c["index_head_dim"]), positions, theta, rd)
    k_idx = layernorm(y @ a["wk_idx"].astype(F32),
                      a["k_idx_norm"].astype(F32),
                      a["k_idx_bias"].astype(F32), INDEX_NORM_EPS)
    k_idx = rope_first(k_idx[:, None, :], positions, theta, rd)[:, 0]
    w_idx = (y @ a["w_idx"].astype(F32)) \
        * (c["index_n_heads"] ** -0.5 * c["index_head_dim"] ** -0.5)
    scores = index_scores(q_idx, w_idx, k_idx, positions, same)
    return selected_keys(scores, c["index_topk"]), scores
