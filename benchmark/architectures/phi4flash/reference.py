"""The plain reference: a Phi-4-mini-flash-reasoning decoder's forward pass
and next-token loss in straightforward ``jax.numpy`` and float32, written from
the model's own ``config.json`` (``model_type`` ``phi4flash``) and the papers
it names: SambaY, the decoder-hybrid-decoder (arXiv:2507.06607), on YOCO's one
KV cache and cross-decoder (arXiv:2405.05254), Mamba-1's selective scan
(arXiv:2312.00752) and the Differential Transformer (arXiv:2410.05258). No
kernels, no cache, no batching, and nothing imported from ``kubeflow_tpu``:
it reads the same weight arrays the program was handed.

Per layer ``i`` of 32, ``x`` its input, every norm a LayerNorm (weight and
bias) BEFORE its sublayer: ``h = x + Mixer_i(LN1(x))``, ``y = h +
MLP(LN2(h))``, ``MLP(u) = (up(u) * SiLU(gate(u))) down``. The mixer by the
layer's index (``layer_types``; ``mb_per_layer`` 2: every even layer is of
the Mamba family, every odd one attention; the self-decoder is layers ``0 ..
num_hidden_layers / 2 + 1``, the cross-decoder the rest):

- **Mamba-1** (even ``i <= 16``), with ``E = 2 hidden``, ``N = 16`` states a
  channel, ``R = hidden / 16``, 4 taps: ``u, z = y Wu, y Wz``; ``c_t =
  SiLU(conv4(u)_t + b_conv)``, causal and depthwise; ``[d_t, B_t, C_t] = c_t
  Wx``; ``Delta_t = softplus(d_t Wdt + b_dt)``; ``A = -exp(A_log)``; with ``h``
  ``[E, N]`` zero at the sequence's start, **token by token** (a ``lax.scan``
  over POSITIONS that carries ``h``: NOT the blocked kernel the program
  runs, so that the program's chunking is what is tested): ``h_t = exp(Delta_t
  A) h_(t-1) + (Delta_t c_t) B_t^T``, ``s_t = h_t C_t + D c_t``; out ``= (s_t *
  SiLU(z_t)) Wout``. Layer 16 is the same and its ``s`` (before the gate) is
  THE MEMORY ``m``.
- **Gated memory unit** (even ``i >= 18``): ``(SiLU(y W1) * m) W2``, ``m`` at
  the same position.
- **Differential attention** (odd ``i``), as two softmaxes of 64-wide heads
  and a subtraction (NOT the padded-query form the program hands its
  kernels): ``q = y Wq + b`` (40 heads), and on layers ``<= 17`` ``k, v = y
  Wk + b, y Wv + b`` (20 heads); NO position of any kind. Query pair ``p`` of
  20 is heads ``(2p, 2p + 1)``, it reads KV pair ``j = p // 2``: keys ``(k[2j],
  k[2j+1])``, values ``[v[2j] | v[2j+1]]`` (128 wide). ``A1 = softmax(q1 k1^T
  / 8)``, ``A2 = softmax(q2 k2^T / 8)`` under the layer's mask: causal, and on
  layers 1 .. 15 only the last ``sliding_window`` keys, the query's own among
  them. ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` (the tree carries it a layer);
  ``o_p = RMSNorm_128((A1 - lambda A2) V) * (1 - lambda_init)``; out ``=
  concat_p(o_p) Wo + b``. Layer 17 sees every key, and its K and V are THE
  CACHE: a **cross layer** (odd ``i >= 19``) computes queries only and
  attends over layer 17's K and V, causally.
- EVERY layer runs at EVERY position: the program's skipped tail (the
  cross-decoder only where logits are read) is what is tested.
- Embedding, the layers, a final LayerNorm, the embedding transposed (tied).

Every caller traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published modeling file (``modeling_phi4flash.py``), each
one a reader can check there: the fused ``in_proj`` / ``Wqkv`` / ``fc1`` stand
here as the halves the tree holds (the same products); the state is ``[E,
N]`` as published while the tree's ``a_log`` lies ``[N, E]`` and its ``wq`` /
``wk`` / ``wv`` out by in (each transposed where read). For memory and none for arithmetic: a layer's weights are upcast
where it uses them; attention takes its queries in blocks against the whole
context; the head multiplies a block of the vocabulary at a time.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product with a weight (the scan's own sums and the
attention's stay in float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, q_block_for, rmsnorm, same

# the tree's groups (``weights.py``): (key, mixers of the even layers, of the
# odd layers) and the layers each holds
GROUPS = (("layers", "ssm", "window"), ("layers_rest", "ssm", "attn"),
          ("layers_rest2", "gmu", "cross"))
HEAD_BLOCKS = 8


def layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w + b


def group_sizes(c: dict) -> tuple:
    """Layers in each of ``GROUPS``, read off ``layer_types``: the windowed
    self-decoder, the layer pair that makes the memory and the cache (a
    Mamba layer and the one full-attention layer), the cross-decoder."""
    types = c["layer_types"]
    full = types.index("full_attention")
    return full - 1, 2, len(types) - full - 1


def layer_of(params: dict, c: dict, i: int):
    """Layer ``i``: (its mixer's kind, {"ln1", "ln1_b", "ln2", "ln2_b",
    "mlp", "mix"}) out of the stacked groups."""
    first = 0
    for (name, even, odd), n in zip(GROUPS, group_sizes(c)):
        if i < first + n:
            g, at = params[name], i - first
            kind = odd if at % 2 else even
            p = {k: jax.tree.map(lambda a: a[at], g[k])
                 for k in ("ln1", "ln1_b", "ln2", "ln2_b", "mlp")}
            p["mix"] = jax.tree.map(lambda a: a[at // 2], g[kind])
            return kind, p
        first += n
    raise IndexError(i)


def mlp(p, x, quant):
    gate = jax.nn.silu(quant(x) @ quant(p["gate"].astype(F32)))
    up = quant(x) @ quant(p["up"].astype(F32))
    return quant(gate * up) @ quant(p["down"].astype(F32))


def mamba_token(a, d):
    """ONE token of one layer: the state [E, N]; c, delta [E]; b, cn [N]."""
    def step(h, xs):
        c, delta, b, cn = xs
        h = jnp.exp(delta[:, None] * a) * h + (delta * c)[:, None] * b[None]
        return h, h @ cn + d * c

    return step


def mamba(p, y, c: dict, quant):
    """The Mamba-1 mixer on ``y`` [S, D]: (out [S, D], the scan's output
    before its gate [S, E])."""
    s = y.shape[0]
    n, r = c["d_state"], c["dt_rank"]
    u = quant(y) @ quant(p["wu"].astype(F32))
    z = quant(y) @ quant(p["wz"].astype(F32))
    taps = p["conv"].astype(F32)                    # [taps, E], [-1] = now
    k = taps.shape[0]
    us = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    cv = jax.nn.silu(sum(taps[j] * us[j:j + s] for j in range(k))
                     + p["conv_b"].astype(F32))
    dbc = quant(cv) @ quant(p["wx"].astype(F32))
    delta = jax.nn.softplus(
        quant(dbc[:, :r]) @ quant(p["wdt"].astype(F32))
        + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32)).T          # [E, N]
    _, m = jax.lax.scan(
        mamba_token(a, p["d_skip"].astype(F32)),
        jnp.zeros((u.shape[1], n), F32),
        (cv, delta, dbc[:, r:r + n], dbc[:, r + n:]))
    return quant(m * jax.nn.silu(z)) @ quant(p["wout"].astype(F32)), m


def gmu(p, y, m, quant):
    gate = jax.nn.silu(quant(y) @ quant(p["w1"].astype(F32)))
    return quant(gate * m) @ quant(p["w2"].astype(F32))


def project(p, name: str, y, quant):
    """``y W + b``; the tree holds ``wq`` / ``wk`` / ``wv`` OUT by IN."""
    w = p["w" + name].astype(F32)
    return quant(y) @ quant(w if name == "o" else w.T) \
        + p["b" + name].astype(F32)


def keys_values(p, y, c: dict, quant):
    """What an attention layer keeps of ``y``: (k1, k2 [S, KV/2, Dh], V [S,
    KV/2, 2 Dh]): the even and the odd heads' keys, the pairs' values side by
    side."""
    s, kv = y.shape[0], c["num_key_value_heads"]
    k = project(p, "k", y, quant).reshape(s, kv, -1)
    v = project(p, "v", y, quant).reshape(s, kv // 2, -1)
    return quant(k[:, 0::2]), quant(k[:, 1::2]), quant(v)


def diff_attention(p, y, kv, c: dict, q_block: int, window: int, quant):
    """Differential attention of ``y``'s queries over ``kv`` (``keys_values``
    of this layer or, for a cross layer, of another)."""
    s, h = y.shape[0], c["num_attention_heads"]
    k1, k2, vv = kv
    pairs, per = h // 2, h // c["num_key_value_heads"]   # query pairs a KV pair
    q = quant(project(p, "q", y, quant)).reshape(s, pairs // per, per, 2, -1)
    dh = q.shape[-1]
    lam0 = p["lambda_init"].astype(F32)
    lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(F32)
                          * p["lambda_k1"].astype(F32))) \
        - jnp.exp(jnp.sum(p["lambda_q2"].astype(F32)
                          * p["lambda_k2"].astype(F32))) + lam0
    kpos = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        qpos = start + jnp.arange(q_block)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)

        def softmax(qh, kh):        # [qb, J, G, Dh] x [S, J, Dh]
            scores = jnp.einsum("qjgd,kjd->jgqk", qh, kh) / jnp.sqrt(F32(dh))
            return jax.nn.softmax(
                jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)

        a = softmax(qb[..., 0, :], k1) - lam * softmax(qb[..., 1, :], k2)
        return jnp.einsum("jgqk,kjd->qjgd", a, vv)      # [qb, J, G, 2 Dh]

    o = jax.lax.map(block, jnp.arange(0, s, q_block)).reshape(s, pairs, -1)
    o = rmsnorm(o, p["subln"].astype(F32), c["layer_norm_eps"]) * (1.0 - lam0)
    return project(p, "o", o.reshape(s, -1), quant)


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    eps = c["layer_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    qb = q_block_for(tokens.shape[0])
    memory = cache = None
    for i in range(c["num_hidden_layers"]):
        kind, p = layer_of(params, c, i)
        y = layernorm(x, p["ln1"].astype(F32), p["ln1_b"].astype(F32), eps)
        if kind == "ssm":
            out, memory = mamba(p["mix"], y, c, quant)
        elif kind == "gmu":
            out = gmu(p["mix"], y, memory, quant)
        elif kind == "cross":
            out = diff_attention(p["mix"], y, cache, c, qb, 0, quant)
        else:
            cache = keys_values(p["mix"], y, c, quant)
            out = diff_attention(
                p["mix"], y, cache, c, qb,
                c["sliding_window"] if kind == "window" else 0, quant)
        x = x + out
        x = x + mlp(p["mlp"], layernorm(
            x, p["ln2"].astype(F32), p["ln2_b"].astype(F32), eps), quant)
    return layernorm(x, params["final_norm"].astype(F32),
                     params["final_norm_b"].astype(F32), eps)


def head(params, x, quant):
    """``x`` [S, D] times the embedding transposed, a block of the
    vocabulary at a time."""
    table = params["embed"]
    blocks = table.reshape(HEAD_BLOCKS, -1, table.shape[1])
    out = jax.lax.map(
        lambda rows: quant(x) @ quant(rows.astype(F32)).T, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)


def logits(params, tokens, c: dict, quant=same, last: int | None = None):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant)
    if last is not None:
        x = x[-last:]
    return head(params, x, quant)


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets. (No cell trains this architecture: the scan has no backward
    in the program.)"""
    lg = head(params, hidden_states(params, tokens[:-1], c, quant), quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
