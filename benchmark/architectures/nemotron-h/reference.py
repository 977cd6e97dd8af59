"""The plain reference: a Nemotron-H decoder's forward pass (Nemotron-3-Super's
stack) and next-token loss in straightforward ``jax.numpy`` and float32,
written from the model's own ``config.json`` (``model_type`` ``nemotron_h``),
the Nemotron-H report (arXiv:2504.03624), Mamba-2 / SSD (arXiv:2405.21060)
and the public ``modeling_nemotron_h.py`` (``NemotronHBlock``,
``NemotronHMamba2Mixer`` with ``MambaRMSNormGated``, ``NemotronHAttention``,
``NemotronHMOE`` with ``fc1_latent_proj`` / ``fc2_latent_proj``,
``NemotronHTopkRouter``). No kernels, no cache, no batching, no chunked form,
and nothing imported from ``kubeflow_tpu``: it reads the same weight arrays
the program was handed.

The stack is walked as PUBLISHED, one sublayer a layer (``layers_held``, the
pattern's letters): layer ``l`` is ``x <- x + F_l(N_l(x))`` with ``N_l`` an
RMSNorm with its own plain weight, ``u = N_l(x)``, per token ``t``:

- **M (Mamba-2)**: ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(conv(xBC) +
  bias)``, causal and depthwise over ``conv_kernel`` taps; ``x`` to
  ``mamba_num_heads`` heads of ``mamba_head_dim``, ``B`` and ``C`` to
  ``n_groups`` groups of ``ssm_state_size``, head ``j`` in group ``j // (heads
  / groups)``; ``dt[j] <- softplus(dt[j] + dt_bias[j])`` (no clamp), ``a[j] =
  exp(-exp(A_log[j]) dt[j])``; with ``S`` ``[P, N]`` a head, float32, zero
  before the first token, **token by token** (a ``lax.scan`` over POSITIONS
  that carries ``S``: NOT the blocked form the program runs, so that the
  program's chunking and its carried state are what is tested): ``S_t = a_t
  S_(t-1) + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; then ``g =
  GroupRMSNorm(y * SiLU(z))``, the gate FIRST, the norm over each of
  ``n_groups`` groups of channels with one weight a channel; ``F = g W_out``.
- **\\* (attention)**: ``q, k, v = u Wq, Wk, Wv`` (no bias, NO rotation, no
  q/k norm); causal softmax attention at scale ``head_dim ** -0.5``, ONE
  softmax over the whole causal context, query head ``i`` reading KV head ``i
  // (heads / kv_heads)``; ``F = o Wo``.
- **E (experts)**: ``s = sigmoid(u W_r)`` in float32 over ALL
  ``n_routed_experts_published`` experts; the ``num_experts_per_tok`` largest
  of ``s + b`` are CHOSEN (``b`` for the choice alone; ``n_group`` 1: no group
  limit); ``w_j = routed_scaling_factor * s_j / (sum of the chosen s +
  1e-20)``; ``l = u W_dn`` (the latent, once a token); ``r = sum over the
  chosen j HELD HERE (``expert_offset ..+ n_routed_experts``) of w_j
  relu(l U_j)^2 V_j``; ``F = r W_up + relu(u U_s)^2 V_s`` (the shared expert
  on ``u`` itself). What the experts held elsewhere would add is left out.
- A final RMSNorm and the untied head.

Departures from the published modeling file, each one a reader can check
there and none for arithmetic: the tree holds the program's BLOCKS in the
program's groups (``weights.py``: a mixer or an attention with the expert
layer behind it; ``blocks`` below finds a published layer's leaves in it);
``wq`` / ``wk`` / ``wv`` [D, heads, Dh] and ``wo`` [heads, Dh, D] (the same
products as the flat matrices); the in-projection as its column blocks
``w_z``, ``w_xbc``, ``w_dt`` (put side by side here into the one ``W_in``);
the published ONE shared expert of 5376 as one matrix pair (which it is); a
layer's weights are upcast where it uses them; attention takes its queries in
blocks against the whole context; the held experts are walked one at a time.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product with a weight and of the attention's two
products (the recurrence's own sums stay in float32).

``VARIANTS`` are the tests' and the chip proof's hook, not part of the model
either: each is the reference with ONE thing wrong, so that a comparison
which still passes against it is shown blind to that thing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import F32, attention, q_block_for, rmsnorm, same

VARIANTS = ("model", "no_mamba", "no_attention", "no_experts",
            "experts_read_hidden", "plain_relu", "one_norm_group")
KINDS = {"M": "ssd", "*": "attn"}
EXPERTS = ("up", "down")        # the leaves stacked [layers, held, ...]


def blocks(params: dict, pattern: str) -> list:
    """The published layers ``pattern`` as [(its letter, its norm's weight,
    its leaves)], found in the program's groups (``layers``, ``layers_rest``,
    ...: in a group ``ln1`` runs over its blocks, an operator's leaves over
    the blocks of its kind, ``ln2`` and ``mlp`` over the blocks an expert
    layer follows)."""
    groups = [params[k] for k in sorted(params) if k.startswith("layers")]
    g, at, out = 0, {}, []

    def take(name):
        i = at[name] = at.get(name, -1) + 1
        return jax.tree.map(lambda a: a[i], groups[g][name])

    for c in pattern:
        if c == "E":        # the expert layer of the block in front
            # (its experts are left in their stack, with the layer's place
            # in it: ``expert_layer`` takes ONE expert's matrices at a time,
            # where a slice of the stack would be a copy of 0.7 GB a matrix)
            mlp = groups[g]["mlp"]
            small = {k: v for k, v in mlp.items() if k not in EXPERTS}
            i = at["mlp"] = at.get("mlp", -1) + 1
            out.append((c, take("ln2"), {
                **jax.tree.map(lambda a: a[i], small),
                **{k: (mlp[k], i) for k in EXPERTS}}))
            continue
        if at.get("ln1", -1) + 1 == groups[g]["ln1"].shape[0]:
            g, at = g + 1, {}
        out.append((c, take("ln1"), take(KINDS[c])))
    return out


def relu2(x, variant: str = "model"):
    r = jax.nn.relu(x)
    return r if variant == "plain_relu" else r * r


def ssd_token(a, d, per: int):
    """ONE token of one layer, every head: the state [H, P, N]; x [H, P];
    dt [H]; b, cn [G, N] (head ``j`` reads group ``j // per``)."""
    def step(s, xs):
        x, dt, b, cn = xs
        b, cn = jnp.repeat(b, per, axis=0), jnp.repeat(cn, per, axis=0)
        s = jnp.exp(a * dt)[:, None, None] * s \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, cn) + d[:, None] * x

    return step


def mamba_layer(p, u, c: dict, quant, variant: str = "model"):
    """The Mamba-2 mixer on ``u`` [S, D]: (F [S, D], the state after the
    last token [H, P, N])."""
    s = u.shape[0]
    heads, groups, n = (c["mamba_num_heads"], c["n_groups"],
                        c["ssm_state_size"])
    e, gn = heads * c["mamba_head_dim"], groups * n
    w_in = jnp.concatenate([p[k].astype(F32)
                            for k in ("w_z", "w_xbc", "w_dt")], axis=1)
    proj = quant(u) @ quant(w_in)
    z, xbc, dt = proj[:, :e], proj[:, e:2 * e + 2 * gn], \
        proj[:, 2 * e + 2 * gn:]
    taps = p["conv"].astype(F32)                    # [taps, C], [-1] = now
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(k))
                      + p["conv_b"].astype(F32))
    x = xbc[:, :e].reshape(s, heads, -1)
    b = xbc[:, e:e + gn].reshape(s, groups, n)
    cn = xbc[:, e + gn:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    end, y = jax.lax.scan(
        ssd_token(a, p["d_skip"].astype(F32), heads // groups),
        jnp.zeros((heads, x.shape[-1], n), F32), (x, dt, b, cn))
    norm_groups = 1 if variant == "one_norm_group" else groups
    gated = (y.reshape(s, e) * jax.nn.silu(z)).reshape(s, norm_groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True)
        + c["layer_norm_epsilon"])
    normed = normed.reshape(s, e) * p["ssd_norm"].astype(F32)
    return quant(normed) @ quant(p["w_out"].astype(F32)), end


def attention_layer(p, u, q_block: int, quant):
    """Softmax GQA without position."""
    q = jnp.einsum("sd,dhk->shk", quant(u), quant(p["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", quant(u), quant(p["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", quant(u), quant(p["wv"].astype(F32)))
    o = attention(quant(q), quant(k), quant(v), q_block)
    return jnp.einsum("shk,hkd->sd", quant(o), quant(p["wo"].astype(F32)))


def routing(p, u, c: dict) -> jax.Array:
    """[S, published experts] float32: a chosen expert's weight, 0 for every
    other."""
    s = jax.nn.sigmoid(u @ p["router"].astype(F32))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           c["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=1)
    w = s * chosen
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return c["routed_scaling_factor"] * w


def expert_layer(p, u, c: dict, quant, variant: str = "model"):
    """The held experts' part of the routed sum, behind the latent
    projections, and the shared expert on ``u`` itself."""
    held, first = c["n_routed_experts"], c["expert_offset"]
    shared = quant(relu2(quant(u) @ quant(p["shared"]["up"].astype(F32)),
                         variant)) @ quant(p["shared"]["down"].astype(F32))
    if variant == "no_experts":
        return shared
    weight = routing(p, u, c)[:, first:first + held]
    r = p["latent_down"].shape[1]
    latent = u[:, :r] if variant == "experts_read_hidden" \
        else quant(u) @ quant(p["latent_down"].astype(F32))

    (ups, i), (downs, _) = p["up"], p["down"]

    def one(acc, xs):
        w_e, e = xs
        inner = relu2(quant(latent) @ quant(ups[i, e].astype(F32)), variant)
        return acc + w_e[:, None] * (quant(inner)
                                     @ quant(downs[i, e].astype(F32))), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (weight.T, jnp.arange(held)))
    return quant(routed) @ quant(p["latent_up"].astype(F32)) + shared


def _layers(params, tokens, c: dict, quant, remat: bool, variant: str):
    """tokens [S] -> (the last layer's output [S, D], every Mamba layer's
    state after the last token [Lm, H, P, N])."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}: one of {VARIANTS}")
    eps = c["layer_norm_epsilon"]
    x = params["embed"].astype(F32)[tokens]
    qb = q_block_for(tokens.shape[0])
    ends = []
    for letter, norm, p in blocks(params, c["layers_held"]):
        def layer(x, norm=norm, p=p, letter=letter):
            u = rmsnorm(x, norm.astype(F32), eps)
            if letter == "M":
                f, end = mamba_layer(p, u, c, quant, variant)
                if variant == "no_mamba":
                    f = jnp.zeros_like(f)
                return x + f, end
            if letter == "*":
                f = attention_layer(p, u, qb, quant)
                return (x if variant == "no_attention" else x + f), None
            return x + expert_layer(p, u, c, quant, variant), None

        x, end = (jax.checkpoint(layer) if remat else layer)(x)
        if end is not None:
            ends.append(end)
    return x, jnp.stack(ends)


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False,
                  variant: str = "model"):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    x, _ = _layers(params, tokens, c, quant, remat, variant)
    return rmsnorm(x, params["final_norm"].astype(F32),
                   c["layer_norm_epsilon"])


def carried_states(params, tokens, c: dict):
    """tokens [S] -> the SSD state every Mamba layer holds after the last
    token, [Lm, H, P, N] float32: what the program's ``ssd_state`` entry is
    held to (transposed: the program's lies [N, P])."""
    return _layers(params, tokens, c, same, False, "model")[1]


def logits(params, tokens, c: dict, quant=same, last: int | None = None,
           variant: str = "model"):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant, variant=variant)
    if last is not None:
        x = x[-last:]
    return quant(x) @ quant(params["lm_head"].astype(F32))


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets. (No cell trains this architecture: the program's SSD kernels
    have no backward.)"""
    x = hidden_states(params, tokens[:-1], c, quant, remat=remat)
    lg = quant(x) @ quant(params["lm_head"].astype(F32))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
