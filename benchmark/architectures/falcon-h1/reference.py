"""The plain reference: a Falcon-H1 decoder's forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, written from the model's
own ``config.json`` (``model_type`` ``falcon_h1``), the Falcon-H1 report
(arXiv:2507.22448), Mamba-2 / SSD (arXiv:2405.21060) and the public
``modeling_falcon_h1.py`` of ``transformers``. No kernels, no cache, no
batching, no chunked form, and nothing imported from ``kubeflow_tpu``: it
reads the same weight arrays the program was handed.

Per layer, ``x`` its input, ``h = RMSNorm(x; ln1)``, per token ``t``:

- **Attention branch**: ``q, k, v = (attention_in_multiplier h) Wq, Wk, Wv``
  (no bias); ``k <- key_multiplier k``; RoPE (``rope_theta``, a head's halves
  paired) on q and k; causal softmax attention at scale ``head_dim ** -0.5``,
  ONE softmax over the whole causal context, query head ``i`` reading KV head
  ``i // (heads / kv_heads)``; ``attn = attention_out_multiplier (o Wo)``.
- **SSD branch**: ``[z | xBC | dt] = ((ssm_in_multiplier h) W_in) * m``, ``m``
  scaling the column blocks ``z, x, B, C, dt`` by ``ssm_multipliers[0..4]``;
  ``xBC <- SiLU(conv1d(xBC) + bias)``, causal and depthwise over
  ``mamba_d_conv`` taps; ``x`` to ``mamba_n_heads`` heads of
  ``mamba_d_head``, ``B`` and ``C`` to ``mamba_n_groups`` groups of
  ``mamba_d_state``, head ``j`` in group ``j // (heads / groups)``; ``dt[j] <-
  softplus(dt[j] + dt_bias[j])``, ``a[j] = exp(-exp(A_log[j]) dt[j])``; with
  ``S`` ``[P, N]`` a head, float32, zero before the first token, **token by
  token** (a ``lax.scan`` over POSITIONS that carries ``S``: NOT the blocked
  form the program runs, so that the program's chunking and its carried state
  are what is tested): ``S_t = a_t S_(t-1) + dt_t x_t (x) B_t``, ``y_t = S_t
  C_t + D x_t``; then (``mamba_rms_norm``, ``mamba_norm_before_gate`` false)
  ``y <- GroupRMSNorm(y * SiLU(z))`` over ``mamba_n_groups`` groups of
  channels with one weight a channel; ``ssm = ssm_out_multiplier (y W_out)``.
- ``x <- x + attn + ssm``; ``x <- x + down_multiplier ((up(h2) *
  SiLU(gate_multiplier gate(h2))) W_down)`` with ``h2 = RMSNorm(x; ln2)``.
- Tokens enter as ``embedding_multiplier E[id]``; logits are
  ``lm_head_multiplier (RMSNorm(x; final) W_head)``.

Every multiplier stands where the published forward has it. Every caller
traces it under ``jax.default_matmul_precision("highest")``.

Departures from the published modeling file, each one a reader can check
there and none for arithmetic: the tree holds ``wq`` / ``wk`` / ``wv`` [D,
heads, Dh] and ``wo`` [heads, Dh, D] (the same products as the flat
matrices) and the in-projection as its column blocks ``w_z``, ``w_xbc`` and
``w_dt`` (put side by side here into the one ``W_in``); the state lies ``[P, N]`` as published while the program's lies
``[N, P]``; a layer's weights are upcast where it uses them; attention takes
its queries in blocks against the whole context; the head multiplies a block
of the vocabulary at a time.

``quant`` is the control's hook, not part of the model: it is applied to both
operands of every matrix product with a weight and of the attention's two
products (the recurrence's own sums stay in float32).

``BLIND`` is the tests' and the chip proof's hook, not part of the model
either: ``hidden_states(..., blind="attention" | "ssd")`` zeroes that
branch's output, so that a comparison which still passes is shown blind to
the branch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import (
    F32, attention, q_block_for, rmsnorm, rope, same,
)

HEAD_BLOCKS = 8
BLIND = (None, "attention", "ssd")


def layer_of(params: dict, i: int) -> dict:
    return jax.tree.map(lambda a: a[i], params["layers"])


def mlp(p, x, c: dict, quant):
    gate_m, down_m = c["mlp_multipliers"]
    gate = jax.nn.silu(gate_m * (quant(x) @ quant(p["gate"].astype(F32))))
    up = quant(x) @ quant(p["up"].astype(F32))
    return down_m * (quant(gate * up) @ quant(p["down"].astype(F32)))


def attention_branch(p, h, positions, c: dict, q_block: int, quant):
    y = quant(c["attention_in_multiplier"] * h)
    q = jnp.einsum("sd,dhk->shk", y, quant(p["wq"].astype(F32)))
    k = jnp.einsum("sd,dhk->shk", y, quant(p["wk"].astype(F32)))
    v = jnp.einsum("sd,dhk->shk", y, quant(p["wv"].astype(F32)))
    k = c["key_multiplier"] * k
    theta = float(c["rope_theta"])      # 1e11: past a 32-bit whole number
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    o = attention(quant(q), quant(k), quant(v), q_block)
    return c["attention_out_multiplier"] * jnp.einsum(
        "shk,hkd->sd", quant(o), quant(p["wo"].astype(F32)))


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


def ssd_branch(p, h, c: dict, quant):
    """The SSD mixer on ``h`` [S, D]: (out [S, D], the state after the last
    token [H, P, N])."""
    s = h.shape[0]
    e, heads, groups, n = (c["mamba_d_ssm"], c["mamba_n_heads"],
                           c["mamba_n_groups"], c["mamba_d_state"])
    gn = groups * n
    m_z, m_x, m_b, m_c, m_dt = c["ssm_multipliers"]
    w_in = jnp.concatenate([p[k].astype(F32)
                            for k in ("w_z", "w_xbc", "w_dt")], axis=1)
    proj = quant(c["ssm_in_multiplier"] * h) @ quant(w_in)
    z = m_z * proj[:, :e]
    xbc = jnp.concatenate([m_x * proj[:, e:2 * e],
                           m_b * proj[:, 2 * e:2 * e + gn],
                           m_c * proj[:, 2 * e + gn:2 * e + 2 * gn]], axis=1)
    dt = m_dt * proj[:, 2 * e + 2 * gn:]
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
    gated = (y.reshape(s, e) * jax.nn.silu(z)).reshape(s, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + c["rms_norm_eps"])
    normed = normed.reshape(s, e) * p["ssd_norm"].astype(F32)
    out = c["ssm_out_multiplier"] * (
        quant(normed) @ quant(p["w_out"].astype(F32)))
    return out, end


def layer(p, x, positions, c: dict, q_block: int, quant, blind=None):
    """One block: (its output [S, D], its SSD state after the last token)."""
    eps = c["rms_norm_eps"]
    h = rmsnorm(x, p["ln1"].astype(F32), eps)
    attn = attention_branch(p["parallel"], h, positions, c, q_block, quant)
    mixed, end = ssd_branch(p["parallel"], h, c, quant)
    if blind == "attention":
        attn = jnp.zeros_like(attn)
    if blind == "ssd":
        mixed = jnp.zeros_like(mixed)
    x = x + attn + mixed
    return x + mlp(p["mlp"], rmsnorm(x, p["ln2"].astype(F32), eps), c,
                   quant), end


def _blocks(params, tokens, c: dict, quant, remat: bool, blind):
    """tokens [S] -> (the last block's output [S, D], every layer's SSD state
    after the last token [L, H, P, N])."""
    positions = jnp.arange(tokens.shape[0])
    x = c["embedding_multiplier"] * params["embed"][tokens].astype(F32)
    qb = q_block_for(tokens.shape[0])

    def body(x, p):
        return layer(p, x, positions, c, qb, quant, blind)

    if remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, x, params["layers"])


def hidden_states(params, tokens, c: dict, quant=same, remat: bool = False,
                  blind=None):
    """tokens [S] -> final-norm hidden states [S, D], float32."""
    if blind not in BLIND:
        raise ValueError(f"blind is one of {BLIND}")
    x, _ = _blocks(params, tokens, c, quant, remat, blind)
    return rmsnorm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])


def carried_states(params, tokens, c: dict):
    """tokens [S] -> the SSD state every layer holds after the last token,
    [L, H, P, N] float32: what the program's ``ssd_state`` entry is held to
    (transposed: the program's lies [N, P])."""
    return _blocks(params, tokens, c, same, False, None)[1]


def head(params, x, c: dict, quant):
    """``lm_head_multiplier (x W_head)``, ``x`` [S, D], a block of the
    vocabulary at a time."""
    w = params["lm_head"]
    block = w.shape[1] // HEAD_BLOCKS if w.shape[1] % HEAD_BLOCKS == 0 \
        else w.shape[1]
    out = jax.lax.map(
        lambda i: quant(x) @ quant(jax.lax.dynamic_slice_in_dim(
            w, i * block, block, 1).astype(F32)),
        jnp.arange(w.shape[1] // block))
    return c["lm_head_multiplier"] * jnp.moveaxis(out, 0, 1).reshape(
        x.shape[0], -1)


def logits(params, tokens, c: dict, quant=same, last: int | None = None,
           blind=None):
    """tokens [S] -> logits [S or last, V] (the last ``last`` positions)."""
    x = hidden_states(params, tokens, c, quant, blind=blind)
    if last is not None:
        x = x[-last:]
    return head(params, x, c, quant)


def sequence_nll(params, tokens, c: dict, quant=same, remat: bool = True):
    """tokens [S + 1] -> summed next-token negative log-likelihood over the
    S targets. (No cell trains this architecture: the program's SSD kernels
    have no backward.)"""
    lg = head(params, hidden_states(params, tokens[:-1], c, quant,
                                    remat=remat), c, quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
