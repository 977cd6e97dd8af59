"""The parameter tree of a Falcon-H1 decoder as the program's decoder expects
it (``kubeflow_tpu/models/decoder.py``): ONE group ``layers`` of alike blocks,
each block's operator the dict ``parallel``: an attention operator's leaves
(``wq`` / ``wk`` / ``wv`` [D, heads, Dh], ``wo`` [heads, Dh, D]) beside an SSD
mixer's (``w_z`` [D, E], ``w_xbc`` [D, C], ``w_dt`` [D, H]: the in-projection
[D, E + C + H] held as its three column blocks, gate ``z``, ``[x | B | C]``
and a step a head, the same products; ``conv`` [taps, C] with
``[-1]`` the current position, ``conv_b``; ``a_log``, ``d_skip``, ``dt_bias``
[H]; ``ssd_norm`` [E]; ``w_out`` [E, D]), the MLP and the two norms stacked
over the layers; an untied head.

**Scales that leave every branch visible.** The model's multipliers are muP
constants that TRAINED weights compensate. Drawn at the plain ``1 /
sqrt(fan_in)``, the keys would be scaled by 0.011 (uniform attention), the
MLP's output by 0.011 and the mixers' by 0.04 and 0.09 beside an embedding
scaled by 5.66: logits could not tell a broken branch from a sound one. So
every matrix that a multiplier precedes or follows is drawn at ``1 /
(multiplier x sqrt(fan_in))``: the in-projection's column blocks each by
``ssm_in_multiplier`` times their own entry of ``ssm_multipliers``, the
embedding at ``1 / embedding_multiplier``, the head at ``1 /
(lm_head_multiplier sqrt(D))``. After its multiplier each product has the
deviation the plain draw gives in the other configurations. ASSUMED, and
said in the configuration file, as are ``A_log = log U(1, 16)``, ``D = 1``,
``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1]
(Mamba-2's initialisation, arXiv:2405.21060's reference code), the taps at
``1 / sqrt(4)``, the norms at 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

STEP_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)


def ssd_widths(c: dict) -> tuple:
    """(E the mixer's inner width, G x N a group block's width, H heads)."""
    return (c["mamba_d_ssm"], c["mamba_n_groups"] * c["mamba_d_state"],
            c["mamba_n_heads"])


def in_projection(c: dict, key, n: int, dtype) -> dict:
    """The in-projection's leaves ``w_z``, ``w_xbc`` [n, D, E + 2 G N] and
    ``w_dt``: each of the column blocks z, x, B, C, dt at ``1 / (ssm_in x
    its multiplier x sqrt(D))``."""
    d = c["hidden_size"]
    e, gn, h = ssd_widths(c)
    widths = (e, e, gn, gn, h)                      # z, x, B, C, dt
    keys = jax.random.split(key, len(widths))
    z, x, b, cc, dt = (
        stacked_normal(k, (n,), (d, w),
                       1.0 / (c["ssm_in_multiplier"] * m * math.sqrt(d)),
                       dtype)
        for k, w, m in zip(keys, widths, c["ssm_multipliers"]))
    return {"w_z": z, "w_xbc": jnp.concatenate([x, b, cc], axis=-1),
            "w_dt": dt}


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``)."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    m, taps = c["intermediate_size"], c["mamba_d_conv"]
    e, gn, sh = ssd_widths(c)
    conv_dim = e + 2 * gn
    a_in, a_out, key_m = (c["attention_in_multiplier"],
                          c["attention_out_multiplier"], c["key_multiplier"])
    gate_m, down_m = c["mlp_multipliers"]
    ks = iter(jax.random.split(key, 14))
    lead = (n,)
    step = jnp.exp(jax.random.uniform(
        next(ks), (n, sh), jnp.float32, *(math.log(x) for x in STEP_RANGE)))
    parallel = {
        "wq": stacked_normal(next(ks), lead, (d, h, dh),
                             1.0 / (a_in * math.sqrt(d)), dtype),
        "wk": stacked_normal(next(ks), lead, (d, kv, dh),
                             1.0 / (a_in * key_m * math.sqrt(d)), dtype),
        "wv": stacked_normal(next(ks), lead, (d, kv, dh),
                             1.0 / (a_in * math.sqrt(d)), dtype),
        "wo": stacked_normal(next(ks), lead, (h, dh, d),
                             1.0 / (a_out * math.sqrt(h * dh)), dtype),
        **in_projection(c, next(ks), n, dtype),
        "conv": stacked_normal(next(ks), lead, (taps, conv_dim),
                               taps ** -0.5, dtype),
        "conv_b": jnp.zeros((n, conv_dim), dtype),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (n, sh), jnp.float32, *A_RANGE)).astype(dtype),
        "d_skip": jnp.ones((n, sh), dtype),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "ssd_norm": jnp.ones((n, e), dtype),
        "w_out": stacked_normal(
            next(ks), lead, (e, d),
            1.0 / (c["ssm_out_multiplier"] * math.sqrt(e)), dtype),
    }
    mlp = {
        "gate": stacked_normal(next(ks), lead, (d, m),
                               1.0 / (gate_m * math.sqrt(d)), dtype),
        "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
        "down": stacked_normal(next(ks), lead, (m, d),
                               1.0 / (down_m * math.sqrt(m)), dtype),
    }
    return {
        "embed": stacked_normal(next(ks), (), (v, d),
                                1.0 / c["embedding_multiplier"], dtype),
        "layers": {"parallel": parallel, "mlp": mlp,
                   "ln1": jnp.ones((n, d), dtype),
                   "ln2": jnp.ones((n, d), dtype)},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stacked_normal(
            next(ks), (), (d, v),
            1.0 / (c["lm_head_multiplier"] * math.sqrt(d)), dtype),
    }
