"""The parameter tree of an LFM2-MoE decoder as the program's decoder expects
it (``kubeflow_tpu/models/decoder.py``): a leading group ``dense_layers``
(conv blocks with a plain MLP) and the expert group ``layers``; in a group
the norms and the feed-forward leaves are stacked over its layers in order,
an operator's leaves over the layers of ITS kind (``attn`` over the
attention layers, ``conv`` over the conv layers). The head is the embedding
(tied).

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at 1;
the convolution's taps at 1/sqrt(taps), so the filtered row keeps the gated
row's scale), so activations stay O(1) through the depth. ASSUMED, and said
in the configuration file: ``expert_bias`` is a trained buffer in the
published model; here it is drawn from the seed, normal with deviation 0.05
beside sigmoid scores spread over 0.1-0.9, so that choosing by ``s + b``
differs from choosing by ``s`` in some tokens and a program that dropped
``b``, or weighted by it, would be caught.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05


def attention_tree(c: dict, key, n: int, dtype) -> dict:
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    dh = d // h
    ks = iter(jax.random.split(key, 4))
    return {
        "wq": stacked_normal(next(ks), (n,), (d, h, dh), d ** -0.5, dtype),
        "wk": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wv": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wo": stacked_normal(next(ks), (n,), (h, dh, d), d ** -0.5, dtype),
        "q_norm": jnp.ones((n, dh), dtype),
        "k_norm": jnp.ones((n, dh), dtype),
    }


def conv_tree(c: dict, key, n: int, dtype) -> dict:
    """``win`` [D, 3, D]: the in-projection's three parts B, C, u in that
    order on the middle axis; ``taps`` [taps, D]: ``taps[-1]`` multiplies
    the current position; ``wout`` [D, D]."""
    d, taps = c["hidden_size"], c["conv_L_cache"]
    ki, kt, ko = jax.random.split(key, 3)
    return {
        "win": stacked_normal(ki, (n,), (d, 3, d), d ** -0.5, dtype),
        "taps": stacked_normal(kt, (n,), (taps, d), taps ** -0.5, dtype),
        "wout": stacked_normal(ko, (n,), (d, d), d ** -0.5, dtype),
    }


def mlp_tree(key, lead: tuple, d: int, m: int, dtype) -> dict:
    ks = iter(jax.random.split(key, 3))
    return {"gate": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype)}


def expert_tree(c: dict, key, n: int, dtype) -> dict:
    d, m, e = c["hidden_size"], c["moe_intermediate_size"], c["num_experts"]
    kr, kb, ke = jax.random.split(key, 3)
    return {
        "router": stacked_normal(kr, (n,), (d, e), d ** -0.5, dtype),
        "router_bias": BIAS_DEVIATION * jax.random.normal(
            kb, (n, e), jnp.float32),
        **mlp_tree(ke, (n, e), d, m, dtype),
    }


def group(c: dict, kinds: list, key, mlp: dict, dtype) -> dict:
    """One stacked group of the program's tree for layers of ``kinds``."""
    d, n = c["hidden_size"], len(kinds)
    k_attn, k_conv = jax.random.split(key)
    out = {"mlp": mlp, "ln1": jnp.ones((n, d), dtype),
           "ln2": jnp.ones((n, d), dtype)}
    if "full_attention" in kinds:
        out["attn"] = attention_tree(
            c, k_attn, kinds.count("full_attention"), dtype)
    if "conv" in kinds:
        out["conv"] = conv_tree(c, k_conv, kinds.count("conv"), dtype)
    return out


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``num_hidden_layers`` and ``layer_types_held`` are the
    layers held)."""
    d, v = c["hidden_size"], c["vocab_size"]
    kinds, n_dense = c["layer_types_held"], c["num_dense_layers"]
    k_embed, k_head, k_dense, k_dm, k_exp, k_em = jax.random.split(key, 6)
    params = {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "dense_layers": group(c, kinds[:n_dense], k_dense, mlp_tree(
            k_dm, (n_dense,), d, c["intermediate_size"], dtype), dtype),
        "layers": group(c, kinds[n_dense:], k_exp, expert_tree(
            c, k_em, len(kinds) - n_dense, dtype), dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    if not c["tie_word_embeddings"]:
        params["lm_head"] = stacked_normal(k_head, (), (d, v), d ** -0.5,
                                            dtype)
    return params
