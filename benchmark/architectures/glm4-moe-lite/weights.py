"""The parameter tree of a GLM-4.7-Flash decoder as the program's decoder
expects it (``kubeflow_tpu/models/decoder.py``): a leading group
``dense_layers`` and the expert group ``layers``, each stacked on a leading
axis; latent attention's seven leaves; an expert layer's router, its
correction bias, the routed experts and the shared one.

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at 1),
so activations stay O(1) through the depth. ASSUMED, and said in the
configuration file: the router's correction bias ``b`` is a trained buffer in
the published model; here it is drawn from the seed, normal with deviation
0.05 beside sigmoid scores spread over 0.1-0.9, so that choosing by ``s + b``
differs from choosing by ``s`` in some tokens and a program that dropped
``b``, or weighted by it, would be caught.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05


def attention_tree(c: dict, key, lead: tuple, dtype) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    q, r = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    ks = iter(jax.random.split(key, 5))
    return {
        "wqa": stacked_normal(next(ks), lead, (d, q), d ** -0.5, dtype),
        "q_norm": jnp.ones(lead + (q,), dtype),
        "wqb": stacked_normal(next(ks), lead, (q, h, nope + rope), q ** -0.5,
                               dtype),
        "wkva": stacked_normal(next(ks), lead, (d, r + rope), d ** -0.5,
                                dtype),
        "kv_norm": jnp.ones(lead + (r,), dtype),
        "wkvb": stacked_normal(next(ks), lead, (r, h, nope + v), r ** -0.5,
                                dtype),
        "wo": stacked_normal(next(ks), lead, (h, v, d), (h * v) ** -0.5,
                              dtype),
    }


def mlp_tree(key, lead: tuple, d: int, m: int, dtype) -> dict:
    ks = iter(jax.random.split(key, 3))
    return {"gate": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype)}


def expert_tree(c: dict, key, lead: tuple, dtype) -> dict:
    d, m, e = c["hidden_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    kr, kb, ke, ks = jax.random.split(key, 4)
    n = lead[0]
    return {
        "router": stacked_normal(kr, lead, (d, e), d ** -0.5, dtype),
        "router_bias": BIAS_DEVIATION * jax.random.normal(
            kb, (n, e), jnp.float32),
        **mlp_tree(ke, lead + (e,), d, m, dtype),
        "shared": mlp_tree(ks, lead, d, c["n_shared_experts"] * m, dtype),
    }


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``num_hidden_layers`` is the depth held)."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    k_embed, k_head, k_da, k_dm, k_ea, k_em = jax.random.split(key, 6)

    def group(n, k_attn, mlp):
        return {"attn": attention_tree(c, k_attn, (n,), dtype), "mlp": mlp,
                "ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype)}

    params = {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "dense_layers": group(n_dense, k_da, mlp_tree(
            k_dm, (n_dense,), d, c["intermediate_size"], dtype)),
        "layers": group(n_moe, k_ea, expert_tree(c, k_em, (n_moe,), dtype)),
        "final_norm": jnp.ones((d,), dtype),
    }
    if not c.get("tie_word_embeddings", False):
        params["lm_head"] = stacked_normal(k_head, (), (d, v), d ** -0.5,
                                            dtype)
    return params
