"""The parameter tree of a GLM-5 decoder as the program's decoder expects it
(``kubeflow_tpu/models/decoder.py``): a leading group ``dense_layers`` and
the expert group ``layers``, each stacked on a leading axis; latent
attention's seven leaves and the indexer's five beside them; an expert
layer's router over the PUBLISHED experts, its correction bias, the routed
experts HELD and the shared one; an untied head over the vocabulary rows
held.

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at 1),
so activations stay O(1) through the depth. ASSUMED, and said in the
configuration file: the router's correction bias ``b`` is a trained buffer in
the published model; here it is the SAME multiset of values in every layer
for every seed (the normal's quantiles times ``BIAS_DEVIATION``), placed by
the seed STRATIFIED over the blocks of experts a chip holds
(``balanced_bias``, PR 40's rule), so that the share of a token's choices
that falls on the held experts is every seed's alike; the indexer's key norm
has a bias, drawn with deviation ``INDEX_BIAS_DEVIATION`` so that a program
that dropped it would be seen. ``Wqb`` keeps the plain draw: attention over
2048 keys of unit logits is nearly a mean, and ISSUE 55 held a gain on it
ready should another choice of 2048 keys move the logits too little to be
seen; on the chip both selection controls read five times the program's
largest reading (PERF.md section 2), so none is drawn.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05
INDEX_BIAS_DEVIATION = 0.1


def attention_tree(c: dict, key, lead: tuple, dtype) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    q, r = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    hi, di = c["index_n_heads"], c["index_head_dim"]
    ks = iter(jax.random.split(key, 9))
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
        "wq_idx": stacked_normal(next(ks), lead, (hi * di, q), q ** -0.5,
                                  dtype),
        "wk_idx": stacked_normal(next(ks), lead, (d, di), d ** -0.5, dtype),
        "k_idx_norm": jnp.ones(lead + (di,), dtype),
        "k_idx_bias": stacked_normal(next(ks), lead, (di,),
                                      INDEX_BIAS_DEVIATION, dtype),
        "w_idx": stacked_normal(next(ks), lead, (d, hi), d ** -0.5, dtype),
    }


def mlp_tree(key, lead: tuple, d: int, m: int, dtype) -> dict:
    ks = iter(jax.random.split(key, 3))
    return {"gate": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype)}


def balanced_bias(key, n: int, experts: int, held: int) -> jax.Array:
    """[n, experts] float32 correction biases: in every layer the normal's
    quantiles at (i + 0.5) / experts times ``BIAS_DEVIATION``, the same
    multiset for every seed, placed by the seed so that each block of
    ``held`` consecutive experts (one chip's share of the group) holds one
    value from each of ``held`` strata of the sorted values."""
    chips = experts // held
    sorted_values = BIAS_DEVIATION * jax.scipy.special.ndtri(
        (jnp.arange(chips * held, dtype=jnp.float32) + 0.5) / (chips * held))
    strata = sorted_values.reshape(held, chips)

    def layer(k):
        across, within = jax.random.split(k)
        by_chip = jax.random.permutation(across, strata, axis=1,
                                         independent=True).T
        return jax.random.permutation(within, by_chip, axis=1,
                                      independent=True).reshape(-1)

    return jax.vmap(layer)(jax.random.split(key, n))


def expert_tree(c: dict, key, n: int, dtype) -> dict:
    d, m = c["hidden_size"], c["moe_intermediate_size"]
    routed, held = c["n_routed_experts_published"], c["n_routed_experts"]
    kr, kb, ke, ks = jax.random.split(key, 4)
    return {
        "router": stacked_normal(kr, (n,), (d, routed), d ** -0.5, dtype),
        "router_bias": balanced_bias(kb, n, routed, held),
        **mlp_tree(ke, (n, held), d, m, dtype),
        "shared": mlp_tree(ks, (n,), d, c["n_shared_experts"] * m, dtype),
    }


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``num_hidden_layers`` is the depth held,
    ``n_routed_experts`` the experts held, ``vocab_size`` the rows held)."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    k_embed, k_head, k_da, k_dm, k_ea, k_em = jax.random.split(key, 6)

    def group(n, k_attn, mlp):
        return {"attn": attention_tree(c, k_attn, (n,), dtype), "mlp": mlp,
                "ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype)}

    return {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "dense_layers": group(n_dense, k_da, mlp_tree(
            k_dm, (n_dense,), d, c["intermediate_size"], dtype)),
        "layers": group(n_moe, k_ea, expert_tree(c, k_em, n_moe, dtype)),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stacked_normal(k_head, (), (d, v), d ** -0.5, dtype),
    }
