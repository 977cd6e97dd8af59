"""The parameter tree of an EXAONE-MoE decoder as the program's decoder
expects it (``kubeflow_tpu/models/decoder.py``): the leading dense layers as
the group ``dense_layers``, the expert layers as ``layers`` (whole periods of
their pattern of kinds) and, where the cut leaves part of a period behind
them, ``layers_rest``; in a group the norms and the feed-forward leaves are
stacked over its layers in order, an operator's leaves over the layers of ITS
kind (``window`` over the window layers, ``attn`` over the global ones: the
same six leaves). An expert layer's stack is the experts HELD
(``num_experts``: one chip's share), its router and bias keep every output
(``num_experts_routed``, the published ``num_experts``). The head is a matrix of its own (untied) over the
vocabulary rows held.

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at
1), so activations stay O(1) through the depth. ASSUMED, and said in the
configuration file: the router's correction bias is a trained buffer in the
published model; here it is drawn from the seed, normal with deviation 0.05
beside sigmoid scores spread over 0.1-0.9, so that choosing by ``s + b``
differs from choosing by ``s`` in some tokens and a program that dropped
``b``, or weighted by it, would be caught. The draw is STRATIFIED
(``balanced_bias``): every seed and every layer gets the same multiset of
values, the normal's quantiles, and every chip's block of consecutive experts
one value from each stratum of them, in another order. A bias of one
deviation nearly doubles how often an expert is chosen (top-8 of 128 sits at
a score of 0.82, where 0.05 is a third of a deviation of the logit), so with
independent draws the 16 experts a chip holds are chosen for 10.4-14.4% of
the rows by seed where the published bias is TRAINED to level the experts'
load, and a decode step's time follows (PERF.md section 6, PR 40).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05
OPERATOR = {"sliding_attention": "window", "full_attention": "attn"}


def attention_tree(c: dict, key, n: int, dtype) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ks = iter(jax.random.split(key, 4))
    return {
        "wq": stacked_normal(next(ks), (n,), (d, h, dh), d ** -0.5, dtype),
        "wk": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wv": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wo": stacked_normal(next(ks), (n,), (h, dh, d),
                             (h * dh) ** -0.5, dtype),
        "q_norm": jnp.ones((n, dh), dtype),
        "k_norm": jnp.ones((n, dh), dtype),
    }


def mlp_tree(key, lead: tuple, d: int, m: int, dtype) -> dict:
    ks = iter(jax.random.split(key, 3))
    return {"gate": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype)}


def expert_tree(c: dict, key, n: int, dtype) -> dict:
    d, m = c["hidden_size"], c["moe_intermediate_size"]
    kr, kb, ke, ks = jax.random.split(key, 4)
    return {
        "router": stacked_normal(kr, (n,), (d, c["num_experts_routed"]),
                                 d ** -0.5, dtype),
        "router_bias": balanced_bias(kb, n, c["num_experts_routed"],
                                     c["num_experts"]),
        **mlp_tree(ke, (n, c["num_experts"]), d, m, dtype),
        "shared": mlp_tree(ks, (n,), d, c["num_shared_experts"] * m, dtype),
    }


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


def group(c: dict, kinds: list, key, mlp: dict, dtype) -> dict:
    """One stacked group of the program's tree for layers of ``kinds``."""
    d, n = c["hidden_size"], len(kinds)
    out = {"mlp": mlp, "ln1": jnp.ones((n, d), dtype),
           "ln2": jnp.ones((n, d), dtype)}
    for i, kind in enumerate(sorted(set(kinds))):
        out[OPERATOR[kind]] = attention_tree(
            c, jax.random.fold_in(key, i), kinds.count(kind), dtype)
    return out


def whole_periods(kinds: list) -> int:
    """The layers of ``kinds`` that are whole periods of its shortest
    period (the decoder scans those as one group and what is left behind
    them as another: ``decoder._periodic``)."""
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if all(kinds[i] == kinds[i - p] for i in range(p, n)))
    return n // p * p


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``num_hidden_layers`` and ``layer_types_held`` are the
    layers held, ``num_experts`` the experts, ``vocab_size`` the vocabulary
    rows)."""
    d, v = c["hidden_size"], c["vocab_size"]
    kinds, n_dense = c["layer_types_held"], c["first_k_dense_replace"]
    k_embed, k_head, k_dense, k_dm, k_exp, k_em, k_rest, k_rm = \
        jax.random.split(key, 8)
    sparse = kinds[n_dense:]
    whole = whole_periods(sparse)
    params = {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "dense_layers": group(c, kinds[:n_dense], k_dense, mlp_tree(
            k_dm, (n_dense,), d, c["intermediate_size"], dtype), dtype),
        "layers": group(c, sparse[:whole], k_exp,
                        expert_tree(c, k_em, whole, dtype), dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    if whole < len(sparse):
        params["layers_rest"] = group(
            c, sparse[whole:], k_rest,
            expert_tree(c, k_rm, len(sparse) - whole, dtype), dtype)
    if not c["tie_word_embeddings"]:
        params["lm_head"] = stacked_normal(k_head, (), (d, v), d ** -0.5,
                                            dtype)
    return params
