"""The parameter tree of a Solar-Open2 decoder as the program's decoder
expects it (``kubeflow_tpu/models/decoder.py``): ONE group ``layers`` of whole
periods of the pattern (GQA, KDA, KDA, KDA); the norms and the expert leaves
stacked over its layers in order, an operator's leaves over the layers of ITS
kind (``attn`` over the GQA layers: wq, wk, wv, wo and the output gate's
``wgate``; ``linear`` over the KDA layers: the three projections and their
taps, the two low-rank pairs, ``a_log``, ``dt_bias``, ``wb``, ``o_norm``,
``wo``). An expert layer's stack is the experts HELD (``n_routed_experts``:
one chip's share), its router and bias keep every output
(``n_routed_experts_routed``, the published count). The head is a matrix of its
own (untied) over the vocabulary rows held.

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at 1;
a convolution's taps at 1/sqrt(taps)), so activations stay O(1) through the
depth. ASSUMED, and said in the configuration file:

- ``a_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus of a
  step log-uniform in [1e-3, 1e-1] a channel, the FLA initialisation of the
  gated delta rule's decay: at the bias alone a token's decay ``exp(-A dt)``
  lies between 0.999 and 0.2, and the low-rank projection's output (O(1) on
  these weights) moves it a channel a token, some channels far below (a
  factor e^-30 a token where A, the step and the projection are all large):
  the range the program's chunked form has to be safe over.
- the router's correction bias is a trained buffer in the published model;
  here it is drawn from the seed STRATIFIED over the 8 blocks of 40
  consecutive experts (``balanced_bias``, K-EXAONE's rule and reason,
  ``exaone-moe/weights.py``: every seed and layer gets the same multiset of
  values, the normal's quantiles at a deviation of 0.05, and every chip's
  block one value from each stratum, in another order; with independent
  draws the share of rows that fall on the 40 held experts moves by a third
  from seed to seed and a decode step's time follows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05
A_RANGE = (1.0, 16.0)
STEP_RANGE = (1e-3, 1e-1)


def gqa_tree(c: dict, key, n: int, dtype) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ks = iter(jax.random.split(key, 5))
    return {
        "wq": stacked_normal(next(ks), (n,), (d, h, dh), d ** -0.5, dtype),
        "wk": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wv": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wo": stacked_normal(next(ks), (n,), (h, dh, d),
                             (h * dh) ** -0.5, dtype),
        "wgate": stacked_normal(next(ks), (n,), (d, h, dh), d ** -0.5, dtype),
    }


def kda_tree(c: dict, key, n: int, dtype) -> dict:
    lin = c["linear_attn_config"]
    d, h, dk = c["hidden_size"], lin["num_heads"], lin["head_dim"]
    taps, r = lin["short_conv_kernel_size"], c["kda_gate_rank"]
    ks = iter(jax.random.split(key, 14))
    out = {}
    for name in ("q", "k", "v"):
        out["w" + name] = stacked_normal(next(ks), (n,), (d, h, dk),
                                         d ** -0.5, dtype)
        out["conv_" + name] = stacked_normal(next(ks), (n,), (taps, h, dk),
                                             taps ** -0.5, dtype)
    step = jnp.exp(jax.random.uniform(
        next(ks), (n, h, dk), jnp.float32, *jnp.log(jnp.asarray(STEP_RANGE))))
    out.update({
        "wf1": stacked_normal(next(ks), (n,), (d, r), d ** -0.5, dtype),
        "wf2": stacked_normal(next(ks), (n,), (r, h, dk), r ** -0.5, dtype),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (n, h), jnp.float32, *A_RANGE)).astype(dtype),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "wb": stacked_normal(next(ks), (n,), (d, h), d ** -0.5, dtype),
        "wg1": stacked_normal(next(ks), (n,), (d, r), d ** -0.5, dtype),
        "wg2": stacked_normal(next(ks), (n,), (r, h, dk), r ** -0.5, dtype),
        "o_norm": jnp.ones((n, dk), dtype),
        "wo": stacked_normal(next(ks), (n,), (h, dk, d),
                             (h * dk) ** -0.5, dtype),
    })
    return out


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
    routed, held = c["n_routed_experts_routed"], c["n_routed_experts"]
    kr, kb, ke, ks = jax.random.split(key, 4)
    return {
        "router": stacked_normal(kr, (n,), (d, routed), d ** -0.5, dtype),
        "router_bias": balanced_bias(kb, n, routed, held),
        **mlp_tree(ke, (n, held), d, m, dtype),
        "shared": mlp_tree(ks, (n,), d, c["n_shared_experts"] * m, dtype),
    }


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``num_hidden_layers`` and ``gqa_layers_held`` are the
    layers held, ``n_routed_experts`` the experts, ``vocab_size`` the
    vocabulary rows)."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    n_gqa = len(c["gqa_layers_held"])
    k_embed, k_head, k_gqa, k_kda, k_exp = jax.random.split(key, 5)
    return {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "layers": {
            "mlp": expert_tree(c, k_exp, n, dtype),
            "ln1": jnp.ones((n, d), dtype), "ln2": jnp.ones((n, d), dtype),
            "attn": gqa_tree(c, k_gqa, n_gqa, dtype),
            "linear": kda_tree(c, k_kda, n - n_gqa, dtype),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stacked_normal(k_head, (), (d, v), d ** -0.5, dtype),
    }
