"""The parameter tree of a Mistral / Mixtral decoder as the program's decoder
expects it (``kubeflow_tpu/models/decoder.py``: layers stacked on a leading
axis), from a key. The same key splits in the same order as before the
architecture seam, so a seed gives the weights it always gave.

Scales are the usual ones (1/sqrt(fan_in); the embedding at 1): what matters
to a benchmark is that activations stay O(1) through the depth, so that a
lower-precision run is told apart by its rounding and not drowned by scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the published sizes in ``c`` (keys of the
    model's ``config.json``)."""
    d, v = c["hidden_size"], c["vocab_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    m, n_layers = c["intermediate_size"], c["num_hidden_layers"]
    e = c.get("num_local_experts", 0)
    ks = iter(jax.random.split(key, 12))
    lead = (n_layers,)
    attn = {
        "wq": stacked_normal(next(ks), lead, (d, h, dh), d ** -0.5, dtype),
        "wk": stacked_normal(next(ks), lead, (d, kv, dh), d ** -0.5, dtype),
        "wv": stacked_normal(next(ks), lead, (d, kv, dh), d ** -0.5, dtype),
        "wo": stacked_normal(next(ks), lead, (h, dh, d), (h * dh) ** -0.5,
                              dtype),
    }
    if e:
        mlp = {
            "router": stacked_normal(next(ks), lead, (d, e), d ** -0.5,
                                      dtype),
            "gate": stacked_normal(next(ks), lead + (e,), (d, m), d ** -0.5,
                                    dtype),
            "up": stacked_normal(next(ks), lead + (e,), (d, m), d ** -0.5,
                                  dtype),
            "down": stacked_normal(next(ks), lead + (e,), (m, d), m ** -0.5,
                                    dtype),
        }
    else:
        mlp = {
            "gate": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype),
        }
    params = {
        "embed": stacked_normal(next(ks), (), (v, d), 1.0, dtype),
        "layers": {"attn": attn, "mlp": mlp,
                   "ln1": jnp.ones((n_layers, d), dtype),
                   "ln2": jnp.ones((n_layers, d), dtype)},
        "final_norm": jnp.ones((d,), dtype),
    }
    if not c.get("tie_word_embeddings", False):
        params["lm_head"] = stacked_normal(next(ks), (), (d, v), d ** -0.5,
                                            dtype)
    return params
