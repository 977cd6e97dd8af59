"""Weights from ``--seed``, made on the device in ONE jitted call, in the
type they are served or trained in, laid out as the program's decoder expects
its parameters (``kubeflow_tpu/models/decoder.py``: layers stacked on a
leading axis). Both the program and the plain reference are given THESE
arrays; neither is given anything the other made.

Scales are the usual ones (1/sqrt(fan_in); the embedding at 1): what matters
to a benchmark is that activations stay O(1) through the depth, so that a
lower-precision run is told apart by its rounding and not drowned by scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number, beyond 32 bits too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _stacked_normal(key, lead: tuple, shape: tuple, scale: float, dtype):
    """[*lead, *shape] normal(0, scale), one ``shape`` block at a time so the
    float32 draw of a whole stacked leaf (5.6 GB for Mixtral's experts) is
    never alive at once."""
    n = math.prod(lead)
    keys = jax.random.split(key, n)
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32)
                   * scale).astype(dtype), keys)
    return blocks.reshape(*lead, *shape)


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
        "wq": _stacked_normal(next(ks), lead, (d, h, dh), d ** -0.5, dtype),
        "wk": _stacked_normal(next(ks), lead, (d, kv, dh), d ** -0.5, dtype),
        "wv": _stacked_normal(next(ks), lead, (d, kv, dh), d ** -0.5, dtype),
        "wo": _stacked_normal(next(ks), lead, (h, dh, d), (h * dh) ** -0.5,
                              dtype),
    }
    if e:
        mlp = {
            "router": _stacked_normal(next(ks), lead, (d, e), d ** -0.5,
                                      dtype),
            "gate": _stacked_normal(next(ks), lead + (e,), (d, m), d ** -0.5,
                                    dtype),
            "up": _stacked_normal(next(ks), lead + (e,), (d, m), d ** -0.5,
                                  dtype),
            "down": _stacked_normal(next(ks), lead + (e,), (m, d), m ** -0.5,
                                    dtype),
        }
    else:
        mlp = {
            "gate": _stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "up": _stacked_normal(next(ks), lead, (d, m), d ** -0.5, dtype),
            "down": _stacked_normal(next(ks), lead, (m, d), m ** -0.5, dtype),
        }
    params = {
        "embed": _stacked_normal(next(ks), (), (v, d), 1.0, dtype),
        "layers": {"attn": attn, "mlp": mlp,
                   "ln1": jnp.ones((n_layers, d), dtype),
                   "ln2": jnp.ones((n_layers, d), dtype)},
        "final_norm": jnp.ones((d,), dtype),
    }
    if not c.get("tie_word_embeddings", False):
        params["lm_head"] = _stacked_normal(next(ks), (), (d, v), d ** -0.5,
                                            dtype)
    return params


def make_params(c: dict, seed: int, dtype, shardings=None) -> dict:
    """One jitted call; with ``shardings`` every leaf is born in its shards."""
    fn = jax.jit(lambda k: param_tree(c, k, jnp.dtype(dtype)),
                 out_shardings=shardings)
    return fn(seed_key(seed))
