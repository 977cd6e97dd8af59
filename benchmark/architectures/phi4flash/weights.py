"""The parameter tree of a Phi-4-mini-flash decoder as the program's decoder
expects it (``kubeflow_tpu/models/decoder.py``): THREE groups, each whole
periods of one pattern and one scan, read off ``layer_types``: ``layers``
(Mamba-1, window attention) up to the pair ``layers_rest`` (Mamba-1, the one
full-attention layer), ``layers_rest2`` (gated memory unit, cross attention)
for the rest. In a
group the norms (weight and bias) and the MLP are stacked over its layers in
order, an operator's leaves over the layers of ITS kind (``ssm``; ``window``
/ ``attn`` / ``cross``: differential attention's flat projections, biases,
four lambda vectors, the pair norm's weight and ``lambda_init``; ``gmu``).
The head is the embedding (tied).

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms'
weights at 1, biases at 0; a convolution's taps at 1/sqrt(taps)), so
activations stay O(1) through the depth. ASSUMED, and said in the
configuration file: ``A_log = log(1 .. d_state)`` a channel, ``D = 1`` and
``dt_bias`` the inverse softplus of a step log-uniform in [1e-3, 1e-1]
(Mamba's initialisation, arXiv:2312.00752 section 3.6 and its reference
code); the four lambda vectors normal at a deviation of 0.1 and
``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` by the layer's index ``i``
(arXiv:2410.05258, section 2.1).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

STEP_RANGE = (1e-3, 1e-1)
LAMBDA_DEVIATION = 0.1


def mlp_tree(key, n: int, d: int, m: int, dtype) -> dict:
    ks = iter(jax.random.split(key, 3))
    return {"gate": stacked_normal(next(ks), (n,), (d, m), d ** -0.5, dtype),
            "up": stacked_normal(next(ks), (n,), (d, m), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), (n,), (m, d), m ** -0.5, dtype)}


def ssm_tree(c: dict, key, n: int, dtype) -> dict:
    d, e = c["hidden_size"], c["expand"] * c["hidden_size"]
    ns, r, taps = c["d_state"], c["dt_rank"], c["d_conv"]
    ks = iter(jax.random.split(key, 7))
    step = jnp.exp(jax.random.uniform(
        next(ks), (n, e), jnp.float32, *(math.log(v) for v in STEP_RANGE)))
    return {
        "wu": stacked_normal(next(ks), (n,), (d, e), d ** -0.5, dtype),
        "wz": stacked_normal(next(ks), (n,), (d, e), d ** -0.5, dtype),
        "conv": stacked_normal(next(ks), (n,), (taps, e), taps ** -0.5,
                               dtype),
        "conv_b": jnp.zeros((n, e), dtype),
        "wx": stacked_normal(next(ks), (n,), (e, r + 2 * ns), e ** -0.5,
                             dtype),
        "wdt": stacked_normal(next(ks), (n,), (r, e), r ** -0.5, dtype),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, ns + 1, dtype=jnp.float32))[None, :, None],
            (n, ns, e)).astype(dtype),
        "d_skip": jnp.ones((n, e), dtype),
        "wout": stacked_normal(next(ks), (n,), (e, d), e ** -0.5, dtype),
    }


def attention_tree(c: dict, key, depths: list, dtype, cross: bool) -> dict:
    """Differential attention operators at the stack's layers ``depths``."""
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    dh, n = d // h, len(depths)
    ks = iter(jax.random.split(key, 8))
    widths = {"q": h * dh} if cross else {"q": h * dh, "k": kv * dh,
                                          "v": kv * dh}
    out = {}
    for name, w in {**widths, "o": d}.items():
        # q, k and v lie OUT by IN (as the program holds them), o IN by OUT
        fan = h * dh if name == "o" else d
        out["w" + name] = stacked_normal(
            next(ks), (n,), (fan, w) if name == "o" else (w, fan),
            fan ** -0.5, dtype)
        out["b" + name] = jnp.zeros((n, w), dtype)
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        out[name] = stacked_normal(next(ks), (n,), (dh,), LAMBDA_DEVIATION,
                                   dtype)
    out["subln"] = jnp.ones((n, 2 * dh), dtype)
    out["lambda_init"] = 0.8 - 0.6 * jnp.exp(
        -0.3 * jnp.asarray(depths, jnp.float32))
    return out


def gmu_tree(c: dict, key, n: int, dtype) -> dict:
    d, e = c["hidden_size"], c["expand"] * c["hidden_size"]
    k1, k2 = jax.random.split(key)
    return {"w1": stacked_normal(k1, (n,), (d, e), d ** -0.5, dtype),
            "w2": stacked_normal(k2, (n,), (e, d), e ** -0.5, dtype)}


def group_tree(c: dict, key, first: int, n: int, even: str, odd: str,
               dtype) -> dict:
    """Layers ``first .. first + n`` of the stack: the even ones' mixer is
    ``even`` ("ssm" | "gmu"), the odd ones' differential attention under the
    key ``odd``."""
    d = c["hidden_size"]
    k_mlp, k_even, k_odd = jax.random.split(key, 3)
    make = ssm_tree if even == "ssm" else gmu_tree
    norms = {name: (jnp.zeros if name.endswith("_b") else jnp.ones)(
        (n, d), dtype) for name in ("ln1", "ln1_b", "ln2", "ln2_b")}
    return {
        "mlp": mlp_tree(k_mlp, n, d, c["intermediate_size"], dtype), **norms,
        even: make(c, k_even, n // 2, dtype),
        odd: attention_tree(c, k_odd, list(range(first + 1, first + n, 2)),
                            dtype, cross=odd == "cross"),
    }


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json`` and the file's ``assumed`` ones)."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    # the Mamba layer in front of the one full-attention layer starts the
    # second group; the cross-decoder is everything behind that pair
    first = c["layer_types"].index("full_attention") - 1
    k_embed, k0, k1, k2 = jax.random.split(key, 4)
    return {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        "layers": group_tree(c, k0, 0, first, "ssm", "window", dtype),
        "layers_rest": group_tree(c, k1, first, 2, "ssm", "attn", dtype),
        "layers_rest2": group_tree(c, k2, first + 2, n - first - 2, "gmu",
                                   "cross", dtype),
        "final_norm": jnp.ones((d,), dtype),
        "final_norm_b": jnp.zeros((d,), dtype),
    }
