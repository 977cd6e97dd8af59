"""Test-only: the parameter tree of the rehearsal's Gemma-shaped decoder as
the program's decoder expects it: no output head (it is the embedding,
transposed), and norm weights that are offsets from one, drawn away from zero
so that a reference that forgot the ``1 +`` could not pass.
"""

from __future__ import annotations

import jax

from benchmark.weights import stacked_normal


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    d, v = c["hidden_size"], c["vocab_size"]
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    m, lead = c["intermediate_size"], (c["num_hidden_layers"],)
    ks = iter(jax.random.split(key, 11))

    def leaf(shape, scale, lead=lead):
        return stacked_normal(next(ks), lead, shape, scale, dtype)

    return {
        "embed": leaf((v, d), 1.0, lead=()),
        "layers": {
            "attn": {"wq": leaf((d, h, dh), d ** -0.5),
                     "wk": leaf((d, kv, dh), d ** -0.5),
                     "wv": leaf((d, kv, dh), d ** -0.5),
                     "wo": leaf((h, dh, d), (h * dh) ** -0.5)},
            "mlp": {"gate": leaf((d, m), d ** -0.5),
                    "up": leaf((d, m), d ** -0.5),
                    "down": leaf((m, d), m ** -0.5)},
            "ln1": leaf((d,), 0.1), "ln2": leaf((d,), 0.1)},
        "final_norm": leaf((d,), 0.1, lead=()),
    }
