"""Operations and bytes a Mistral / Mixtral decoder NEEDS, from a
configuration's published sizes (the keys of the model's own ``config.json``):
the ``mistral`` architecture's counts, the old ``benchmark/flops.py`` moved
whole. Kept with the benchmark
so that every PR divides by the same count. Recomputation, padding and the
MoE dispatch's capacity slack are work the program chose to do, not work the
model needs: none of it is counted, so a utilization built on these counts
cannot pass 100% while the time covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    dh = c.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": c["num_key_value_heads"], "dh": dh,
            "m": c["intermediate_size"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"],
            "experts": c.get("num_local_experts", 0),
            "top_k": c.get("num_experts_per_tok", 0)}


def attention_params(c: dict) -> int:
    x = _dims(c)
    return x["d"] * x["h"] * x["dh"] * 2 + x["d"] * x["kv"] * x["dh"] * 2


def mlp_params_one(c: dict) -> int:
    """One feed-forward block (one expert of an MoE layer): gate, up, down."""
    x = _dims(c)
    return 3 * x["d"] * x["m"]


def layer_matmul_params_active(c: dict) -> int:
    """Parameters one token multiplies against in one layer: attention
    projections, the router, and the experts it is routed to (top-k of an
    MoE layer, the one block of a dense layer)."""
    x = _dims(c)
    if x["experts"]:
        return (attention_params(c) + x["d"] * x["experts"]
                + x["top_k"] * mlp_params_one(c))
    return attention_params(c) + mlp_params_one(c)


def layer_params_total(c: dict) -> int:
    x = _dims(c)
    norms = 2 * x["d"]
    if x["experts"]:
        return (attention_params(c) + x["d"] * x["experts"]
                + x["experts"] * mlp_params_one(c) + norms)
    return attention_params(c) + mlp_params_one(c) + norms


def params_total(c: dict) -> int:
    x = _dims(c)
    embed = x["v"] * x["d"] * (1 if c.get("tie_word_embeddings") else 2)
    return x["layers"] * layer_params_total(c) + embed + x["d"]


def matmul_params_active(c: dict) -> int:
    """Per token through the whole model, output head included (the
    embedding is a gather, not a multiplication)."""
    x = _dims(c)
    return x["layers"] * layer_matmul_params_active(c) + x["d"] * x["v"]


def attention_flops_causal(c: dict, n_query: int, start: int = 0) -> float:
    """QK^T and PV of ``n_query`` positions starting at ``start``, each
    attending to itself and everything before it: 2 products of 2*dh
    operations per (query, key, head), all layers."""
    x = _dims(c)
    pairs = n_query * start + n_query * (n_query + 1) / 2
    return 4.0 * x["h"] * x["dh"] * pairs * x["layers"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens."""
    return (2.0 * matmul_params_active(c) * prompt_len
            + attention_flops_causal(c, prompt_len))


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter, and three times the causal attention forward
    (its backward is two products per forward product). No recompute."""
    return (6.0 * matmul_params_active(c)
            + 3.0 * attention_flops_causal(c, seq_len) / seq_len)


def decode_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Bytes of weights ONE decode step has to read whatever the batch:
    every layer's matrices and norms and the output head. An MoE layer
    counts only the experts a single token needs (top-k), the least any
    batch can read, so the share stays a floor. The embedding row gather
    and the KV cache are left out on purpose (a floor cannot pass 100%)."""
    x = _dims(c)
    per_layer = layer_matmul_params_active(c) + 2 * x["d"]
    return float(bytes_per_param) * (x["layers"] * per_layer
                                     + x["d"] * x["v"] + x["d"])


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    x = _dims(c)
    return 2 * x["layers"] * x["kv"] * x["dh"] * bytes_per_value
