"""Operations and bytes an LFM2-MoE decoder NEEDS, from a configuration's
sizes (the keys of the model's own ``config.json``; ``num_hidden_layers`` and
``layer_types_held`` are the layers held). What the model needs, not what a
program chose to do: a token multiplies against its top-k experts, never
against the others; a conv layer's filter and gates are a few operations a
value; a prompt needs the output head once, for its last position (the
program computes it for every row of every chunk today); only the attention
layers attend and hold rows a token. So a utilisation built on these counts
cannot pass 100% while the time covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    kinds = c["layer_types_held"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"],
            "dh": c["hidden_size"] // c["num_attention_heads"],
            "m": c["intermediate_size"], "me": c["moe_intermediate_size"],
            "e": c["num_experts"], "k": c["num_experts_per_tok"],
            "taps": c["conv_L_cache"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"], "dense": c["num_dense_layers"],
            "attn": kinds.count("full_attention"),
            "conv": kinds.count("conv")}


def attention_matmul_params(c: dict) -> int:
    """wq, wk, wv, wo of one attention layer."""
    x = _dims(c)
    return 2 * x["d"] * x["h"] * x["dh"] + 2 * x["d"] * x["kv"] * x["dh"]


def attention_params(c: dict) -> int:
    """With the two per-head norms."""
    return attention_matmul_params(c) + 2 * _dims(c)["dh"]


def conv_matmul_params(c: dict) -> int:
    """The in-projection's three parts and the out-projection."""
    return 4 * _dims(c)["d"] ** 2


def conv_params(c: dict) -> int:
    """With the depthwise taps."""
    x = _dims(c)
    return conv_matmul_params(c) + x["taps"] * x["d"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def operators_params(c: dict, matmul_only: bool = False) -> int:
    """The operators of every layer held."""
    x = _dims(c)
    if matmul_only:
        return x["attn"] * attention_matmul_params(c) \
            + x["conv"] * conv_matmul_params(c)
    return x["attn"] * attention_params(c) + x["conv"] * conv_params(c)


def params_total(c: dict) -> int:
    """Held: every layer's operator and two norms, the dense layers' MLP,
    every expert layer's router, bias and all its experts, the embedding
    (once if tied), the final norm."""
    x = _dims(c)
    experts = x["layers"] - x["dense"]
    embed = x["v"] * x["d"] * (1 if c["tie_word_embeddings"] else 2)
    return (operators_params(c) + x["layers"] * 2 * x["d"]
            + x["dense"] * 3 * x["d"] * x["m"]
            + experts * (x["d"] * x["e"] + x["e"]
                         + x["e"] * expert_params_one(c))
            + embed + x["d"])


def layers_matmul_params_active(c: dict) -> int:
    """Per token through every layer held, the head left out: the
    operators' matrices, the dense MLPs, the router and the top-k experts."""
    x = _dims(c)
    experts = x["layers"] - x["dense"]
    return (operators_params(c, matmul_only=True)
            + x["dense"] * 3 * x["d"] * x["m"]
            + experts * (x["d"] * x["e"] + x["k"] * expert_params_one(c)))


def attention_flops_causal(c: dict, n_query: int, start: int = 0) -> float:
    """QK^T and PV of ``n_query`` positions from ``start``, each attending
    to itself and everything before it: per (query, key, head) 2 * head_dim
    operations for the score and as many for the value; the attention
    layers held only."""
    x = _dims(c)
    pairs = n_query * start + n_query * (n_query + 1) / 2
    return 4.0 * x["dh"] * x["h"] * pairs * x["attn"]


def conv_flops_per_token(c: dict) -> float:
    """The two gates and the ``taps`` multiply-adds a value, in every conv
    layer held (the projections are among the matrices)."""
    x = _dims(c)
    return float(x["conv"] * x["d"] * (2 + 2 * x["taps"]))


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer for every token, causal attention in the attention
    layers, and the output head ONCE."""
    x = _dims(c)
    return ((2.0 * layers_matmul_params_active(c) + conv_flops_per_token(c))
            * prompt_len + attention_flops_causal(c, prompt_len)
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token: each is a target),
    three times the conv layers' elementwise work and the causal attention
    forward. No recompute."""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * conv_flops_per_token(c)
            + 3.0 * attention_flops_causal(c, seq_len) / seq_len)


def decode_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Bytes of weights ONE decode step has to read whatever the batch:
    every layer's operator with its norms and taps, the dense MLPs, the
    router and its bias, the experts a single token needs (top-k), the
    final norm and the head (the embedding, tied). A floor: a batch of 64
    reads nearly every expert (``resident_weight_bytes``), and the cache's
    bytes are left out."""
    x = _dims(c)
    experts = x["layers"] - x["dense"]
    total = (operators_params(c) + x["layers"] * 2 * x["d"]
             + x["dense"] * 3 * x["d"] * x["m"]
             + experts * (x["d"] * x["e"] + x["e"]
                          + x["k"] * expert_params_one(c))
             + x["d"] * x["v"] + x["d"])
    return float(bytes_per_param) * total


def resident_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Every weight held: what a step reads once its batch routes to every
    expert (beside the point of a floor; PERF.md gives it beside the
    share)."""
    return float(bytes_per_param) * params_total(c)


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head, in the attention layers held; a conv layer
    holds nothing a token (``state_bytes_per_sequence``)."""
    x = _dims(c)
    return x["attn"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def state_bytes_per_sequence(c: dict, bytes_per_value: int) -> int:
    """The conv layers' state: ``taps - 1`` hidden-wide rows a layer."""
    x = _dims(c)
    return x["conv"] * (x["taps"] - 1) * x["d"] * bytes_per_value


# -- the packed-row decode kernel (ops/paged_attention.py) -----------------------

def packed_decode_bytes(c: dict, context_tokens: float,
                        bytes_per_value: int) -> float:
    """Bytes ONE call of the packed-row decode kernel (one attention layer,
    one step) has to read: the K row and the V row of the context it
    attends to. The queries and the output (a few KB a slot) are left out:
    a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value


def packed_decode_flops(c: dict, context_tokens: float) -> float:
    """Operations the equations need of the same call: per context token
    and query head a score and a value sum over head_dim values (the kernel
    multiplies whole rows, eight times that, and stays under the bus)."""
    x = _dims(c)
    return float(context_tokens) * x["h"] * 4.0 * x["dh"]
