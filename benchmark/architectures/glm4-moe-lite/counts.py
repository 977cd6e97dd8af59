"""Operations and bytes a GLM-4.7-Flash decoder NEEDS, from a configuration's
sizes (the keys of the model's own ``config.json``; ``num_hidden_layers`` is
the depth held). What the model needs, not what a program chose to do: each
token's keys and values are expanded from its latent row ONCE (a program that
re-expands the context for every chunk, or attends absorbed at 22.3 kFLOP a
context token where the expanded form needs 20.5, does more and is charged
none of it); a token multiplies against its top-k experts and the shared one,
never against the others; a prompt needs the output head once, for its last
position (the program computes it for every row of every chunk today). So a
utilisation built on these counts cannot pass 100% while the time covers the
work, now or after a later PR stops doing the extra.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "q": c["q_lora_rank"], "r": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "vd": c["v_head_dim"], "m": c["intermediate_size"],
            "me": c["moe_intermediate_size"], "e": c["n_routed_experts"],
            "shared": c["n_shared_experts"], "k": c["num_experts_per_tok"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"]}


def attention_matmul_params(c: dict) -> int:
    """The five matrices of a latent attention block."""
    x = _dims(c)
    return (x["d"] * x["q"] + x["q"] * x["h"] * (x["nope"] + x["rope"])
            + x["d"] * (x["r"] + x["rope"])
            + x["r"] * x["h"] * (x["nope"] + x["vd"])
            + x["h"] * x["vd"] * x["d"])


def attention_params(c: dict) -> int:
    """With the two latent norms."""
    x = _dims(c)
    return attention_matmul_params(c) + x["q"] + x["r"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def expert_layer_matmul_params_active(c: dict) -> int:
    """Parameters one token multiplies against in an expert layer: the
    attention matrices, the router, its top-k experts and the shared one."""
    x = _dims(c)
    return (attention_matmul_params(c) + x["d"] * x["e"]
            + (x["k"] + x["shared"]) * expert_params_one(c))


def dense_layer_matmul_params(c: dict) -> int:
    x = _dims(c)
    return attention_matmul_params(c) + 3 * x["d"] * x["m"]


def expert_layer_params_total(c: dict) -> int:
    """Held: every routed expert, the shared one, router, correction bias,
    attention with its norms, the block's two norms."""
    x = _dims(c)
    return (attention_params(c) + x["d"] * x["e"] + x["e"]
            + (x["e"] + x["shared"]) * expert_params_one(c) + 2 * x["d"])


def dense_layer_params_total(c: dict) -> int:
    x = _dims(c)
    return attention_params(c) + 3 * x["d"] * x["m"] + 2 * x["d"]


def params_total(c: dict) -> int:
    x = _dims(c)
    embed = x["v"] * x["d"] * (1 if c.get("tie_word_embeddings") else 2)
    return (x["dense"] * dense_layer_params_total(c)
            + (x["layers"] - x["dense"]) * expert_layer_params_total(c)
            + embed + x["d"])


def layers_matmul_params_active(c: dict) -> int:
    """Per token through every layer held, the head left out."""
    x = _dims(c)
    return (x["dense"] * dense_layer_matmul_params(c)
            + (x["layers"] - x["dense"])
            * expert_layer_matmul_params_active(c))


def attention_flops_causal(c: dict, n_query: int, start: int = 0) -> float:
    """QK^T and PV of ``n_query`` positions from ``start``, each attending
    to itself and everything before it, in the EXPANDED form (the least the
    equations need): per (query, key, head) 2 * (nope + rope) operations
    for the score and 2 * v_head_dim for the value; all layers held."""
    x = _dims(c)
    pairs = n_query * start + n_query * (n_query + 1) / 2
    return (2.0 * (x["nope"] + x["rope"] + x["vd"]) * x["h"] * pairs
            * x["layers"])


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer for every token, causal attention, and the output
    head ONCE."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + attention_flops_causal(c, prompt_len)
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token: each is a target) and
    three times the causal attention forward. No recompute."""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * attention_flops_causal(c, seq_len) / seq_len)


def decode_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Bytes of weights ONE decode step has to read whatever the batch:
    every layer's attention with its norms, the router and its bias, the
    experts a single token needs (top-k and the shared one), the block
    norms, the final norm and the head. A floor: a batch reads more
    experts, and the cache's bytes are left out."""
    x = _dims(c)
    per_layer_extra = x["q"] + x["r"] + 2 * x["d"]
    total = (layers_matmul_params_active(c)
             + x["layers"] * per_layer_extra
             + (x["layers"] - x["dense"]) * x["e"]
             + x["d"] * x["v"] + x["d"])
    return float(bytes_per_param) * total


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """One latent row and one rotary row a token a layer."""
    x = _dims(c)
    return x["layers"] * (x["r"] + x["rope"]) * bytes_per_value


# -- the latent decode kernel (ops/paged_attention.py) ---------------------------

def latent_decode_bytes(c: dict, context_tokens: float,
                        bytes_per_value: int) -> float:
    """Bytes ONE call of the latent decode kernel (one layer, one step) has
    to read: the latent and rotary rows of the context it attends to. The
    queries and the output (a few KB a slot) are left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * (x["r"] + x["rope"]) * bytes_per_value


def latent_decode_flops(c: dict, context_tokens: float) -> float:
    """Operations of the same call, absorbed: per context token and head a
    score over r + rope values and a value sum over r."""
    x = _dims(c)
    return float(context_tokens) * x["h"] * 2.0 * (2 * x["r"] + x["rope"])
