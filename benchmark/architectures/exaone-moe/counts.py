"""Operations and bytes an EXAONE-MoE decoder NEEDS on ONE CHIP of the group
that shares each layer, from a configuration's sizes (the keys of the model's
own ``config.json``; ``num_hidden_layers`` and ``layer_types_held`` are the
layers held, ``num_experts`` the experts held of ``num_experts_routed``,
``vocab_size`` the vocabulary rows). What the model needs here, not what a program chose to do: a token
multiplies against those of its top-k experts that are HELD (in expectation
``k * held / experts`` of them: the router does not know where an expert
lies) and the shared one; a window layer's query scores ``sliding_window``
keys however long its context; a prompt needs the output head once, for its
last position (the program computes it for every row of every chunk today);
only the global layers hold rows a token, a window layer a ring a sequence.
So a utilisation built on these counts cannot pass 100% while the time
covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    kinds = c["layer_types_held"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "dh": c["head_dim"],
            "m": c["intermediate_size"], "me": c["moe_intermediate_size"],
            "e": c["num_experts_routed"], "held": c["num_experts"],
            "k": c["num_experts_per_tok"], "shared": c["num_shared_experts"],
            "v": c["vocab_size"], "w": c["sliding_window"],
            "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"],
            "glob": kinds.count("full_attention"),
            "win": kinds.count("sliding_attention")}


def attention_matmul_params(c: dict) -> int:
    """wq, wk, wv, wo of one attention layer, window or global."""
    x = _dims(c)
    return 2 * x["d"] * x["h"] * x["dh"] + 2 * x["d"] * x["kv"] * x["dh"]


def attention_params(c: dict) -> int:
    """With the two per-head norms."""
    return attention_matmul_params(c) + 2 * _dims(c)["dh"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def expert_layer_params(c: dict) -> int:
    """One expert layer's feed-forward as HELD: the whole router and its
    bias, the held experts, the shared expert."""
    x = _dims(c)
    return x["d"] * x["e"] + x["e"] \
        + (x["held"] + x["shared"]) * expert_params_one(c)


def params_total(c: dict) -> int:
    """Held on this chip: every layer's attention and two norms, the dense
    layers' MLP, every expert layer's router, bias, held experts and shared
    expert, the embedding and the head over the vocabulary rows held, the
    final norm."""
    x = _dims(c)
    embed = x["v"] * x["d"] * (1 if c["tie_word_embeddings"] else 2)
    return (x["layers"] * (attention_params(c) + 2 * x["d"])
            + x["dense"] * 3 * x["d"] * x["m"]
            + (x["layers"] - x["dense"]) * expert_layer_params(c)
            + embed + x["d"])


def experts_met(c: dict) -> float:
    """Routed experts a token meets HERE, in expectation: its
    ``num_experts_per_tok`` choices fall on a held expert with probability
    ``held / experts`` each (1.0 at 8 choices, 16 of 128 held)."""
    x = _dims(c)
    return x["k"] * x["held"] / x["e"]


def layers_matmul_params_active(c: dict) -> float:
    """Per token through every layer held, the head left out: attention's
    matrices, the dense MLPs, the router, the experts met and the shared
    one."""
    x = _dims(c)
    return (x["layers"] * attention_matmul_params(c)
            + x["dense"] * 3 * x["d"] * x["m"]
            + (x["layers"] - x["dense"]) * (
                x["d"] * x["e"]
                + (experts_met(c) + x["shared"]) * expert_params_one(c)))


def causal_pairs(n_query: int, start: int = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and everything before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def window_pairs(n_query: int, start: int, window: int) -> float:
    """The same where a query sees its last ``window`` keys only: a query
    at position ``t`` sees ``min(t + 1, window)``."""
    full = max(0, start + n_query - max(start, window - 1))
    ramp_to = min(start + n_query, window - 1)      # positions < window - 1
    ramp = max(0, ramp_to - start)
    return full * window + ramp * (2 * start + ramp + 1) / 2


def attention_flops(c: dict, n_query: int, start: int = 0) -> float:
    """QK^T and PV of ``n_query`` positions from ``start`` in every layer
    held: per (query, key, head) 2 * head_dim operations for the score and
    as many for the value; a global layer's pairs are causal, a window
    layer's at most the window a query."""
    x = _dims(c)
    return 4.0 * x["dh"] * x["h"] * (
        x["glob"] * causal_pairs(n_query, start)
        + x["win"] * window_pairs(n_query, start, x["w"]))


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer for every token (the experts at the EXPECTED rows
    held), attention as above, and the output head ONCE."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + attention_flops(c, prompt_len) + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token: each is a target) and
    three times the attention forward. No recompute. (No cell trains this
    architecture: one expert layer's share alone is 12 GB of state.)"""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * attention_flops(c, seq_len) / seq_len)


def expert_stack_params(c: dict) -> int:
    """The held routed experts of every expert layer."""
    x = _dims(c)
    return (x["layers"] - x["dense"]) * x["held"] * expert_params_one(c)


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step over ``live`` streams has to read:
    every layer's attention and norms, the dense MLPs, the routers and
    biases, the shared experts, the final norm and the head, and of the held
    routed experts those that some live token chose: an expert is chosen by
    a token with probability ``k / experts``, so by none of ``live`` tokens
    with ``(1 - k / experts) ** live`` (12.7% at 32 streams). The embedding
    is a row a stream, the cache's bytes are left out: a floor."""
    x = _dims(c)
    touched = 1.0 - (1.0 - x["k"] / x["e"]) ** max(live, 0.0)
    fixed = params_total(c) - expert_stack_params(c) - x["v"] * x["d"]
    return float(bytes_per_param) * (fixed + touched * expert_stack_params(c))


def resident_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Every weight held."""
    return float(bytes_per_param) * params_total(c)


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head in the GLOBAL layers held: the rows a token
    keeps for as long as its sequence lives. A window layer keeps none a
    token (``window_ring_bytes_per_sequence``)."""
    x = _dims(c)
    return x["glob"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def window_ring_bytes_per_sequence(c: dict, bytes_per_value: int,
                                   ring_pages: int, page_size: int) -> int:
    """What a sequence keeps in the window layers held: a ring of
    ``ring_pages`` pages of K and V rows a layer, whatever its length."""
    x = _dims(c)
    return x["win"] * ring_pages * page_size * 2 * x["kv"] * x["dh"] \
        * bytes_per_value


# -- the attention kernels (ops/paged_attention.py) ------------------------------

def decode_attention_bytes(c: dict, context_tokens: float,
                           bytes_per_value: int) -> float:
    """Bytes ONE call of a decode kernel (one layer, one step) has to read:
    the K rows and the V rows of the ``context_tokens`` its live streams
    attend to (4096 B a token at 8 KV heads of 128 in bf16): a global
    layer's call the whole contexts, a window layer's the windows. The
    queries and the output (16 KB a stream) are left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value


def chunk_attention_flops(c: dict, prompt_len: int) -> float:
    """Operations the chunk kernel's calls NEED over one whole prompt, all
    layers held: ``attention_flops`` (a window call's at the window's
    length, whatever blocks the kernel computes on)."""
    return attention_flops(c, prompt_len)
