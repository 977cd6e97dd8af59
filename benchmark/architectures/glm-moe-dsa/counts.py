"""Operations and bytes a GLM-5 decoder NEEDS as one chip of its
expert-parallel group holds it, from a configuration's sizes (the keys of the
model's own ``config.json``; ``num_hidden_layers`` is the depth held,
``n_routed_experts`` the experts held of the ``n_routed_experts_published``
the router scores, ``vocab_size`` the vocabulary rows held). What the model
needs, not what a program chose to do: a query's indexer scores EVERY key it
can see (that is the indexer's work, 8192 operations a pair at the published
widths) and attention reads the keys the indexer SELECTED and no other (a
program that attends to every visible key under a mask, as this repository's
does today, computes six times the selected pairs at a context of 12k and is
charged none of the rest); each token's keys and values are expanded from
its latent row ONCE; a token multiplies against the shared expert and the
EXPECTED share of its top-k choices that falls on a held expert; a prompt
needs the output head once. So a utilisation built on these counts cannot
pass 100% while the time covers the work, and a later PR that gathers the
selected rows, or skips what nobody selected, shows as a gain under the same
names.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "q": c["q_lora_rank"], "r": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "vd": c["v_head_dim"], "m": c["intermediate_size"],
            "me": c["moe_intermediate_size"],
            "held": c["n_routed_experts"],
            "e": c["n_routed_experts_published"],
            "shared": c["n_shared_experts"], "k": c["num_experts_per_tok"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"],
            "hi": c["index_n_heads"], "di": c["index_head_dim"],
            "topk": c["index_topk"]}


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


def indexer_matmul_params(c: dict) -> int:
    """The indexer's three matrices: its queries from the latent query, its
    one key a token, a weight a head."""
    x = _dims(c)
    return x["q"] * x["hi"] * x["di"] + x["d"] * x["di"] + x["d"] * x["hi"]


def indexer_params(c: dict) -> int:
    """With the key's LayerNorm (weight and bias)."""
    return indexer_matmul_params(c) + 2 * _dims(c)["di"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def router_params(c: dict) -> int:
    """Over the PUBLISHED experts, with the correction bias."""
    x = _dims(c)
    return x["d"] * x["e"] + x["e"]


def dense_mlp_params(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["m"]


def dense_layer_params_total(c: dict) -> int:
    return (attention_params(c) + indexer_params(c) + dense_mlp_params(c)
            + 2 * _dims(c)["d"])


def expert_layer_params_total(c: dict) -> int:
    """As HELD: the held routed experts, the shared one, the whole router,
    attention and the indexer with their norms, the block's two norms."""
    x = _dims(c)
    return (attention_params(c) + indexer_params(c) + router_params(c)
            + (x["held"] + x["shared"]) * expert_params_one(c) + 2 * x["d"])


def expert_layer_params_published(c: dict) -> int:
    """The same layer with every published expert: what one chip cannot
    hold."""
    x = _dims(c)
    return expert_layer_params_total(c) \
        + (x["e"] - x["held"]) * expert_params_one(c)


def params_total(c: dict) -> int:
    x = _dims(c)
    return (x["dense"] * dense_layer_params_total(c)
            + (x["layers"] - x["dense"]) * expert_layer_params_total(c)
            + 2 * x["v"] * x["d"] + x["d"])


def expert_layer_matmul_params_active(c: dict) -> float:
    """Parameters one token multiplies against in an expert layer HERE:
    attention, the indexer, the router, the shared expert and the expected
    share of its top-k choices that is held (k x held / published)."""
    x = _dims(c)
    return (attention_matmul_params(c) + indexer_matmul_params(c)
            + x["d"] * x["e"]
            + (x["k"] * x["held"] / x["e"] + x["shared"])
            * expert_params_one(c))


def dense_layer_matmul_params(c: dict) -> int:
    return (attention_matmul_params(c) + indexer_matmul_params(c)
            + dense_mlp_params(c))


def layers_matmul_params_active(c: dict) -> float:
    """Per token through every layer held, the head left out."""
    x = _dims(c)
    return (x["dense"] * dense_layer_matmul_params(c)
            + (x["layers"] - x["dense"])
            * expert_layer_matmul_params_active(c))


def visible_pairs(n_query: int, start: int = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and everything before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def selected_pairs(c: dict, n_query: float, start: float = 0) -> float:
    """Of those pairs the ones the indexer selects: ``min(index_topk, t +
    1)`` for the query at position ``t``."""
    topk = _dims(c)["topk"]
    whole = min(max(topk - start, 0), n_query)  # queries that see <= topk
    return whole * start + whole * (whole + 1) / 2 + (n_query - whole) * topk


def index_scores_flops(c: dict, pairs: float) -> float:
    """ONE layer's indexer over ``pairs`` (query, key) pairs: a product of
    ``index_head_dim`` a head (the ReLU and the weighted sum are not
    counted)."""
    x = _dims(c)
    return 2.0 * x["hi"] * x["di"] * pairs


def index_scores_bytes(c: dict, keys: float, bytes_per_value: int) -> float:
    """Bytes ONE call of the indexer's kernel has to read: the index keys of
    the context it scores (256 bytes a token at the published width). The
    queries and the scores it writes are left out: a floor."""
    return float(keys) * _dims(c)["di"] * bytes_per_value


def attention_flops_selected(c: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` SELECTED (query, key) pairs in the
    EXPANDED form (the least the equations need): per pair and head 2 *
    (nope + rope) for the score and 2 * v_head_dim for the value; ONE
    layer."""
    x = _dims(c)
    return 2.0 * (x["nope"] + x["rope"] + x["vd"]) * x["h"] * pairs


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer's matrices for every token (the experts at the
    expected rows held), the indexer over the VISIBLE pairs, attention over
    the SELECTED pairs, and the output head ONCE."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + x["layers"] * (
                index_scores_flops(c, visible_pairs(prompt_len))
                + attention_flops_selected(
                    c, selected_pairs(c, prompt_len)))
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token) and three times the
    indexer's and attention's forward. (No cell trains this architecture:
    one expert layer's share alone is 13 GB of state.)"""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * x["layers"] * (
                index_scores_flops(c, visible_pairs(seq_len))
                + attention_flops_selected(c, selected_pairs(c, seq_len)))
            / seq_len)


def expert_stack_params(c: dict) -> int:
    """The held routed experts of every expert layer."""
    x = _dims(c)
    return (x["layers"] - x["dense"]) * x["held"] * expert_params_one(c)


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step over ``live`` streams has to read:
    every layer's attention, indexer and norms, the dense MLP, the routers
    and biases, the shared experts, the final norm and the head, and of the
    held routed experts those that some live token chose (an expert is
    chosen by none of ``live`` tokens with ``(1 - k / published) **
    live``). The embedding is a row a stream, the cache's bytes are left
    out: a floor."""
    x = _dims(c)
    touched = 1.0 - (1.0 - x["k"] / x["e"]) ** max(live, 0.0)
    fixed = params_total(c) - expert_stack_params(c) - x["v"] * x["d"]
    return float(bytes_per_param) * (fixed + touched * expert_stack_params(c))


def latent_row_values(c: dict) -> int:
    """Values of the ONE row a token keeps in a layer for attention: the
    latent and the rotary key, padded to whole 128-value lanes (640)."""
    x = _dims(c)
    return -(-(x["r"] + x["rope"]) // 128) * 128


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """What a token holds in the pool: in every layer the latent row as it
    is held (1280 bytes, padding included: it is read with the row) and the
    indexer's key (256): 1536 a layer at the published widths."""
    x = _dims(c)
    return x["layers"] * (latent_row_values(c) + x["di"]) * bytes_per_value


# -- the kernels (ops/paged_attention.py) ----------------------------------------

def latent_decode_bytes(c: dict, selected_rows: float,
                        bytes_per_value: int) -> float:
    """Bytes ONE call of the latent decode kernel (one layer, one step) has
    to read: the rows its queries SELECTED, as they are held (1280 bytes a
    row). A kernel that walks every live page reads more and is charged
    none of it."""
    return float(selected_rows) * latent_row_values(c) * bytes_per_value


def latent_chunk_attention_flops(c: dict, pairs: float) -> float:
    """Operations ONE call set of the latent chunk kernel (one layer) needs
    for ``pairs`` SELECTED (query, key) pairs, absorbed as the kernel runs
    it: per pair and head a score over r + rope values and a value sum over
    r."""
    x = _dims(c)
    return float(pairs) * x["h"] * 2.0 * (2 * x["r"] + x["rope"])
