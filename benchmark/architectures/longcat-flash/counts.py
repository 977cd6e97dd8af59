"""Operations and bytes the language model of LongCat-Flash-Omni NEEDS as one
chip of its expert-parallel group holds it, from a configuration's sizes
(the keys of the model's own ``config.json``; ``num_layers`` is the PUBLISHED
layers held, each two latent attentions, two dense MLPs and one expert
layer; ``n_routed_experts`` the experts held of the
``n_routed_experts_published`` the router scores beside its
``zero_expert_num`` zero experts; ``vocab_size`` the vocabulary rows held).
What the model needs, not what a program chose to do: a ZERO expert costs 0
operations and 0 bytes (it hands a token its own input back); a token
multiplies against the EXPECTED share of its ``moe_topk`` choices that falls
on a held expert (``moe_topk x held / (published + zero)``: a quarter of an
expert at the published sizes); a decode step reads the held experts its
live tokens are EXPECTED to touch; each token's keys and values are expanded
from its latent row ONCE; a prompt needs the output head once. So a
utilisation built on these counts cannot pass 100% while the time covers the
work, and a later PR that gathers the held rows alone (the sorted path
gathers every routed row today) shows as a gain under the same names.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "q": c["q_lora_rank"], "r": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "vd": c["v_head_dim"], "m": c["ffn_hidden_size"],
            "me": c["expert_ffn_hidden_size"],
            "held": c["n_routed_experts"],
            "e": c["n_routed_experts_published"],
            "z": c["zero_expert_num"], "k": c["moe_topk"],
            "v": c["vocab_size"], "layers": c["num_layers"]}


def attention_matmul_params(c: dict) -> int:
    """The five matrices of ONE latent attention."""
    x = _dims(c)
    return (x["d"] * x["q"] + x["q"] * x["h"] * (x["nope"] + x["rope"])
            + x["d"] * (x["r"] + x["rope"])
            + x["r"] * x["h"] * (x["nope"] + x["vd"])
            + x["h"] * x["vd"] * x["d"])


def attention_params(c: dict) -> int:
    """With the two latent norms."""
    x = _dims(c)
    return attention_matmul_params(c) + x["q"] + x["r"]


def dense_mlp_params(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["m"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def router_width(c: dict) -> int:
    """The router's outputs: the published experts and the zero experts."""
    x = _dims(c)
    return x["e"] + x["z"]


def router_params(c: dict) -> int:
    """Over EVERY output, with the correction bias."""
    return (_dims(c)["d"] + 1) * router_width(c)


def layer_params_outside_experts(c: dict) -> int:
    """A published layer but for its experts: two attentions, two dense
    MLPs, the router, four norms."""
    return (2 * attention_params(c) + 2 * dense_mlp_params(c)
            + router_params(c) + 4 * _dims(c)["d"])


def layer_params_total(c: dict) -> int:
    """As HELD: with the experts this chip keeps (a zero expert has no
    parameter)."""
    return layer_params_outside_experts(c) \
        + _dims(c)["held"] * expert_params_one(c)


def layer_params_published(c: dict) -> int:
    """The same layer with every published expert: what one chip cannot
    hold."""
    return layer_params_outside_experts(c) \
        + _dims(c)["e"] * expert_params_one(c)


def params_total(c: dict) -> int:
    x = _dims(c)
    return x["layers"] * layer_params_total(c) + 2 * x["v"] * x["d"] + x["d"]


def experts_met(c: dict) -> float:
    """Held experts one token multiplies against in one expert layer, in
    expectation: its ``moe_topk`` choices fall on the router's outputs
    alike, the zero experts' among them."""
    x = _dims(c)
    return x["k"] * x["held"] / router_width(c)


def layer_matmul_params_active(c: dict) -> float:
    """Parameters one token multiplies against in a published layer HERE:
    both attentions, both dense MLPs, the router and the expected held
    experts; a zero expert none."""
    x = _dims(c)
    return (2 * attention_matmul_params(c) + 2 * dense_mlp_params(c)
            + x["d"] * router_width(c) + experts_met(c) * expert_params_one(c))


def layers_matmul_params_active(c: dict) -> float:
    """Per token through every layer held, the head left out."""
    return _dims(c)["layers"] * layer_matmul_params_active(c)


def visible_pairs(n_query: float, start: float = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and everything before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def attention_flops(c: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, key) pairs in the EXPANDED form
    (the least the equations need): per pair and head 2 * (nope + rope) for
    the score and 2 * v_head_dim for the value; ONE attention."""
    x = _dims(c)
    return 2.0 * (x["nope"] + x["rope"] + x["vd"]) * x["h"] * pairs


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer's matrices for every token (the experts at the
    expected rows held, the zero experts at nothing), both attentions of
    every layer over the visible pairs, and the output head ONCE."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + 2 * x["layers"] * attention_flops(
                c, visible_pairs(prompt_len))
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token) and three times
    attention's forward. (No cell trains this architecture: four layers as
    held are 80 GB of state.)"""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * 2 * x["layers"] * attention_flops(
                c, visible_pairs(seq_len)) / seq_len)


def expert_stack_params(c: dict) -> int:
    """The held experts of every expert layer."""
    x = _dims(c)
    return x["layers"] * x["held"] * expert_params_one(c)


def experts_touched_share(c: dict, live: float) -> float:
    """The share of the held experts that SOME of ``live`` tokens chose: an
    expert is chosen by none of them with ``(1 - moe_topk / (published +
    zero)) ** live``."""
    return 1.0 - (1.0 - _dims(c)["k"] / router_width(c)) ** max(live, 0.0)


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step over ``live`` streams has to read:
    every layer's attentions, dense MLPs, router and norms, the final norm
    and the head, and of the held experts those that some live token is
    EXPECTED to choose. The embedding is a row a stream, the cache's bytes
    are left out: a floor."""
    x = _dims(c)
    fixed = params_total(c) - expert_stack_params(c) - x["v"] * x["d"]
    return float(bytes_per_param) * (
        fixed + experts_touched_share(c, live) * expert_stack_params(c))


def latent_row_values(c: dict) -> int:
    """Values of the ONE row a token keeps in an attention: the latent and
    the rotary key, padded to whole 128-value lanes (640)."""
    x = _dims(c)
    return -(-(x["r"] + x["rope"]) // 128) * 128


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """What a token holds in the pool: the latent row as it is held (1280
    bytes, padding included: it is read with the row) in each of a
    published layer's TWO attentions."""
    return 2 * _dims(c)["layers"] * latent_row_values(c) * bytes_per_value


# -- the kernels (ops/paged_attention.py) ----------------------------------------

def latent_decode_bytes(c: dict, context_rows: float,
                        bytes_per_value: int) -> float:
    """Bytes ONE call of the latent decode kernel (one attention, one step)
    has to read: the rows of its live streams' contexts, as they are held
    (1280 bytes a row)."""
    return float(context_rows) * latent_row_values(c) * bytes_per_value


def latent_chunk_attention_flops(c: dict, pairs: float) -> float:
    """Operations ONE call set of the latent chunk kernel (one attention)
    needs for ``pairs`` visible (query, key) pairs, absorbed as the kernel
    runs it: per pair and head a score over r + rope values and a value sum
    over r."""
    x = _dims(c)
    return float(pairs) * x["h"] * 2.0 * (2 * x["r"] + x["rope"])
