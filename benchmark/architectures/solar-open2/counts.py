"""Operations and bytes a Solar-Open2 decoder NEEDS on ONE CHIP of the group
that shares each layer, from a configuration's sizes (the keys of the model's
own ``config.json``; ``num_hidden_layers`` and ``gqa_layers_held`` are the
layers held, ``n_routed_experts`` the experts held of
``n_routed_experts_routed``, ``vocab_size`` the vocabulary rows). What the
model needs here, not what a program chose to do: a token multiplies against
those of its top-k experts that are HELD (in expectation ``k * held /
experts`` of them) and the shared one; a KDA layer's token costs its
projections and the recurrence on a [dk, dv] state a head whatever the
context; a prompt needs the output head once; only the GQA layers hold rows a
token, a KDA layer a state a sequence. So a utilisation built on these counts
cannot pass 100% while the time covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    lin = c["linear_attn_config"]
    gqa = len(c["gqa_layers_held"])
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "dh": c["head_dim"],
            "me": c["moe_intermediate_size"],
            "e": c["n_routed_experts_routed"], "held": c["n_routed_experts"],
            "k": c["num_experts_per_tok"], "shared": c["n_shared_experts"],
            "v": c["vocab_size"], "layers": c["num_hidden_layers"],
            "gqa": gqa, "kda": c["num_hidden_layers"] - gqa,
            "lh": lin["num_heads"], "dk": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"], "r": c["kda_gate_rank"]}


def gqa_matmul_params(c: dict) -> int:
    """wq, wk, wv, wo and the output gate's matrix of one GQA layer."""
    x = _dims(c)
    return 3 * x["d"] * x["h"] * x["dh"] + 2 * x["d"] * x["kv"] * x["dh"]


def kda_matmul_params(c: dict) -> int:
    """One KDA layer's matrices: q, k, v and output projections, the two
    low-rank pairs (decay, gate), beta's projection."""
    x = _dims(c)
    n = x["lh"] * x["dk"]
    return 4 * x["d"] * n + 2 * (x["d"] * x["r"] + x["r"] * n) \
        + x["d"] * x["lh"]


def kda_params(c: dict) -> int:
    """With the three convolutions' taps, ``a_log`` a head, ``dt_bias`` a
    channel and the output norm's weight."""
    x = _dims(c)
    n = x["lh"] * x["dk"]
    return kda_matmul_params(c) + 3 * x["taps"] * n + x["lh"] + n + x["dk"]


def expert_params_one(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["me"]


def expert_layer_params(c: dict) -> int:
    """One expert layer as HELD: the whole router and its bias, the held
    experts, the shared expert."""
    x = _dims(c)
    return x["d"] * x["e"] + x["e"] \
        + (x["held"] + x["shared"]) * expert_params_one(c)


def params_total(c: dict) -> int:
    """Held on this chip: every layer's operator and two norms, every
    layer's router, bias, held experts and shared expert, the embedding and
    the head over the vocabulary rows held, the final norm."""
    x = _dims(c)
    return (x["gqa"] * gqa_matmul_params(c) + x["kda"] * kda_params(c)
            + x["layers"] * (2 * x["d"] + expert_layer_params(c))
            + 2 * x["v"] * x["d"] + x["d"])


def experts_met(c: dict) -> float:
    """Routed experts a token meets HERE, in expectation: its
    ``num_experts_per_tok`` choices fall on a held expert with probability
    ``held / experts`` each (1.0 at 8 choices, 40 of 320 held)."""
    x = _dims(c)
    return x["k"] * x["held"] / x["e"]


def layers_matmul_params_active(c: dict) -> float:
    """Per token through every layer held, the head left out: the
    operators' matrices, the router, the experts met and the shared one."""
    x = _dims(c)
    return (x["gqa"] * gqa_matmul_params(c) + x["kda"] * kda_matmul_params(c)
            + x["layers"] * (
                x["d"] * x["e"]
                + (experts_met(c) + x["shared"]) * expert_params_one(c)))


def causal_pairs(n_query: int, start: int = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and everything before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def attention_flops(c: dict, n_query: int, start: int = 0) -> float:
    """QK^T and PV of ``n_query`` positions from ``start`` in the GQA layers
    held: per (query, key, head) 2 * head_dim operations for the score and
    as many for the value, the pairs causal."""
    x = _dims(c)
    return 4.0 * x["dh"] * x["h"] * x["gqa"] * causal_pairs(n_query, start)


def kda_token_flops(c: dict) -> float:
    """The recurrence of ONE token in ONE KDA layer, all heads: per head on
    its [dk, dv] state the decay (dk dv), ``(Diag(a) S)^T k`` (2 dk dv), the
    rank-one update (2 dk dv) and ``S^T q`` (2 dk dv): 7 dk dv, whatever the
    context. (The chunked form at blocks of 64 does 6 dk dv + 2 * 64 dv a
    token in its four products, the same number at dk = 128.)"""
    x = _dims(c)
    return 7.0 * x["lh"] * x["dk"] * x["dk"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer's matrices for every token (the experts at the
    EXPECTED rows held), the KDA recurrence a token, attention in the GQA
    layers, and the output head ONCE."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + x["kda"] * kda_token_flops(c) * prompt_len
            + attention_flops(c, prompt_len) + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence: 6 per
    multiplied parameter (the head for every token) and three times the
    attention and recurrence forward. (No cell trains this architecture: its
    four layers' share alone is 53 GB of state.)"""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * (attention_flops(c, seq_len) / seq_len
                     + x["kda"] * kda_token_flops(c)))


def expert_stack_params(c: dict) -> int:
    """The held routed experts of every layer."""
    x = _dims(c)
    return x["layers"] * x["held"] * expert_params_one(c)


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step over ``live`` streams has to read:
    every layer's operator and norms, the routers and biases, the shared
    experts, the final norm and the head, and of the held routed experts
    those that some live token chose: an expert is chosen by a token with
    probability ``k / experts``, so by none of ``live`` tokens with ``(1 - k
    / experts) ** live`` (44.5% at 32 streams, 8 of 320). The embedding is a
    row a stream; the cache's and the states' bytes are left out: a floor."""
    x = _dims(c)
    touched = 1.0 - (1.0 - x["k"] / x["e"]) ** max(live, 0.0)
    fixed = params_total(c) - expert_stack_params(c) - x["v"] * x["d"]
    return float(bytes_per_param) * (fixed + touched * expert_stack_params(c))


def resident_weight_bytes(c: dict, bytes_per_param: int) -> float:
    """Every weight held."""
    return float(bytes_per_param) * params_total(c)


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head in the GQA layers held: the rows a token
    keeps for as long as its sequence lives. A KDA layer keeps none a token
    (``state_bytes_per_sequence``)."""
    x = _dims(c)
    return x["gqa"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def state_bytes_per_sequence(c: dict, bytes_per_value: int) -> int:
    """What a sequence keeps in the KDA layers held, whatever its length:
    the recurrent matrix a head in float32 and the last ``taps - 1`` inputs
    of the three convolutions in the activation type."""
    x = _dims(c)
    n = x["lh"] * x["dk"]
    return x["kda"] * (x["lh"] * x["dk"] * x["dk"] * 4
                       + 3 * (x["taps"] - 1) * n * bytes_per_value)


# -- the kernels (ops/kda.py, ops/paged_attention.py) -------------------------------

# Positions a block of the chunked form (ops/kda.py::BLOCK): a position's row
# of the block's [T, T] matrix is that many float32 values.
KDA_BLOCK = 64


def kda_mixer_flops(c: dict, tokens: float) -> float:
    """Operations ONE KDA layer's whole mixer needs for ``tokens`` prompt
    tokens: 2 per multiplied parameter of its matrices (q, k, v and output
    projections, the two low-rank pairs, beta) and the recurrence a token.
    The convolutions' taps, the norms and the gates are left out (a floor),
    and so is whatever the chunked form computes beyond the recurrence (the
    blocks' triangular solves: work the program chose)."""
    return (2.0 * kda_matmul_params(c) + kda_token_flops(c)) * tokens


def kda_chunk_flops(c: dict, tokens: float) -> float:
    """Operations ONE call of the kernel ``kda_chunk`` (one KDA layer of one
    chunk program) needs for ``tokens`` prompt tokens: the recurrence,
    ``kda_token_flops`` a token, which is what its four products a block add
    up to. A last chunk's padding is the program's choice and not counted."""
    return kda_token_flops(c) * tokens


def kda_chunk_bytes(c: dict, tokens: float, chunks: float) -> float:
    """Bytes the same call has to move for ``tokens`` tokens in ``chunks``
    rows: a token a head its rows of the four float32 operands the blocks'
    products read ([dk] of Q e^G, W and K e^(G_T - G), [dv] of U~, ``KDA_BLOCK``
    of B) and [dv] of output; a row a head the state in and out. The decay
    vectors a block are left out: a floor."""
    x = _dims(c)
    per_token = x["lh"] * 4 * (3 * x["dk"] + 2 * x["dk"] + KDA_BLOCK)
    per_chunk = x["lh"] * 2 * x["dk"] * x["dk"] * 4
    return float(per_token) * tokens + float(per_chunk) * chunks


def kda_step_bytes(c: dict, live: float) -> float:
    """Bytes ONE call of the kernel ``kda_step`` (one KDA layer of one decode
    step) has to move for ``live`` streams: a stream's [H, dk, dv] float32
    state read and written where it lies (8.4 MB), its four [H, dk] column
    operands, its values and its output in float32."""
    x = _dims(c)
    n = x["lh"] * x["dk"]
    return float(live) * (2 * n * x["dk"] * 4 + 6 * n * 4)


def decode_attention_bytes(c: dict, context_tokens: float,
                           bytes_per_value: int) -> float:
    """Bytes ONE call of the GQA decode kernel (one layer, one step) has to
    read: the K rows and the V rows of the ``context_tokens`` its live
    streams attend to (4096 B a token at 8 KV heads of 128 in bf16). The
    queries and the output are left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value


def chunk_attention_flops(c: dict, prompt_len: int) -> float:
    """Operations the chunk attention kernel's calls NEED over one whole
    prompt, the GQA layers held."""
    return attention_flops(c, prompt_len)
