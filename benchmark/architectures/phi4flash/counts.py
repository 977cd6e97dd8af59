"""Operations and bytes a Phi-4-mini-flash decoder NEEDS, from a
configuration's sizes (the keys of the model's own ``config.json`` and the
file's ``assumed`` ones: ``d_state``, ``d_conv``, ``expand``, ``dt_rank``,
``layer_types``). What the model needs, not what a program chose to do: a
prompt needs its cross-decoder (the gated memory units and the cross layers
behind the one full-attention layer) and the output head at ONE position,
the one whose logits are read (YOCO's linear prefill, arXiv:2405.05254); a
window layer's query scores at most ``sliding_window`` keys; a score is a
``head_dim``-wide product (the program's padded queries of twice the width
are its choice); a Mamba layer's token costs its projections and the
recurrence on ``E x N`` states whatever the context; only the ONE
full-attention layer holds rows a token. So a utilisation built on these
counts cannot pass 100% while the time covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    types = c["layer_types"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"], "dh": d // h,
            "m": c["intermediate_size"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"], "e": c["expand"] * d,
            "n": c["d_state"], "r": c["dt_rank"], "taps": c["d_conv"],
            "window": c["sliding_window"],
            "mamba": types.count("mamba"),
            "sliding": types.count("sliding_attention"),
            "full": types.count("full_attention"),
            "gmu": types.count("gmu"),
            "cross": types.count("cross_attention")}


def mamba_matmul_params(c: dict) -> int:
    """One Mamba mixer's matrices: in (u and z), ``Wx`` (step, B, C), the
    step's projection, out."""
    x = _dims(c)
    return 2 * x["d"] * x["e"] + x["e"] * (x["r"] + 2 * x["n"]) \
        + x["r"] * x["e"] + x["e"] * x["d"]


def mamba_params(c: dict) -> int:
    """With the taps and their bias, the step's bias, ``A`` a channel and
    state and ``D``."""
    x = _dims(c)
    return mamba_matmul_params(c) + (x["taps"] + 1) * x["e"] + x["e"] \
        + x["n"] * x["e"] + x["e"]


def attention_matmul_params(c: dict, cross: bool = False) -> int:
    """q (k and v unless ``cross``) and output projections."""
    x = _dims(c)
    kv = 0 if cross else 2 * x["kv"] * x["dh"]
    return x["d"] * (x["h"] * x["dh"] + kv) + x["h"] * x["dh"] * x["d"]


def attention_params(c: dict, cross: bool = False) -> int:
    """With the projections' biases, the four lambda vectors and the pair
    norm's weight (``lambda_init`` is a constant of the depth)."""
    x = _dims(c)
    kv = 0 if cross else 2 * x["kv"] * x["dh"]
    return attention_matmul_params(c, cross) + x["h"] * x["dh"] + kv \
        + x["d"] + 6 * x["dh"]


def gmu_params(c: dict) -> int:
    x = _dims(c)
    return 2 * x["d"] * x["e"]


def mlp_params(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["m"]


def params_by_part(c: dict) -> dict:
    """Parameters held, by part (the embedding once: the head is tied)."""
    x = _dims(c)
    return {
        "mlp": x["layers"] * mlp_params(c),
        "mamba": x["mamba"] * mamba_params(c),
        "attention": (x["sliding"] + x["full"]) * attention_params(c),
        "gmu": x["gmu"] * gmu_params(c),
        "cross": x["cross"] * attention_params(c, cross=True),
        "norms": (2 * x["layers"] + 1) * 2 * x["d"],
        "embedding": x["v"] * x["d"],
    }


def params_total(c: dict) -> int:
    return sum(params_by_part(c).values())


def self_decoder_matmul_params(c: dict) -> int:
    """Per token through the layers that keep state (the Mamba layers, the
    window layers and the full one) and their MLPs."""
    x = _dims(c)
    held = x["mamba"] + x["sliding"] + x["full"]
    return x["mamba"] * mamba_matmul_params(c) \
        + (x["sliding"] + x["full"]) * attention_matmul_params(c) \
        + held * mlp_params(c)


def cross_decoder_matmul_params(c: dict) -> int:
    """Per position through the layers that keep none."""
    x = _dims(c)
    return x["gmu"] * gmu_params(c) \
        + x["cross"] * attention_matmul_params(c, cross=True) \
        + (x["gmu"] + x["cross"]) * mlp_params(c)


def causal_pairs(n_query: int, start: int = 0, window: int = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and what is before it, at most ``window`` keys where
    given."""
    if not window:
        return n_query * start + n_query * (n_query + 1) / 2
    return float(sum(min(start + i + 1, window) for i in range(n_query)))


def pair_flops(c: dict) -> float:
    """Operations one (query position, key) pair costs in ONE attention
    layer, all heads: per head a ``head_dim``-wide score (2 Dh) and as much
    for the value (a pair's value is 2 Dh wide, weighted once for two
    heads' scores)."""
    x = _dims(c)
    return 4.0 * x["dh"] * x["h"]


def attention_flops(c: dict, n_query: int, start: int = 0) -> float:
    """Scores and values of ``n_query`` positions from ``start`` in the
    layers that keep K and V: the window layers at the window's length, the
    full layer causal."""
    x = _dims(c)
    return pair_flops(c) * (
        x["sliding"] * causal_pairs(n_query, start, x["window"])
        + x["full"] * causal_pairs(n_query, start))


def ssm_scan_elements(c: dict, tokens: float) -> float:
    """Exponentials ONE Mamba layer's scan needs for ``tokens`` tokens: one a
    channel and state (``exp(Delta A)``)."""
    x = _dims(c)
    return float(x["e"] * x["n"]) * tokens


def ssm_scan_flops(c: dict, tokens: float) -> float:
    """Multiply-adds of the same scan: a channel, state and token the decay's
    argument, the state's update (2), ``Delta x B`` and ``h C`` (2 each): 7,
    beside the exponential."""
    return 7.0 * ssm_scan_elements(c, tokens)


def ssm_scan_bytes(c: dict, tokens: float, chunks: float) -> float:
    """Bytes ONE call of the kernel ``ssm_scan`` (one Mamba layer of one
    chunk program) has to move for ``tokens`` tokens in ``chunks`` rows: a
    token its ``E`` channels of ``x`` and ``Delta`` in and of ``y`` out in
    float32 and its ``B`` and ``C``; a row the state in and out."""
    x = _dims(c)
    return float(4 * (3 * x["e"] + 2 * x["n"])) * tokens \
        + float(2 * 4 * x["e"] * x["n"]) * chunks


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: the self-decoder's matrices, scans and attention for every token;
    the cross-decoder (its matrices, the cross layers' ONE query over the
    whole prompt) and the output head at ONE position."""
    x = _dims(c)
    return (2.0 * self_decoder_matmul_params(c) * prompt_len
            + x["mamba"] * ssm_scan_flops(c, prompt_len)
            + attention_flops(c, prompt_len)
            + 2.0 * cross_decoder_matmul_params(c)
            + x["cross"] * pair_flops(c) * prompt_len
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence, every
    layer and the head at every position. (No cell trains this architecture:
    the program's scan has no backward.)"""
    x = _dims(c)
    return (6.0 * (self_decoder_matmul_params(c)
                   + cross_decoder_matmul_params(c) + x["d"] * x["v"])
            + 3.0 * (x["mamba"] * ssm_scan_flops(c, 1.0)
                     + (attention_flops(c, seq_len)
                        + x["cross"] * pair_flops(c)
                        * causal_pairs(seq_len)) / seq_len))


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step has to read: every layer of both
    decoders, the final norm and the head (the tied table, read whole as the
    head). The embedding lookup is a row a stream; the cache's and the
    states' bytes are left out: a floor. ``live`` moves nothing (dense)."""
    return float(bytes_per_param) * params_total(c)


def resident_weight_bytes(c: dict, bytes_per_param: int) -> float:
    return float(bytes_per_param) * params_total(c)


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head in the ONE full-attention layer: the rows a
    token keeps for as long as its sequence lives (5120 B in bfloat16). A
    window layer keeps a ring, a Mamba layer a state a sequence, a cross
    layer and a gated memory unit nothing."""
    x = _dims(c)
    return x["full"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def window_bytes_per_sequence(c: dict, bytes_per_value: int,
                              ring_tokens: int) -> int:
    """What a sequence keeps in the window layers: K and V of a ring of
    ``ring_tokens`` positions a layer."""
    x = _dims(c)
    return x["sliding"] * ring_tokens * 2 * x["kv"] * x["dh"] \
        * bytes_per_value


def state_bytes_per_sequence(c: dict, bytes_per_value: int) -> int:
    """What a sequence keeps in the Mamba layers, whatever its length: the
    ``[N, E]`` state in float32 and the last ``taps - 1`` inputs of the
    convolution in the activation type."""
    x = _dims(c)
    return x["mamba"] * (x["n"] * x["e"] * 4
                         + (x["taps"] - 1) * x["e"] * bytes_per_value)


def ssm_step_bytes(c: dict, live: float) -> float:
    """Bytes ONE Mamba layer of one decode step has to move for ``live``
    streams: a stream's state read and written (0.66 MB), its convolution
    tail in and out."""
    x = _dims(c)
    return float(live) * (2 * x["n"] * x["e"] * 4
                          + 2 * (x["taps"] - 1) * x["e"] * 2)


def decode_attention_calls(c: dict) -> int:
    """Calls of the global decode kernel a decode step makes: the one
    full-attention layer and every cross layer, all over ONE layer's rows."""
    x = _dims(c)
    return x["full"] + x["cross"]


def decode_attention_bytes(c: dict, context_tokens: float,
                           bytes_per_value: int) -> float:
    """Bytes ONE call of a decode attention kernel (one layer, one step) has
    to read: the K rows and the V rows of the ``context_tokens`` its live
    streams attend to (5120 B a token at 20 KV heads of 64 in bfloat16): a
    step makes ``decode_attention_calls`` of them over the full layer's rows
    and one a window layer over its ring. The queries and the output are
    left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value


def chunk_attention_flops(c: dict, prompt_len: int) -> float:
    """Operations the chunk attention kernel's calls NEED over one whole
    prompt: the window layers and the full layer (a prompt's cross layers
    attend with one query, through the decode kernel)."""
    return attention_flops(c, prompt_len)
