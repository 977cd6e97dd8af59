"""Operations and bytes a Falcon-H1 decoder NEEDS, from a configuration's
sizes (the keys of the model's own ``config.json``). What the model needs,
not what a program chose to do: a token costs its projections, its MLP, a
causal attention over what is before it, and the SSD RECURRENCE on ``H x P x
N`` states whatever the context (the chunked form's ``C B^T`` and masked
products are a program's way to run it on a matrix unit and are not counted);
a prompt needs the output head at ONE position; every layer holds K and V
rows a token AND a state a sequence. So a utilisation built on these counts
cannot pass 100% while the time covers the work.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    e, g, n = c["mamba_d_ssm"], c["mamba_n_groups"], c["mamba_d_state"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "dh": c["head_dim"],
            "m": c["intermediate_size"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"], "e": e, "g": g, "n": n,
            "sh": c["mamba_n_heads"], "p": c["mamba_d_head"],
            "conv": e + 2 * g * n, "taps": c["mamba_d_conv"]}


def attention_params(c: dict) -> int:
    """q, k, v and output projections (no bias)."""
    x = _dims(c)
    return x["d"] * (x["h"] + 2 * x["kv"]) * x["dh"] \
        + x["h"] * x["dh"] * x["d"]


def ssd_matmul_params(c: dict) -> int:
    """The SSD mixer's two matrices: the in-projection (gate, ``[x | B |
    C]``, a step a head) and the out-projection."""
    x = _dims(c)
    return x["d"] * (x["e"] + x["conv"] + x["sh"]) + x["e"] * x["d"]


def ssd_params(c: dict) -> int:
    """With the taps and their bias, ``A_log``, ``D`` and ``dt_bias`` a head
    and the gated norm's weight."""
    x = _dims(c)
    return ssd_matmul_params(c) + (x["taps"] + 1) * x["conv"] \
        + 3 * x["sh"] + x["e"]


def mlp_params(c: dict) -> int:
    x = _dims(c)
    return 3 * x["d"] * x["m"]


def layer_params(c: dict) -> int:
    """One block: both mixers, the MLP, the two norms."""
    return attention_params(c) + ssd_params(c) + mlp_params(c) \
        + 2 * c["hidden_size"]


def layer_matmul_params(c: dict) -> int:
    """Parameters a token multiplies against in one block."""
    return attention_params(c) + ssd_matmul_params(c) + mlp_params(c)


def params_by_part(c: dict) -> dict:
    """Parameters held, by part (embedding and head untied: each once)."""
    x = _dims(c)
    n = x["layers"]
    return {"attention": n * attention_params(c), "ssd": n * ssd_params(c),
            "mlp": n * mlp_params(c), "norms": (2 * n + 1) * x["d"],
            "embedding": x["v"] * x["d"], "head": x["d"] * x["v"]}


def params_total(c: dict) -> int:
    return sum(params_by_part(c).values())


def causal_pairs(n_query: int, start: int = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and what is before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def chunk_attention_flops(c: dict, prompt_len: int) -> float:
    """Operations the chunk attention kernel's calls NEED over one whole
    prompt, every layer: per (query, key) pair and head a ``head_dim``-wide
    score and as much for the value."""
    x = _dims(c)
    return x["layers"] * 4.0 * x["dh"] * x["h"] * causal_pairs(prompt_len)


def ssd_chunk_flops(c: dict, tokens: float) -> float:
    """Operations ONE SSD layer's recurrence needs for ``tokens`` tokens: a
    head, state and value the decay, ``dt x B`` and its add, ``S C`` (a
    multiply and an add): 5 a token."""
    x = _dims(c)
    return 5.0 * x["sh"] * x["p"] * x["n"] * tokens


def ssd_chunk_bytes(c: dict, tokens: float, chunks: float,
                    bytes_per_value: int = 2) -> float:
    """Bytes ONE call of the kernel ``ssd_chunk`` (one layer of one chunk
    program) has to move for ``tokens`` tokens in ``chunks`` rows: a token
    its heads' ``x dt`` in, ``y`` out in float32, its groups' ``B`` and ``C``
    and a log-decay a head; a row the ``[H, N, P]`` float32 state in and
    out."""
    x = _dims(c)
    return float(x["e"] * (bytes_per_value + 4)
                 + 2 * x["g"] * x["n"] * bytes_per_value
                 + 4 * x["sh"]) * tokens \
        + float(2 * 4 * x["sh"] * x["n"] * x["p"]) * chunks


def ssd_step_bytes(c: dict, live: float, bytes_per_value: int = 2) -> float:
    """Bytes ONE SSD layer of one decode step has to move for ``live``
    streams: a stream's ``[H, N, P]`` float32 state read and written where it
    lies (8.4 MB) and its convolution tail in and out; a dead row moves
    nothing."""
    x = _dims(c)
    return float(live) * (2 * 4 * x["sh"] * x["n"] * x["p"]
                          + 2 * (x["taps"] - 1) * x["conv"]
                          * bytes_per_value)


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer's matrices, causal attention and SSD recurrence for
    every token, the output head at ONE position."""
    x = _dims(c)
    return (2.0 * x["layers"] * layer_matmul_params(c) * prompt_len
            + chunk_attention_flops(c, prompt_len)
            + x["layers"] * ssd_chunk_flops(c, prompt_len)
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence, the head
    at every position. (No cell trains this architecture.)"""
    x = _dims(c)
    return (6.0 * (x["layers"] * layer_matmul_params(c) + x["d"] * x["v"])
            + 3.0 * (x["layers"] * ssd_chunk_flops(c, 1.0)
                     + chunk_attention_flops(c, seq_len) / seq_len))


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step has to read: every layer, the final
    norm and the head (untied: the embedding is a row a stream and is left
    out, as are the cache's and the states' bytes: a floor). ``live`` moves
    nothing (dense)."""
    p = params_by_part(c)
    return float(bytes_per_param) * (params_total(c) - p["embedding"])


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head in EVERY layer: the rows a token keeps for as
    long as its sequence lives (2048 B a layer at 4 KV heads of 128 in
    bfloat16)."""
    x = _dims(c)
    return x["layers"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def state_bytes_per_sequence(c: dict, bytes_per_value: int) -> int:
    """What a sequence keeps beside its rows, whatever its length: every
    layer's ``[H, N, P]`` state in float32 and the last ``taps - 1`` inputs
    of its convolution in the activation type (4,225,024 B a layer)."""
    x = _dims(c)
    return x["layers"] * (4 * x["sh"] * x["n"] * x["p"]
                          + (x["taps"] - 1) * x["conv"] * bytes_per_value)


def decode_attention_bytes(c: dict, context_tokens: float,
                           bytes_per_value: int) -> float:
    """Bytes ONE call of the decode attention kernel (one layer, one step)
    has to read: the K rows and the V rows of the ``context_tokens`` its live
    streams attend to. The queries and the output are left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value
