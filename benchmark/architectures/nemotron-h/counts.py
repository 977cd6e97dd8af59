"""Operations and bytes a Nemotron-H decoder (Nemotron-3-Super's stack) NEEDS
as one chip of its expert-parallel group holds it, from a configuration's
sizes (the keys of the model's own ``config.json``; ``layers_held`` the
PUBLISHED layers held, one sublayer each: ``M`` a Mamba-2 mixer, ``*`` an
attention, ``E`` an expert layer; ``n_routed_experts`` the experts held of the
``n_routed_experts_published`` the router scores; ``vocab_size`` the
vocabulary rows held). What the model needs, not what a program chose to do:
a token costs its projections, a causal attention over what is before it in
the ONE kind of layer that keeps keys, the SSD RECURRENCE on ``H x P x N``
states whatever the context (the chunked form's ``C B^T`` and masked products
are a program's way to run it on a matrix unit and are not counted), its
latent projections ONCE, and the EXPECTED share of its ``num_experts_per_tok``
choices that falls on a held expert (``k x held / published``: 5.5 experts at
the published sizes), each at the LATENT's width; a decode step reads the held
experts its live tokens are EXPECTED to touch; a prompt needs the output head
once. So a utilisation built on these counts cannot pass 100% while the time
covers the work, and a later PR that gathers the held rows alone (the sorted
path gathers every routed row today) shows as a gain under the same names.
"""

from __future__ import annotations


def _dims(c: dict) -> dict:
    sh, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    held = c["layers_held"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "dh": c["head_dim"],
            "v": c["vocab_size"], "sh": sh, "p": p, "g": g, "n": n,
            "e": sh * p, "conv": sh * p + 2 * g * n,
            "taps": c["conv_kernel"], "r": c["moe_latent_size"],
            "me": c["moe_intermediate_size"],
            "ms": c["n_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            "held": c["n_routed_experts"],
            "experts": c["n_routed_experts_published"],
            "k": c["num_experts_per_tok"],
            "mamba": held.count("M"), "attn": held.count("*"),
            "moe": held.count("E")}


# -- parameters -----------------------------------------------------------------

def mamba_matmul_params(c: dict) -> int:
    """The mixer's two matrices: the in-projection (gate, ``[x | B | C]``, a
    step a head) and the out-projection."""
    x = _dims(c)
    return x["d"] * (x["e"] + x["conv"] + x["sh"]) + x["e"] * x["d"]


def mamba_layer_params(c: dict) -> int:
    """With the taps and their bias, ``A_log``, ``D`` and ``dt_bias`` a head,
    the gated norm's weight and the layer's own norm (109,640,064)."""
    x = _dims(c)
    return mamba_matmul_params(c) + (x["taps"] + 1) * x["conv"] \
        + 3 * x["sh"] + x["e"] + x["d"]


def attention_matmul_params(c: dict) -> int:
    x = _dims(c)
    return x["d"] * (x["h"] + 2 * x["kv"]) * x["dh"] \
        + x["h"] * x["dh"] * x["d"]


def attention_layer_params(c: dict) -> int:
    """q, k, v and output projections (no bias) and the layer's norm
    (35,655,680)."""
    return attention_matmul_params(c) + _dims(c)["d"]


def expert_params_one(c: dict) -> int:
    """ONE routed expert: two matrices at the latent's width (5,505,024)."""
    x = _dims(c)
    return 2 * x["r"] * x["me"]


def shared_expert_params(c: dict) -> int:
    x = _dims(c)
    return 2 * x["d"] * x["ms"]


def latent_params(c: dict) -> int:
    x = _dims(c)
    return 2 * x["d"] * x["r"]


def expert_layer_params_outside_experts(c: dict) -> int:
    """An expert layer but for its routed experts: the router over EVERY
    published expert with its correction bias, both latent projections, the
    shared expert, the layer's norm (54,530,560)."""
    x = _dims(c)
    return (x["d"] + 1) * x["experts"] + latent_params(c) \
        + shared_expert_params(c) + x["d"]


def expert_layer_params_total(c: dict) -> int:
    """As HELD: with the experts this chip keeps (759,173,632)."""
    return expert_layer_params_outside_experts(c) \
        + _dims(c)["held"] * expert_params_one(c)


def expert_layer_params_published(c: dict) -> int:
    """The same layer with every published expert (2,873,102,848): what one
    chip cannot hold twice."""
    return expert_layer_params_outside_experts(c) \
        + _dims(c)["experts"] * expert_params_one(c)


def params_by_part(c: dict) -> dict:
    """Parameters held, by part (embedding and head untied: each once)."""
    x = _dims(c)
    return {"mamba": x["mamba"] * mamba_layer_params(c),
            "attention": x["attn"] * attention_layer_params(c),
            "expert_layers": x["moe"] * expert_layer_params_total(c),
            "final_norm": x["d"], "embedding": x["v"] * x["d"],
            "head": x["d"] * x["v"]}


def params_total(c: dict) -> int:
    return sum(params_by_part(c).values())


def expert_stack_params(c: dict) -> int:
    """The held routed experts of every expert layer."""
    x = _dims(c)
    return x["moe"] * x["held"] * expert_params_one(c)


# -- a token's work -------------------------------------------------------------

def experts_met(c: dict) -> float:
    """Held experts one token multiplies against in one expert layer, in
    expectation: its choices fall on the published experts alike."""
    x = _dims(c)
    return x["k"] * x["held"] / x["experts"]


def expert_layer_matmul_params_active(c: dict) -> float:
    """Parameters one token multiplies against in an expert layer HERE: the
    router, both latent projections, the shared expert and the expected held
    experts."""
    x = _dims(c)
    return x["d"] * x["experts"] + latent_params(c) \
        + shared_expert_params(c) + experts_met(c) * expert_params_one(c)


def layers_matmul_params_active(c: dict) -> float:
    """Per token through every layer held, the head left out."""
    x = _dims(c)
    return x["mamba"] * mamba_matmul_params(c) \
        + x["attn"] * attention_matmul_params(c) \
        + x["moe"] * expert_layer_matmul_params_active(c)


def causal_pairs(n_query: float, start: float = 0) -> float:
    """(query, key) pairs of ``n_query`` positions from ``start``, each
    seeing itself and what is before it."""
    return n_query * start + n_query * (n_query + 1) / 2


def chunk_attention_flops(c: dict, prompt_len: int) -> float:
    """Operations the chunk attention kernel's calls NEED over one whole
    prompt, every ATTENTION layer held: per (query, key) pair and head a
    ``head_dim``-wide score and as much for the value."""
    x = _dims(c)
    return x["attn"] * 4.0 * x["dh"] * x["h"] * causal_pairs(prompt_len)


def ssd_chunk_flops(c: dict, tokens: float) -> float:
    """Operations ONE Mamba layer's recurrence needs for ``tokens`` tokens: a
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
    """Bytes ONE Mamba layer of one decode step has to move for ``live``
    streams: a stream's ``[H, N, P]`` float32 state read and written where it
    lies (8.4 MB) and its convolution tail in and out; a dead row moves
    nothing."""
    x = _dims(c)
    return float(live) * (2 * 4 * x["sh"] * x["n"] * x["p"]
                          + 2 * (x["taps"] - 1) * x["conv"]
                          * bytes_per_value)


def prefill_flops(c: dict, prompt_len: int) -> float:
    """Forward pass of one prompt of ``prompt_len`` tokens, for its next
    token: every layer's matrices for every token (the experts at the
    expected rows held, at the latent's width), causal attention in the
    attention layers, the SSD recurrence in the Mamba layers, the output head
    at ONE position."""
    x = _dims(c)
    return (2.0 * layers_matmul_params_active(c) * prompt_len
            + chunk_attention_flops(c, prompt_len)
            + x["mamba"] * ssd_chunk_flops(c, prompt_len)
            + 2.0 * x["d"] * x["v"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward of one token of a ``seq_len`` sequence, the head
    at every position. (No cell trains this architecture: one period as held
    is 74 GB of state at 16 bytes a parameter.)"""
    x = _dims(c)
    return (6.0 * (layers_matmul_params_active(c) + x["d"] * x["v"])
            + 3.0 * (x["mamba"] * ssd_chunk_flops(c, 1.0)
                     + chunk_attention_flops(c, seq_len) / seq_len))


# -- a decode step's bytes ------------------------------------------------------

def experts_touched_share(c: dict, live: float) -> float:
    """The share of the held experts that SOME of ``live`` tokens chose: an
    expert is chosen by none of them with ``(1 - k / published) ** live``
    (99.6% at 128 streams: a step reads them all)."""
    x = _dims(c)
    return 1.0 - (1.0 - x["k"] / x["experts"]) ** max(live, 0.0)


def decode_weight_bytes(c: dict, bytes_per_param: int,
                        live: float = 1.0) -> float:
    """Bytes of weights ONE decode step over ``live`` streams has to read:
    every mixer, the attention, every expert layer's router, latent
    projections, shared expert and norms, the final norm and the head, and of
    the held experts those that some live token is EXPECTED to choose. The
    embedding is a row a stream, the cache's and the states' bytes are left
    out: a floor."""
    x = _dims(c)
    fixed = params_total(c) - expert_stack_params(c) - x["v"] * x["d"]
    return float(bytes_per_param) * (
        fixed + experts_touched_share(c, live) * expert_stack_params(c))


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    """K and V of every KV head in every ATTENTION layer held: the rows a
    token keeps for as long as its sequence lives (1024 B at 2 KV heads of
    128 in bfloat16, one layer); a Mamba or an expert layer keeps none a
    token."""
    x = _dims(c)
    return x["attn"] * 2 * x["kv"] * x["dh"] * bytes_per_value


def state_bytes_per_sequence(c: dict, bytes_per_value: int) -> int:
    """What a sequence keeps beside its rows, whatever its length: every
    Mamba layer's ``[H, N, P]`` state in float32 and the last ``taps - 1``
    inputs of its convolution in the activation type (4,255,744 B a layer;
    21,278,720 B over five)."""
    x = _dims(c)
    return x["mamba"] * (4 * x["sh"] * x["n"] * x["p"]
                         + (x["taps"] - 1) * x["conv"] * bytes_per_value)


def decode_attention_bytes(c: dict, context_tokens: float,
                           bytes_per_value: int) -> float:
    """Bytes ONE call of the decode attention kernel (one layer, one step)
    has to read: the K rows and the V rows of the ``context_tokens`` its live
    streams attend to. The queries and the output are left out: a floor."""
    x = _dims(c)
    return float(context_tokens) * 2 * x["kv"] * x["dh"] * bytes_per_value
