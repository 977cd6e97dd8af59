"""The parameter tree of a Nemotron-H decoder (Nemotron-3-Super's stack) as
the program's decoder expects it (``kubeflow_tpu/models/decoder.py``).

The published stack lists every SUBLAYER as a layer of its own (``M`` a
Mamba-2 mixer, ``*`` an attention, ``E`` an expert layer, each ``x + F(N(x))``
with its own norm); the program reads it as BLOCKS, a mixer or an attention
and the expert layer behind it, a mixer in front of an attention being a
block of its one sublayer (``blocks_of``). The program cuts its blocks into
groups that are each one scan: whole periods of the shortest pattern of
(kind, has a feed-forward part), and what is left of a cut period as a group
of its own (``groups_of``: ``MEMEMEM*EME`` is the five blocks ``ME ME ME M *E``
under ``layers`` and the one block ``ME`` under ``layers_rest``). In a group,
``ln1`` is stacked over its blocks, an operator's leaves (``ssd``: ``w_z`` [D,
E], ``w_xbc`` [D, C], ``w_dt`` [D, H]: the in-projection ``[z | x | B | C |
dt]`` held as three column blocks, the same products; ``conv`` [taps, C] with
``[-1]`` the current position, ``conv_b``; ``a_log``, ``d_skip``, ``dt_bias``
[H]; ``ssd_norm`` [E]; ``w_out`` [E, D]. ``attn``: ``wq`` / ``wk`` / ``wv``
[D, heads, Dh], ``wo`` [heads, Dh, D]) over the blocks of its kind, and
``ln2`` and the expert layer ``mlp`` over the blocks that HAVE one: the router
over the PUBLISHED experts and its correction bias, the two latent
projections ``latent_down`` [D, R] and ``latent_up`` [R, D], the experts HELD
``up`` [held, R, M] and ``down`` [held, M, R] (two matrices: squared ReLU
takes no gate), the shared expert ``shared`` (``up`` [D, Ms], ``down`` [Ms,
D]); an untied head over the vocabulary rows held.

Scales are the usual ones (1/sqrt(fan_in); the embedding and the norms at 1;
the convolution's taps at 1/sqrt(taps), its bias 0), ``A_log = log U(1, 16)``
a head, ``D = 1``, ``dt_bias`` the inverse softplus of a step log-uniform in
[``time_step_min``, ``time_step_max``] floored at ``time_step_floor``
(Mamba-2's initialisation, the keys the row gives for it). ASSUMED, and said
in the configuration file: the router's correction bias is a trained buffer in
the published model; here it is the SAME multiset of values in every layer for
every seed (the normal's quantiles times ``BIAS_DEVIATION``), placed by the
seed STRATIFIED over the blocks of ``n_routed_experts`` consecutive experts
(``balanced_bias``, PR 40's rule: at the published sizes 4 blocks of 128), so
that the share of a token's choices that falls on the held experts is every
seed's alike.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import stacked_normal

BIAS_DEVIATION = 0.05
A_RANGE = (1.0, 16.0)
KINDS = {"M": "ssd", "*": "attn"}


def blocks_of(pattern: str) -> list:
    """The published pattern as blocks: (the operator's key, whether an
    expert layer follows it)."""
    out = []
    for i, c in enumerate(pattern):
        if c == "E":
            if not out or pattern[i - 1] == "E":
                raise ValueError(f"{pattern!r}: an expert layer at {i} "
                                 "behind no mixer or attention")
        else:
            out.append((KINDS[c], pattern[i + 1:i + 2] == "E"))
    return out


def groups_of(blocks: list) -> list:
    """The blocks as the program groups them for its scans: whole periods
    of their shortest pattern under ``layers``, what a cut period leaves
    under ``layers_rest``: [(name, blocks)]. (A pattern that REPEATS a
    stretch of two kinds or more at least twice in a row is cut there too by
    the program; ``program.py`` holds the two against each other.)"""
    n = len(blocks)
    p = next(p for p in range(1, n + 1)
             if all(blocks[i] == blocks[i - p] for i in range(p, n)))
    whole = n // p * p
    out = [("layers", blocks[:whole])]
    if whole < n:
        out.append(("layers_rest", blocks[whole:]))
    return out


def ssd_widths(c: dict) -> tuple:
    """(E the mixer's inner width, G x N a group block's width, H heads)."""
    return (c["mamba_num_heads"] * c["mamba_head_dim"],
            c["n_groups"] * c["ssm_state_size"], c["mamba_num_heads"])


def ssd_tree(c: dict, key, n: int, dtype) -> dict:
    d, taps = c["hidden_size"], c["conv_kernel"]
    e, gn, h = ssd_widths(c)
    conv_dim = e + 2 * gn
    ks = iter(jax.random.split(key, 7))
    step = jnp.exp(jax.random.uniform(
        next(ks), (n, h), jnp.float32, math.log(c["time_step_min"]),
        math.log(c["time_step_max"])))
    step = jnp.maximum(step, c["time_step_floor"])
    return {
        "w_z": stacked_normal(next(ks), (n,), (d, e), d ** -0.5, dtype),
        "w_xbc": stacked_normal(next(ks), (n,), (d, conv_dim), d ** -0.5,
                                dtype),
        "w_dt": stacked_normal(next(ks), (n,), (d, h), d ** -0.5, dtype),
        "conv": stacked_normal(next(ks), (n,), (taps, conv_dim),
                               taps ** -0.5, dtype),
        "conv_b": jnp.zeros((n, conv_dim), dtype),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (n, h), jnp.float32, *A_RANGE)).astype(dtype),
        "d_skip": jnp.ones((n, h), dtype),
        # softplus(dt_bias) = step
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "ssd_norm": jnp.ones((n, e), dtype),
        "w_out": stacked_normal(next(ks), (n,), (e, d), e ** -0.5, dtype),
    }


def attention_tree(c: dict, key, n: int, dtype) -> dict:
    d, h, kv, dh = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    ks = iter(jax.random.split(key, 4))
    return {
        "wq": stacked_normal(next(ks), (n,), (d, h, dh), d ** -0.5, dtype),
        "wk": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wv": stacked_normal(next(ks), (n,), (d, kv, dh), d ** -0.5, dtype),
        "wo": stacked_normal(next(ks), (n,), (h, dh, d), (h * dh) ** -0.5,
                             dtype),
    }


def balanced_bias(key, n: int, experts: int, held: int) -> jax.Array:
    """[n, experts] float32 correction biases: in every layer the normal's
    quantiles at (i + 0.5) / experts times ``BIAS_DEVIATION``, the same
    multiset for every seed, placed by the seed so that each block of
    ``held`` consecutive experts (one chip's share of the group) holds one
    value from each of ``held`` strata of the sorted values."""
    chips = experts // held
    sorted_values = BIAS_DEVIATION * jax.scipy.special.ndtri(
        (jnp.arange(chips * held, dtype=jnp.float32) + 0.5) / (chips * held))
    strata = sorted_values.reshape(held, chips)

    def layer(k):
        across, within = jax.random.split(k)
        by_chip = jax.random.permutation(across, strata, axis=1,
                                         independent=True).T
        return jax.random.permutation(within, by_chip, axis=1,
                                      independent=True).reshape(-1)

    return jax.vmap(layer)(jax.random.split(key, n))


def expert_tree(c: dict, key, n: int, dtype) -> dict:
    d, r, m = (c["hidden_size"], c["moe_latent_size"],
               c["moe_intermediate_size"])
    ms = c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    held, experts = c["n_routed_experts"], c["n_routed_experts_published"]
    ks = iter(jax.random.split(key, 8))
    return {
        "router": stacked_normal(next(ks), (n,), (d, experts), d ** -0.5,
                                 dtype),
        "router_bias": balanced_bias(next(ks), n, experts, held),
        "latent_down": stacked_normal(next(ks), (n,), (d, r), d ** -0.5,
                                      dtype),
        "latent_up": stacked_normal(next(ks), (n,), (r, d), r ** -0.5,
                                    dtype),
        "up": stacked_normal(next(ks), (n, held), (r, m), r ** -0.5, dtype),
        "down": stacked_normal(next(ks), (n, held), (m, r), m ** -0.5,
                               dtype),
        "shared": {
            "up": stacked_normal(next(ks), (n,), (d, ms), d ** -0.5, dtype),
            "down": stacked_normal(next(ks), (n,), (ms, d), ms ** -0.5,
                                   dtype)},
    }


def group_tree(c: dict, key, blocks: list, dtype) -> dict:
    d = c["hidden_size"]
    k_ssd, k_attn, k_moe = jax.random.split(key, 3)
    count = {kind: sum(k == kind for k, _ in blocks)
             for kind in KINDS.values()}
    fed = sum(has for _, has in blocks)
    out = {"ln1": jnp.ones((len(blocks), d), dtype)}
    if count["ssd"]:
        out["ssd"] = ssd_tree(c, k_ssd, count["ssd"], dtype)
    if count["attn"]:
        out["attn"] = attention_tree(c, k_attn, count["attn"], dtype)
    if fed:
        out["ln2"] = jnp.ones((fed, d), dtype)
        out["mlp"] = expert_tree(c, k_moe, fed, dtype)
    return out


def param_tree(c: dict, key: jax.Array, dtype) -> dict:
    """The decoder's parameters for the sizes in ``c`` (keys of the model's
    ``config.json``; ``layers_held`` the published layers held, in the
    pattern's letters; ``n_routed_experts`` the experts held, ``vocab_size``
    the rows held)."""
    d, v = c["hidden_size"], c["vocab_size"]
    groups = groups_of(blocks_of(c["layers_held"]))
    k_embed, k_head, *k_groups = jax.random.split(key, 2 + len(groups))
    return {
        "embed": stacked_normal(k_embed, (), (v, d), 1.0, dtype),
        **{name: group_tree(c, k, blocks, dtype)
           for (name, blocks), k in zip(groups, k_groups)},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stacked_normal(k_head, (), (d, v), d ** -0.5, dtype),
    }
