"""Nemotron-3-Super's structure in the MODEL (ISSUE 61) at the tiny preset on
the CPU: a stack whose published layers are ONE sublayer each, read as blocks
of an operator (an SSD mixer of its own kind, "ssd", or an attention) and the
expert layer behind it, a block in front of an attention being its operator
alone (``DecoderConfig.ffn_free``); experts behind a latent projection
(``moe_latent_dim``); squared-ReLU MLPs of two matrices. The whole forward
against the benchmark's plain reference (which walks the PUBLISHED layers and
shares no code with the program), the sorted expert path against the dense
oracle behind the projection, the four chips' shares adding up to the uncut
layer, the block without a feed-forward part by hand, the tree, the counts
and what a config cannot be, by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture
from benchmark.manifest import load_json
from benchmark.weights import make_params
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import blocks_of, preset
from kubeflow_tpu.models.decoder import (
    _block_forward, decoder_forward, decoder_param_specs,
    init_decoder_params, layer_groups,
)

REHEARSAL = load_json("benchmark/configs/rehearsal-tiny-nemotronh.json")
BASE = preset("tiny-nemotron-h", dtype="float32", param_dtype="float32")
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _tokens(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        3, BASE.vocab_size, n).astype(np.int32)


def _reference():
    return architecture.part(REHEARSAL, "reference")


def _seeded(seed: int = 7):
    """The benchmark's tree for the rehearsal file, in float32 (a balanced
    correction bias, Mamba-2's initialisation)."""
    return make_params(REHEARSAL, seed, "float32")


# -- the stack ---------------------------------------------------------------------

def test_the_published_pattern_reads_as_blocks():
    kinds, free = blocks_of(PUBLISHED)
    assert len(PUBLISHED) == 88 and len(kinds) == 48
    assert (kinds.count("ssd"), kinds.count("attention")) == (40, 8)
    # the eight mixers in front of an attention are blocks of one sublayer
    assert free == (3, 8, 13, 19, 25, 31, 37, 42)
    assert all(kinds[i] == "ssd" and kinds[i + 1] == "attention"
               for i in free)
    assert blocks_of("MEMEMEM*EME") == (
        ("ssd", "ssd", "ssd", "ssd", "attention", "ssd"), (3,))
    assert blocks_of("MEM*EME") == (BASE.layer_kinds, BASE.ffn_free)
    with pytest.raises(ValueError, match="behind no operator"):
        blocks_of("EM")
    with pytest.raises(ValueError, match="behind no operator"):
        blocks_of("MEE")


def test_the_published_counts_are_the_hand_written_ones():
    """ISSUE 61's arithmetic, from the program's own config: the whole
    published model and one chip's cut of it."""
    full = preset("nemotron-3-super-120b-a12b")
    assert (full.kinds, full.ffn_free) == blocks_of(PUBLISHED)
    assert full._ssd_params() + full.hidden == 109_640_064
    assert full._attn_params() + full.hidden == 35_655_680
    assert full._mlp_params(False) + full.hidden == 2_873_102_848
    assert full.num_params() == 120_668_707_840
    held = dataclasses.replace(
        full, n_layers=6, layer_kinds=blocks_of("MEMEMEM*EME")[0],
        ffn_free=(3,), experts_held=128, vocab_size=32768)
    assert held._mlp_params(False) + held.hidden == 759_173_632
    assert held.num_params() == 4_648_163_712
    # a token multiplies against 5.5 held experts of 2 x 1024 x 2688
    assert held._mlp_params(True) == int(5.5 * 5_505_024) + 44_040_192 \
        + 2 * 4096 * 1024
    assert BASE.num_params() == sum(
        a.size for a in jax.tree.leaves(
            init_decoder_params(jax.random.PRNGKey(0), BASE)))


def test_the_tree_is_groups_of_blocks_some_without_a_feed_forward():
    """The tiny stack is a period of three blocks and a cut period of one;
    ``ln1`` runs over a group's blocks, an operator's leaves over the blocks
    of its kind, ``ln2`` and the expert layer over the blocks that have one;
    the benchmark's tree is the program's, leaf for leaf."""
    groups = layer_groups(BASE)
    assert [(n, g.layer_kinds, g.ffn_free, g.n_layers, first)
            for n, g, first in groups] == [
        ("layers", ("ssd", "ssd", "attention"), (1,), 3, 0),
        ("layers_rest", ("ssd",), (), 1, 3)]
    params = init_decoder_params(jax.random.PRNGKey(0), BASE)
    first = params["layers"]
    assert first["ln1"].shape[0] == 3 and first["ln2"].shape[0] == 2
    assert first["ssd"]["w_z"].shape[0] == 2
    assert first["attn"]["wq"].shape[0] == 1
    mlp = first["mlp"]
    assert mlp["router"].shape == (2, 64, 16) and "gate" not in mlp \
        and "gate" not in mlp["shared"]
    assert mlp["up"].shape == (2, 4, 32, 32)        # held, latent, width
    assert mlp["down"].shape == (2, 4, 32, 32)
    assert mlp["latent_down"].shape == (2, 64, 32)
    assert mlp["latent_up"].shape == (2, 32, 64)
    assert mlp["shared"]["up"].shape == (2, 64, 64)
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert jax.tree.map(lambda a: a.shape, _seeded()) == shapes
    assert jax.tree.structure(
        decoder_param_specs(BASE),
        is_leaf=lambda s: isinstance(s, tuple)) == jax.tree.structure(shapes, is_leaf=lambda s: isinstance(s, tuple))
    # every preset's groups cover its layers once, in order
    for name in ("nemotron-3-super-120b-a12b", "tiny-nemotron-h"):
        cfg = preset(name)
        got = layer_groups(cfg)
        assert [first for _, _, first in got] == list(np.cumsum(
            [0] + [g.n_layers for _, g, _ in got[:-1]]))
        assert sum(g.n_layers for _, g, _ in got) == cfg.n_layers
        assert sum((g.kinds for _, g, _ in got), ()) == cfg.kinds
        assert sum((g.fed for _, g, _ in got), ()) == cfg.fed


def test_the_unrolled_stack_is_the_scanned_one():
    tokens = jnp.asarray(_tokens(1, 24))[None]
    scanned = decoder_forward(
        init_decoder_params(jax.random.PRNGKey(3), BASE), tokens, BASE)[0]
    listed = dataclasses.replace(BASE, scan_layers=False)
    params = init_decoder_params(jax.random.PRNGKey(3), listed)
    assert "mlp" not in params["layers"][1] and "ln2" not in \
        params["layers"][1]
    np.testing.assert_allclose(decoder_forward(params, tokens, listed)[0],
                               scanned, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("change,match", [
    (dict(ffn_free=(4,)), "names places of the 4"),
    (dict(ffn_free=(1,), moe_shortcut=True, layer_kinds=()), "ffn_free"),
    (dict(moe_impl="dispatch"), "sorted or dense expert layer"),
    (dict(zero_experts=4), "zero"),
    (dict(layer_kinds=("ssd", "parallel", "attention", "ssd")),
     "beside another kind"),
    (dict(ssd_heads=0), "parallel and ssd layers need"),
    (dict(layer_kinds=("ssd", "mamba", "attention", "ssd")),
     "unknown layer kinds"),
])
def test_what_such_a_config_cannot_be_is_refused_by_name(change, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        dataclasses.replace(BASE, **change)


# -- the forward against the plain reference -----------------------------------------

def test_the_forward_is_the_plain_reference_on_logits_in_float32():
    """``decoder_forward`` over the program's blocks against the reference's
    walk over the PUBLISHED layers (the recurrence token by token, one
    softmax, the experts one at a time behind the latent projections), on
    the benchmark's seeded tree: tight in float32."""
    params, tokens = _seeded(), _tokens(5, 46)
    with jax.default_matmul_precision("highest"):
        want = _reference().logits(params, jnp.asarray(tokens), REHEARSAL)
    got = decoder_forward(params, jnp.asarray(tokens)[None], BASE)[0][0]
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
    assert float(jnp.abs(want).max()) > 1.0


@pytest.mark.parametrize("variant", [
    "no_mamba", "no_attention", "no_experts", "experts_read_hidden",
    "plain_relu", "one_norm_group"])
def test_the_comparison_sees_every_part_of_the_stack(variant):
    """The reference with ONE thing wrong is far from the program: what the
    chip's blind controls read at the published widths
    (scripts/nemotronh_kernels_chip.py --parts blind)."""
    from benchmark import correctness

    params, tokens = _seeded(), _tokens(6, 46)
    got = decoder_forward(params, jnp.asarray(tokens)[None], BASE)[0][0]
    with jax.default_matmul_precision("highest"):
        wrong = _reference().logits(params, jnp.asarray(tokens), REHEARSAL,
                                    variant=variant)
    assert float(np.median(correctness.position_errors(got, wrong))) > 0.1


def test_a_block_without_a_feed_forward_part_is_its_operator_alone():
    """``x + F(N(x))`` by hand for the tiny stack's second block (a mixer in
    front of an attention): no second norm, no expert layer, no aux."""
    params = init_decoder_params(jax.random.PRNGKey(2), BASE)
    gcfg = layer_groups(BASE)[0][1]
    ssd = jax.tree.map(lambda a: a[1], params["layers"]["ssd"])
    bp = {"ssd": ssd, "ln1": params["layers"]["ln1"][1]}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, BASE.hidden))
    out, cache, aux = _block_forward(bp, x, None, gcfg)
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + BASE.norm_eps) * bp["ln1"]
    want = x + L.ssd_block(ssd, u, gcfg)[0]
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    assert cache is None and float(aux) == 0.0
    # the same operator in a block WITH its expert layer adds it
    fed = {**bp, "ln2": params["layers"]["ln2"][0],
           "mlp": jax.tree.map(lambda a: a[0], params["layers"]["mlp"])}
    more, _, _ = _block_forward(fed, x, None, gcfg)
    assert float(jnp.abs(more - out).max()) > 1e-2


# -- experts behind a latent projection ----------------------------------------------

def _expert_layer(cfg, seed=0):
    p, _ = L.init_moe(jax.random.PRNGKey(seed), cfg)
    # a bias that moves choices, as a trained one does
    p["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), p["router_bias"].shape)
    return p


def test_sorted_experts_are_the_dense_oracle_behind_the_latent_projection():
    """Every expert held: the sorted path (rows of the LATENT's width sorted
    by expert, two grouped products) against the dense oracle (every expert
    on every token's latent row), and both against the equations by hand:
    the router and the shared expert read the hidden, the routed experts the
    latent, squared ReLU, no gate."""
    whole = dataclasses.replace(BASE, experts_held=0)
    p = _expert_layer(whole)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 13, whole.hidden))
    got, _ = L.moe_block(p, x, whole)
    dense, _ = L.moe_block(p, x, dataclasses.replace(whole,
                                                     moe_impl="dense"))
    np.testing.assert_allclose(got, dense, rtol=2e-5, atol=2e-5)
    s = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(s + p["router_bias"], whole.experts_per_token)
    w = jnp.take_along_axis(s, idx, -1)
    w = whole.router_scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    lat = x @ p["latent_down"]
    each = jnp.einsum(
        "bsem,emr->bser",
        jnp.square(jax.nn.relu(jnp.einsum("bsr,erm->bsem", lat, p["up"]))),
        p["down"])
    routed = jnp.einsum("bskr,bsk->bsr",
                        jnp.take_along_axis(each, idx[..., None], 2), w)
    want = routed @ p["latent_up"] + jnp.square(jax.nn.relu(
        x @ p["shared"]["up"])) @ p["shared"]["down"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert lat.shape[-1] == 32 != whole.hidden


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: the held parts of every share, summed
    BEHIND ``W_up`` (the up-projection is linear: the exchange would carry
    latent rows), with the shared expert counted ONCE, are the uncut layer;
    and a share's counted rows are its quarter of the routed ones."""
    whole = dataclasses.replace(BASE, experts_held=0)
    p = _expert_layer(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 11, whole.hidden))
    uncut, _ = L.moe_block(p, x, whole)
    shared = L.mlp_block(p["shared"], x, whole)
    total, held_rows = jnp.zeros_like(uncut), 0
    for chip in range(4):
        cfg = dataclasses.replace(BASE, experts_held=4,
                                  expert_offset=4 * chip)
        part = {**p, "up": p["up"][4 * chip:4 * chip + 4],
                "down": p["down"][4 * chip:4 * chip + 4]}
        out, _, rows = L.moe_block(part, x, cfg, rows_out=True)
        total = total + (out - shared)
        assert int(rows[0]) == 3 * 11 * 4
        held_rows += int(rows[1])
    np.testing.assert_allclose(total + shared, uncut, rtol=3e-5, atol=3e-5)
    assert held_rows == 3 * 11 * 4
