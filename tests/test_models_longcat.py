"""LongCat-Flash's block in the model (the ``tiny-longcat-flash`` preset: two
published layers, each two latent attentions and two dense MLPs with ONE
expert layer on a shortcut beside them; a softmax router over 16 experts and
8 that are the identity, top-4; both rank factors), on the CPU:
``decoder_forward`` against the benchmark's plain reference on seeded
weights, and against the reference with each part of the block got wrong; the
router's third score by hand; ``_moe_sorted`` against ``_moe_dense`` with zero
experts, a token whose choices are ALL zero experts and one with none among
them; the shares of all chips and the zero experts' term ONCE adding up to
the uncut layer; the three row counts against a count by hand; the rank
factors on the queries and the cached row; the scanned pair of blocks against
the list of blocks; the counts of the published sizes; what the config and
the expert layer refuse by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture, correctness
from benchmark import manifest as mf
from benchmark.weights import make_params
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import DecoderConfig, preset
from kubeflow_tpu.models.decoder import (
    decoder_forward, decoder_loss, init_decoder_params, layer_groups,
    unit_blocks, period_units,
)

CONF = mf.load_json("benchmark/configs/rehearsal-tiny-longcat.json")
REF = architecture.part(CONF, "reference")


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny-longcat-flash", dtype="float32",
                  param_dtype="float32")


@pytest.fixture(scope="module")
def params():
    """The benchmark's seeded tree (a stratified correction bias), float32."""
    return make_params(CONF, 11, "float32")


def moe_cfg(**kw):
    """One expert layer of the tiny preset, every expert held."""
    return preset("tiny-longcat-flash", dtype="float32",
                  param_dtype="float32", experts_held=0, **kw)


def moe_params(cfg, seed=3):
    p, _ = L.init_moe(jax.random.PRNGKey(seed), cfg)
    p["router_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (cfg.router_width,))
    return p


# -- the model against the plain reference -----------------------------------------

def test_the_seeded_tree_is_the_programs_tree(cfg, params):
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape
    layers = params["layers"]
    # four blocks, an expert layer a PAIR: "moe" beside every block's "mlp"
    assert layers["attn"]["wqa"].shape == (4, 64, 24)
    assert layers["mlp"]["gate"].shape == (4, 64, 160)
    assert layers["moe"]["router"].shape == (2, 64, 24)
    assert layers["moe"]["router_bias"].shape == (2, 24)
    assert layers["moe"]["gate"].shape == (2, 4, 64, 48)
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_decoder_forward_is_the_references_forward(cfg, seed):
    params = make_params(CONF, seed, "float32")
    toks = correctness.check_tokens(seed, 0, 70, 256)
    with jax.default_matmul_precision("highest"):
        got, _, _ = decoder_forward(params, jnp.asarray(toks[None]), cfg)
        want = REF.logits(params, jnp.asarray(toks), CONF)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=5e-5)


@pytest.mark.parametrize("variant", REF.VARIANTS[1:])
def test_a_block_got_wrong_is_far_from_the_model(cfg, params, variant):
    """The reference's own controls: the expert layer left out, the zero
    experts' term left out, the result joined a sublayer early, both rank
    factors left out: each far from what ``decoder_forward`` computes."""
    toks = correctness.check_tokens(5, 0, 64, 256)
    with jax.default_matmul_precision("highest"):
        got, _, _ = decoder_forward(params, jnp.asarray(toks[None]), cfg)
        other = REF.logits(params, jnp.asarray(toks), CONF, variant=variant)
    assert np.median(correctness.position_errors(got[0], other)) > 0.1


def test_an_unknown_variant_is_refused(params):
    with pytest.raises(ValueError, match="variant"):
        REF.logits(params, jnp.arange(8), CONF, variant="other")


def test_the_list_of_blocks_is_the_scanned_pairs(cfg, params):
    """``scan_layers`` off: a list of blocks, a pair's first holding the
    expert layer; the same numbers as the scan over pairs."""
    stack = params["layers"]
    blocks = []
    for i in range(cfg.n_layers):
        b = {k: jax.tree.map(lambda a: a[i], v)
             for k, v in stack.items() if k != "moe"}
        if i % 2 == 0:
            b["moe"] = jax.tree.map(lambda a: a[i // 2], stack["moe"])
        blocks.append(b)
    toks = jnp.asarray(correctness.check_tokens(2, 0, 40, 256))[None]
    want, _, _ = decoder_forward(params, toks, cfg)
    listed = dataclasses.replace(cfg, scan_layers=False)
    got, _, _ = decoder_forward({**params, "layers": blocks}, toks, listed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    init = init_decoder_params(jax.random.PRNGKey(1), listed)
    assert ["moe" in b for b in init["layers"]] == [True, False, True, False]


def test_a_scan_unit_is_a_pair_whose_first_block_starts_the_experts(cfg,
                                                                    params):
    (name, gcfg, first), = layer_groups(cfg)
    assert (name, first, gcfg.period) == ("layers", 0, ("attention",) * 2)
    assert cfg.kinds == ("attention",) * 4
    unit = jax.tree.map(lambda a: a[1], period_units(params["layers"], gcfg))
    (k0, i0, b0), (k1, i1, b1) = unit_blocks(unit, gcfg)
    assert (k0, i0, k1, i1) == ("attention", 0, "attention", 1)
    assert "moe" in b0 and "moe" not in b1
    np.testing.assert_array_equal(b0["moe"]["router"],
                                  params["layers"]["moe"]["router"][1])
    np.testing.assert_array_equal(b1["mlp"]["gate"],
                                  params["layers"]["mlp"]["gate"][3])
    assert [cfg.expert_layer(i) for i in range(4)] == [0, 0, 1, 1]
    assert [preset("tiny-glm").expert_layer(i) for i in range(3)] == [0, 1, 2]


def test_the_loss_trains_every_part_of_the_pair(cfg, params):
    toks = jnp.asarray(correctness.check_tokens(4, 0, 33, 256))[None]

    def loss(p):
        return decoder_loss(p, toks, cfg)[0]

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for part in ("attn", "mlp", "moe"):
        for leaf in jax.tree.leaves(grads["layers"][part]):
            if leaf.dtype == jnp.float32 and leaf.ndim > 2:
                assert float(jnp.abs(leaf).max()) > 0, part


# -- the router's third score, by hand ------------------------------------------

def test_the_router_scores_every_output_and_does_not_normalise():
    cfg = moe_cfg()
    p = moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 64))
    logits, idx, w = L.route(p, x, cfg)
    lg = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    s = np.exp(lg - lg.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)                     # over all 24 outputs
    chosen = np.argsort(-(s + np.asarray(p["router_bias"])), axis=-1)[:, :4]
    assert logits.shape == (5, 24)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(chosen, -1))
    want = 6.0 * np.take_along_axis(s, np.asarray(idx), -1)    # without b
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
    assert not np.allclose(np.asarray(w).sum(-1), 6.0)         # no sum to 1


def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg = moe_cfg()
    p = moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (6, 64))
    _, idx0, w0 = L.route({**p, "router_bias": jnp.zeros((24,))}, x, cfg)
    pushed = jnp.zeros((24,)).at[20].set(1.0)         # a zero expert, always
    _, idx1, w1 = L.route({**p, "router_bias": pushed}, x, cfg)
    assert (np.asarray(idx1) == 20).any(-1).all()
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    at = np.asarray(idx1) == 20
    np.testing.assert_allclose(np.asarray(w1)[at],
                               6.0 * np.asarray(s)[:, 20], rtol=1e-5)


# -- zero experts -----------------------------------------------------------------

def steered(cfg):
    """Tokens and a router whose choice follows a token's first value: token
    0 (+) chooses zero experts ALONE, token 1 (-) none, the rest as they
    fall."""
    p = moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 12, 64))
    x = x.at[0, 0, 0].set(40.0).at[0, 1, 0].set(-40.0)
    pull = jnp.where(jnp.arange(24) >= 16, 1.0, -1.0)
    p["router"] = p["router"].at[0].set(pull)
    return p, x


def test_sorted_is_dense_with_zero_experts_all_and_none():
    cfg = moe_cfg()
    p, x = steered(cfg)
    _, idx, w = L.route(p, x.reshape(-1, 64), cfg)
    idx = np.asarray(idx)
    assert (idx[0] >= 16).all() and (idx[1] < 16).all()
    mixed = ((idx >= 16).any(-1) & (idx < 16).any(-1))
    assert mixed.any()
    dense, _ = L.moe_block(p, x, dataclasses.replace(cfg, moe_impl="dense"))
    got, _, rows = L.moe_block(p, x, cfg, rows_out=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    # the token of zero experts alone: its own input times its weights' sum
    np.testing.assert_allclose(
        np.asarray(got[0, 0]), float(np.asarray(w)[0].sum()) * x[0, 0],
        rtol=1e-5)
    # routed, held (every expert with weights is held here), zero: by hand
    assert [int(n) for n in rows] == [12 * 4, int((idx < 16).sum()),
                                      int((idx >= 16).sum())]


def test_the_zero_experts_rows_cost_no_group_of_the_grouped_matmul(
        monkeypatch):
    """A zero expert's row sorts behind every held group: the grouped
    matmul's sizes count the rows of experts with weights alone."""
    cfg = moe_cfg()
    p, x = steered(cfg)
    seen = []
    real = L.grouped_matmul

    def spy(rows, w, sizes, c):
        seen.append(np.asarray(sizes))
        return real(rows, w, sizes, c)

    monkeypatch.setattr(L, "grouped_matmul", spy)
    with jax.disable_jit():
        L.moe_block(p, x, cfg)
    _, idx, _ = L.route(p, x.reshape(-1, 64), cfg)
    want = np.bincount(np.asarray(idx).reshape(-1), minlength=24)[:16]
    assert len(seen) == 3 and all(np.array_equal(s, want) for s in seen)
    assert want.sum() < 12 * 4


def test_the_shares_of_all_chips_and_the_zero_experts_once_add_up():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer (the tiny
    preset's group; the published one is thirty-two of 16): every chip
    computes the zero experts' term where the token is, so over the group it
    counts ONCE, as a shared expert does: the held parts and that term are
    the uncut layer's result."""
    whole = moe_cfg()
    p, x = steered(whole)
    want, _, all_rows = L.moe_block(p, x, whole, rows_out=True)
    _, idx, w = L.route(p, x.reshape(-1, 64), whole)
    zero = jnp.sum(jnp.where(idx >= 16, w, 0), -1).reshape(1, 12, 1) * x
    parts, held, zeros = [], 0, set()
    for chip in range(4):
        own_cfg = dataclasses.replace(whole, experts_held=4,
                                      expert_offset=4 * chip)
        own = {**p, **{n: p[n][4 * chip:4 * chip + 4]
                       for n in L.EXPERT_LEAVES}}
        out, _, rows = L.moe_block(own, x, own_cfg, rows_out=True)
        assert int(rows[0]) == 12 * 4
        held += int(rows[1])
        zeros.add(int(rows[2]))
        parts.append(out - zero)
    assert zeros == {int(all_rows[2])} and held == int(all_rows[1])
    assert held + int(all_rows[2]) == 12 * 4
    np.testing.assert_allclose(np.asarray(sum(parts) + zero),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_rows_counts_are_two_without_zero_experts_and_three_with():
    glm = preset("tiny-glm-5", dtype="float32", param_dtype="float32")
    p, _ = L.init_moe(jax.random.PRNGKey(0), glm)
    x = jnp.ones((1, 8, 64))
    assert L.moe_block(p, x, glm, rows_out=True)[2].shape == (2,)
    cfg = preset("tiny-longcat-flash", dtype="float32",
                 param_dtype="float32")
    p, _ = L.init_moe(jax.random.PRNGKey(0), cfg)
    rows = L.moe_block(p, x, cfg, rows_out=True)[2]
    assert rows.shape == (3,) and int(rows[0]) == 8 * 4


# -- the rank factors ---------------------------------------------------------------

def test_the_rank_factors_scale_the_queries_and_the_cached_latent(cfg,
                                                                 params):
    a = jax.tree.map(lambda w: w[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64))
    pos = jnp.arange(9)[None]
    plain = dataclasses.replace(cfg, latent_rank_scale=False)
    qn, qr, row, cq = L.latent_qkv(a, x, pos, cfg)
    qn0, qr0, row0, cq0 = L.latent_qkv(a, x, pos, plain)
    s_q, s_kv = (64 / 24) ** 0.5, (64 / 40) ** 0.5
    np.testing.assert_allclose(np.asarray(qn), s_q * np.asarray(qn0),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(qr), s_q * np.asarray(qr0),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cq), np.asarray(cq0))
    # the row as the cache holds it: the latent scaled, the rotary key not
    np.testing.assert_allclose(np.asarray(row[..., :40]),
                               s_kv * np.asarray(row0[..., :40]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(row[..., 40:]),
                                  np.asarray(row0[..., 40:]))
    assert row.shape[-1] == 128 and not np.asarray(row[..., 48:]).any()


# -- counts and what is refused ------------------------------------------------------

def test_the_published_sizes_count_the_published_parameters():
    cfg = preset("longcat-flash-omni")
    assert (cfg.n_layers, cfg.router_width, cfg.experts_per_token) \
        == (56, 768, 12)
    assert cfg.num_params() == 28 * 19_966_227_200 + 2 * 131072 * 6144 \
        + 6144 == 560_664_980_480
    held = dataclasses.replace(cfg, n_layers=8, experts_held=16,
                               vocab_size=16384)
    assert held.num_params() == 5_172_749_312
    # a quarter of a held expert a token in expectation
    assert held._mlp_params(True) == int(0.25 * 3 * 6144 * 2048)
    # the older presets count what they counted
    assert preset("glm-5").num_params() == 743_911_218_432
    assert preset("mixtral-8x7b").num_params() == 46_702_792_704


def test_the_config_refuses_what_the_fields_cannot_mean():
    with pytest.raises(ValueError, match="zero experts"):
        preset("tiny", zero_experts=4)
    with pytest.raises(ValueError, match="shortcut"):
        preset("tiny", moe_shortcut=True)
    with pytest.raises(ValueError, match="shortcut"):
        preset("tiny-longcat-flash", n_layers=3)
    with pytest.raises(ValueError, match="shortcut"):
        preset("tiny-longcat-flash", leading_dense_layers=1)
    with pytest.raises(ValueError, match="shortcut"):
        preset("tiny-longcat-flash", layer_kinds=("attention", "window"),
               attn_window=8)
    assert "zero_experts" in {f.name for f in
                              dataclasses.fields(DecoderConfig)}


def test_a_capacity_path_refuses_zero_experts_by_name():
    cfg = moe_cfg(moe_impl="dispatch")
    p = moe_params(cfg)
    with pytest.raises(NotImplementedError, match="zero experts"):
        L.moe_block(p, jnp.ones((1, 4, 64)), cfg)
    dense = moe_cfg(moe_impl="dense")
    with pytest.raises(NotImplementedError, match="counts their rows"):
        L.moe_block(p, jnp.ones((1, 4, 64)), dense, rows_out=True)
    with pytest.raises(ValueError, match="router_score"):
        L.route(p, jnp.ones((4, 64)), moe_cfg(router_score="other"))
