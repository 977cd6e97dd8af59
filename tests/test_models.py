"""Model numerics tests: forward shapes, decode==full equivalence,
sharded-vs-unsharded equivalence (the test class the reference never needed —
SURVEY.md §4 rebuild translation (d))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import (
    preset, init_decoder_params, decoder_forward, decoder_loss,
)
from kubeflow_tpu.models.decoder import decoder_param_specs
from kubeflow_tpu.parallel.sharding import (
    DEFAULT_RULES, logical_to_mesh_axes, shard_params,
)
from kubeflow_tpu.runtime.mesh import build_mesh


@pytest.mark.parametrize("name", ["tiny", "tiny-gemma", "tiny-moe",
                                  "tiny-glm"])
def test_forward_shapes_and_loss(name):
    cfg = preset(name)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    logits, caches, aux = decoder_forward(params, toks, cfg)
    assert logits.shape == (2, 17, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert caches is None
    loss, metrics = decoder_loss(params, toks, cfg)
    assert np.isfinite(float(loss))
    if cfg.is_moe and cfg.router_score == "softmax":
        assert float(aux) > 0       # a sigmoid router balances by its bias


def test_scan_vs_unrolled_equivalence():
    # float32 so fusion-order rounding doesn't mask real mismatches (bf16
    # differs ~1e-2 between fused-scan and eager-unrolled execution).
    cfg = preset("tiny", dtype="float32")
    cfg_unrolled = preset("tiny", scan_layers=False, dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    # Unstack the scanned params into the unrolled layout.
    unrolled_layers = [
        jax.tree.map(lambda a: a[i], params["layers"]) for i in range(cfg.n_layers)
    ]
    params_u = {**params, "layers": unrolled_layers}
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    l1, _, _ = decoder_forward(params, toks, cfg)
    l2, _, _ = decoder_forward(params_u, toks, cfg_unrolled)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_decode_cache_matches_full_forward():
    cfg = preset("tiny")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 9), 0, cfg.vocab_size)
    full, _, _ = decoder_forward(params, toks, cfg)
    shape = (cfg.n_layers, 1, 16, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, cfg.activation_dtype),
             "v": jnp.zeros(shape, cfg.activation_dtype),
             "len": jnp.int32(0)}
    out, cache, _ = decoder_forward(params, toks[:, :6], cfg, kv_caches=cache)
    chunks = [out]
    for i in range(6, 9):
        pos = jnp.full((1, 1), i, jnp.int32)
        lg, cache, _ = decoder_forward(params, toks[:, i:i + 1], cfg,
                                       positions=pos, kv_caches=cache)
        chunks.append(lg)
    inc = jnp.concatenate(chunks, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc), atol=2e-2)
    assert int(cache["len"]) == 9


def test_remat_policies_same_loss():
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256)
    losses = []
    for policy in ["none", "nothing_saveable", "full", "dots_no_batch",
                   "dots_flash"]:
        cfg = preset("tiny", remat_policy=policy)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        loss, _ = jax.jit(lambda p, t: decoder_loss(p, t, cfg))(params, toks)
        losses.append(float(loss))
    assert max(losses) - min(losses) < 1e-5


@pytest.mark.slow  # tier-1 budget: two pallas grad traces A/B'd, ~8s
def test_dots_flash_grads_match_unrematted():
    """The dots_flash policy (saved flash (o,lse) residuals) must not
    change gradients — only what the backward recomputes. Pallas impl so
    the saved names actually appear in the trace."""
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    grads = []
    for policy in ["none", "dots_flash"]:
        cfg = preset("tiny", remat_policy=policy, dtype="float32")
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        g = jax.grad(lambda p: decoder_loss(p, toks, cfg,
                                            attn_impl="pallas")[0])(params)
        grads.append(g)
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_param_count_formula_matches_actual():
    for name in ["tiny", "tiny-gemma", "tiny-moe", "tiny-glm"]:
        cfg = preset(name)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        assert actual == cfg.num_params(), (name, actual, cfg.num_params())


def test_spec_tree_matches_param_tree():
    for name in ["tiny", "tiny-moe", "tiny-glm"]:
        cfg = preset(name)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        specs = decoder_param_specs(cfg)
        from kubeflow_tpu.parallel.sharding import _is_spec_leaf

        pleaves, ptree = jax.tree.flatten(params)
        sleaves, stree = jax.tree.flatten(specs, is_leaf=_is_spec_leaf)
        assert len(pleaves) == len(sleaves)
        for p, s in zip(pleaves, sleaves):
            assert p.ndim == len(s), (p.shape, s)


# -- sharded vs unsharded equivalence (the core SPMD correctness test) --------

@pytest.mark.slow
@pytest.mark.parametrize("axes", [
    {"data": 8}, {"fsdp": 8}, {"fsdp": 4, "model": 2}, {"fsdp": 2, "model": 4},
    {"data": 2, "fsdp": 2, "model": 2},
])
def test_sharded_matches_unsharded(axes):
    cfg = preset("tiny")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab_size)

    ref_loss, _ = jax.jit(lambda p, t: decoder_loss(p, t, cfg))(params, toks)

    mesh = build_mesh(axes)
    specs = decoder_param_specs(cfg)
    shardings = shard_params(params, specs, mesh)
    sharded_params = jax.tree.map(
        lambda a, sh: jax.device_put(a, sh), params,
        shardings)
    batch_sh = jax.NamedSharding(mesh, logical_to_mesh_axes(("batch", None)))
    sharded_toks = jax.device_put(toks, batch_sh)
    loss, _ = jax.jit(
        lambda p, t: decoder_loss(p, t, cfg, mesh=mesh))(sharded_params, sharded_toks)
    np.testing.assert_allclose(float(ref_loss), float(loss), rtol=2e-4)


@pytest.mark.slow
def test_moe_sharded_matches_unsharded_expert_parallel():
    cfg = preset("tiny-moe")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 9), 0, cfg.vocab_size)
    ref_loss, _ = jax.jit(lambda p, t: decoder_loss(p, t, cfg))(params, toks)
    mesh = build_mesh({"fsdp": 2, "expert": 4})
    specs = decoder_param_specs(cfg)
    shardings = shard_params(params, specs, mesh)
    sharded_params = jax.tree.map(lambda a, sh: jax.device_put(a, sh), params, shardings)
    batch_sh = jax.NamedSharding(mesh, logical_to_mesh_axes(("batch", None)))
    loss, _ = jax.jit(lambda p, t: decoder_loss(p, t, cfg, mesh=mesh))(
        sharded_params, jax.device_put(toks, batch_sh))
    # bf16 all-to-all/psum reduction order differs under EP; ~1e-3 abs noise
    np.testing.assert_allclose(float(ref_loss), float(loss), rtol=5e-4)


@pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
def test_chunked_ce_matches_full():
    """loss_chunk_size must be numerics-identical (loss, accuracy, grads) to
    the full-logits path — it's a memory optimization, not an approximation."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import decoder_loss, init_decoder_params

    for name in ("tiny", "tiny-gemma"):       # gemma: softcap + tied head
        cfg = preset(name, dtype="float32")
        chunked = dataclasses.replace(cfg, loss_chunk_size=32)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0,
                                  cfg.vocab_size)
        l0, m0 = decoder_loss(params, toks, cfg)
        l1, m1 = decoder_loss(params, toks, chunked)
        assert abs(float(l0) - float(l1)) < 1e-5
        assert float(m0["accuracy"]) == float(m1["accuracy"])
        g0 = jax.grad(lambda p: decoder_loss(p, toks, cfg)[0])(params)
        g1 = jax.grad(lambda p: decoder_loss(p, toks, chunked)[0])(params)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            assert float(jnp.abs(a - b).max()) < 1e-5


def test_chunked_ce_odd_tail_falls_back():
    import dataclasses

    import jax

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import decoder_loss, init_decoder_params

    cfg = preset("tiny", dtype="float32")
    chunked = dataclasses.replace(cfg, loss_chunk_size=50)  # 128 % 50 != 0
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 256)
    l0, _ = decoder_loss(params, toks, cfg)
    l1, _ = decoder_loss(params, toks, chunked)
    assert abs(float(l0) - float(l1)) < 1e-5
