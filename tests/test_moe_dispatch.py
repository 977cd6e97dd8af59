"""Capacity-factor MoE dispatch vs the dense oracle (SURVEY.md §2.6 EP row:
the dispatch path is the default — only selected experts compute — while the
drop-free dense formulation remains the correctness oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "jax.experimental.pallas",
    reason="Pallas unavailable: the MoE dispatch path's kernels need it")
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import (
    decoder_loss, init_decoder_params)


def mk(impl, cf=8.0, **over):
    # capacity_factor=E (here up to 8) => C = k*T: nothing can drop, so
    # dispatch must match dense exactly (up to fp reduction order).
    return preset("tiny-moe", dtype="float32", moe_impl=impl,
                  capacity_factor=cf, **over)


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64), jnp.float32)


@pytest.fixture(scope="module")
def moe_params(x):
    cfg = mk("dense")
    p, _ = L.init_moe(jax.random.PRNGKey(0), cfg)
    return p


def test_dispatch_matches_dense_with_ample_capacity(x, moe_params):
    out_d, aux_d = L.moe_block(moe_params, x, mk("dense"))
    out_s, aux_s = L.moe_block(moe_params, x, mk("dispatch"))
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_d), rtol=1e-6)


def test_dispatch_gradients_match_dense(x, moe_params):
    def loss(p, cfg):
        out, aux = L.moe_block(p, x, cfg)
        return jnp.sum(out ** 2) + 0.01 * aux

    g_d = jax.grad(loss)(moe_params, mk("dense"))
    g_s = jax.grad(loss)(moe_params, mk("dispatch"))
    for path in ("router", "gate", "up", "down"):
        np.testing.assert_allclose(np.asarray(g_s[path]),
                                   np.asarray(g_d[path]),
                                   rtol=5e-5, atol=1e-5, err_msg=path)


def test_dispatch_flop_shape_is_k_over_e():
    """The whole point: per-expert buffers total ~cf*k*T rows, NOT E*T."""
    cfg = mk("dispatch", cf=1.25)
    t = 2 * 16
    c = L.moe_capacity(cfg, t)
    assert c < t  # dense would be C == T per expert
    assert c >= cfg.experts_per_token * t // cfg.num_experts


def test_drop_policy_over_capacity():
    """All tokens routed to ONE expert with capacity_factor=1: only the
    first C (choice-major priority) survive; dropped (token, choice) pairs
    contribute nothing (no renormalization)."""
    cfg = mk("dispatch", cf=1.0)
    p, _ = L.init_moe(jax.random.PRNGKey(0), cfg)
    # Force the router: huge weight toward expert 0 for every token.
    p = dict(p)
    router = np.zeros((64, cfg.num_experts), np.float32)
    router[:, 0] = 100.0
    router[:, 1] = 50.0
    p["router"] = jnp.asarray(router)
    x = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(3), (1, 1, 64)),
        (1, 32, 64)).astype(jnp.float32)  # identical tokens
    out, _ = L.moe_block(p, x, cfg)
    t = 32
    c = L.moe_capacity(cfg, t)  # cf=1: C = k*T/E rounded to 8s
    assert c < t, "test needs real drops"
    out = np.asarray(out)[0]
    # Identical tokens, so surviving rows (both choices kept) share one
    # value; tokens with dropped choices differ. First tokens keep their
    # first choice (choice-major priority): their outputs must be non-zero.
    assert np.abs(out[0]).sum() > 0
    # A fully-dropped token's MoE output is exactly zero.
    full = np.abs(out).sum(-1)
    assert (full[:c] > 0).all()          # first C kept their primary choice
    assert full[-1] == 0                 # tail token fully dropped


def test_decoder_loss_trains_with_dispatch():
    cfg = mk("dispatch", cf=1.25)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                              cfg.vocab_size)
    loss, _ = decoder_loss(params, toks, cfg)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: decoder_loss(p, toks, cfg)[0])(params)
    gn = jax.tree.reduce(
        lambda a, b: a + float(jnp.sum(jnp.abs(b))), grads, 0.0)
    assert np.isfinite(gn) and gn > 0


def test_dispatch_sharded_matches_unsharded():
    """dp×ep mesh: the expert dim of the dispatch buffers shards over the
    expert axis; sharded == unsharded."""
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.train.optim import OptimizerConfig
    from kubeflow_tpu.train.step import setup_train

    cfg = mk("dispatch", cf=8.0, n_layers=2)
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, cfg.max_seq_len + 1)).astype(np.int32)

    losses = {}
    for axes in ({"data": 1}, {"data": 2, "expert": 4}):
        mesh = build_mesh(axes, jax.devices()[:int(np.prod(
            list(axes.values())))])
        task = setup_train(cfg, OptimizerConfig(total_steps=2), mesh)
        batch = jax.device_put(toks, task.batch_sharding)
        _, metrics = task.step_fn(task.state, batch)
        losses[tuple(axes)] = float(metrics["loss"])
    vals = list(losses.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=2e-5)


def test_serving_engine_moe_phase_resolution():
    """A request's tokens must not depend on co-batched traffic. Decode
    co-batches slots, so it resolves to the drop-free dense formulation;
    prefill runs per-request, so the dispatch path is batch-independent by
    construction and stays (the measured winner — tests/test_serve_moe.py
    pins both paths token-exact against dense)."""
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = preset("tiny-moe", moe_impl="dispatch")
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=32,
                                      page_size=16, chunked_prefill_tokens=16))
    assert eng._cfg_decode.moe_impl == "dense"
    assert eng._cfg_prefill.moe_impl == "dispatch"
    assert eng.cfg.moe_impl == "dispatch"    # model cfg left untouched
