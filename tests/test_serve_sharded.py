"""Tensor-parallel (mesh-mode) serving: a TP-sharded engine must reproduce
the single-device engine EXACTLY — same tokens, same continuous-batching
behavior — with weights and KV cache actually distributed over the mesh.

The TPU-native analog of vLLM's ``tensor_parallel_size`` serving path ((U)
kserve python/huggingfaceserver; SURVEY.md §2.3#27): GSPMD partitions the
same jitted dispatches; no separate "distributed engine" codebase exists to
drift from the single-chip one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "jax.experimental.pallas",
    reason="Pallas unavailable: the sharded prefill path's kernels need it")
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.runtime.mesh import build_mesh
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def cfg():
    # 4 q heads, 2 kv heads: tp=2 divides both. fp32 activations for the
    # token-exact tests: sharding changes GSPMD's collective decomposition,
    # which legitimately shifts bf16 rounding by one ulp (measured ~0.016 at
    # tp=4) — enough to flip argmax on a random-init 256-vocab model. In
    # fp32 the reduction-order noise is ~1e-6 against ~0.2 logit gaps.
    return preset("tiny", dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def mk_engine(cfg, params, *, tp=1, **kw):
    batching = BatchingSpec(**{**dict(
        max_batch_size=4, max_seq_len=96, page_size=16,
        chunked_prefill_tokens=64), **kw})
    mesh = None
    if tp > 1:
        mesh = build_mesh({"model": tp}, jax.devices()[:tp])
    return LLMEngine(cfg, batching, params=params, seed=0, mesh=mesh)


PROMPTS = [[5, 17, 3, 99, 42], [7] * 20, [9, 8, 7, 6, 5, 4], [30, 31]]


def run_all(engine, sampling=None):
    sampling = sampling or SamplingParams(max_new_tokens=10)
    reqs = [engine.submit(p, sampling) for p in PROMPTS]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    return [r.output_tokens for r in reqs]


@pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
def test_tp2_matches_single_device_greedy(cfg, params):
    want = run_all(mk_engine(cfg, params))
    got = run_all(mk_engine(cfg, params, tp=2))
    assert got == want


@pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
def test_tp4_matches_single_device_greedy(cfg, params):
    want = run_all(mk_engine(cfg, params))
    got = run_all(mk_engine(cfg, params, tp=4))
    assert got == want


def test_tp2_weights_and_cache_are_distributed(cfg, params):
    eng = mk_engine(cfg, params, tp=2)
    # A TP-split weight (wq: [L, D, H, Dh] sharded on heads) must place half
    # the array on each device — the whole point is escaping one chip's HBM.
    wq = eng.params["layers"]["attn"]["wq"]
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    assert shard_shapes == {wq.shape[:2] + (wq.shape[2] // 2, wq.shape[3])}
    ck = eng.cache["k"]
    assert {s.data.shape[3] for s in ck.addressable_shards} == \
        {ck.shape[3] // 2}
    # And serving still works end to end.
    out = eng.generate(PROMPTS[0], SamplingParams(max_new_tokens=6))
    assert len(out) == 6


@pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
def test_tp2_sampled_matches_single_device(cfg, params):
    """Same PRNG seed => identical sampled streams: sharding must not change
    sampling semantics (threefry values are placement-invariant)."""
    sp = SamplingParams(max_new_tokens=8, temperature=0.8, top_k=20,
                        top_p=0.9)
    want = run_all(mk_engine(cfg, params), sp)
    got = run_all(mk_engine(cfg, params, tp=2), sp)
    assert got == want


@pytest.mark.slow  # tier-1 budget: tp=2 paged engine compile, ~9s;
# tp2_chunked_prefill keeps the sharded-identity lane in tier-1
def test_tp2_paged_matches_single_device(cfg, params):
    dense = mk_engine(cfg, params, paged=True, page_size=16,
                      chunked_prefill_tokens=16)
    sharded = mk_engine(cfg, params, tp=2, paged=True, page_size=16,
                        chunked_prefill_tokens=16)
    want = run_all(dense)
    got = run_all(sharded)
    assert got == want
    for eng in (dense, sharded):
        assert eng.kv_pages_in_use() == 0
        eng._allocator.assert_quiescent()


def test_tp2_chunked_prefill_matches(cfg, params):
    """Long prompt through the chunked-prefill path, sharded vs not."""
    sp = SamplingParams(max_new_tokens=6)
    prompt = list(np.arange(70) % cfg.vocab_size)
    want = mk_engine(cfg, params,
                     chunked_prefill_tokens=32).generate(prompt, sp)
    got = mk_engine(cfg, params, tp=2,
                    chunked_prefill_tokens=32).generate(prompt, sp)
    assert got == want


def test_tp2_bf16_serves(params):
    """The production dtype (bf16 activations) through the sharded path —
    smoke only: one-ulp rounding differs by collective decomposition, so
    token-exactness is pinned in fp32 above."""
    cfgb = preset("tiny")
    pb = init_decoder_params(jax.random.PRNGKey(0), cfgb)
    out = mk_engine(cfgb, pb, tp=2).generate(
        PROMPTS[0], SamplingParams(max_new_tokens=6))
    assert len(out) == 6


def test_gqa_nondivisible_kv_replicates(params):
    """1 kv head under tp=2: the cache replicates (heads still split) and
    generation still matches the unsharded engine."""
    cfg1 = preset("tiny-gemma", dtype="float32")     # n_kv_heads=1
    p1 = init_decoder_params(jax.random.PRNGKey(1), cfg1)
    want = mk_engine(cfg1, p1).generate(PROMPTS[0],
                                        SamplingParams(max_new_tokens=6))
    eng = mk_engine(cfg1, p1, tp=2)
    assert eng._cache_sh.spec == jax.sharding.PartitionSpec()
    got = eng.generate(PROMPTS[0], SamplingParams(max_new_tokens=6))
    assert got == want
