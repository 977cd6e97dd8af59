"""int8 weight-only serving quantization + int8 paged KV (VERDICT round-4
next #3; SURVEY.md §2.3#27 — (U) kserve huggingfaceserver/vLLM ships weight
quantization as a first-class serving capability).

Covers: the per-channel scheme's error bound, which decoder weights
quantize (and which must not), the engine knob end-to-end (greedy quality
gate vs the bf16 engine), the int8 paged pool, and the TP-sharded
quantized engine (per-field shardings from the weight's own logical spec).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.ops.quantization import (
    QuantizedTensor, dequantize_kv, packed_param_bytes, quantization_quality,
    quantize_kv, quantize_params_int8, quantize_weight,
)


def test_quantize_weight_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.1
    qt = quantize_weight(w, (0,))
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w.shape
    assert qt.scale.shape == (1, 32)
    deq = qt.astype(jnp.float32)
    # Round-to-nearest: |error| <= scale/2 per element, scale = amax/127.
    bound = np.asarray(qt.scale)[0] / 2 + 1e-9
    assert np.all(np.abs(np.asarray(deq - w)) <= bound[None, :])


def test_quantize_weight_per_channel_independence():
    # One huge-magnitude channel must not destroy the others' resolution
    # (the whole point of per-channel over per-tensor).
    w = np.ones((16, 4), np.float32) * 0.01
    w[:, 0] = 100.0
    qt = quantize_weight(jnp.asarray(w), (0,))
    deq = np.asarray(qt.astype(jnp.float32))
    assert np.allclose(deq[:, 1:], 0.01, rtol=0.01)


def test_quantize_params_layout():
    cfg = preset("tiny-moe", param_dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params_int8(params, cfg)
    lay = qp["layers"]
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(lay["attn"][name], QuantizedTensor), name
    for name in ("gate", "up", "down"):
        assert isinstance(lay["mlp"][name], QuantizedTensor), name
    # Accuracy-critical / non-matmul leaves stay full precision.
    assert not isinstance(lay["mlp"]["router"], QuantizedTensor)
    assert not isinstance(qp["embed"], QuantizedTensor)
    assert not isinstance(lay["ln1"], QuantizedTensor)
    assert isinstance(qp["lm_head"], QuantizedTensor)
    # Stacked scan layout: scale keeps the layer dim, collapses contraction.
    wq = lay["attn"]["wq"]
    assert wq.scale.shape == (cfg.n_layers, 1, cfg.n_heads, cfg.head_dim)
    # MoE experts quantize per-expert-per-channel.
    assert lay["mlp"]["gate"].scale.shape == (
        cfg.n_layers, cfg.num_experts, 1, cfg.mlp_dim)
    # Density: packed bytes land near 1 byte/param for the quantized leaves.
    assert packed_param_bytes(qp) < packed_param_bytes(params) * 0.55


@pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
def test_forward_parity_tiny():
    """Dequant-in-matmul forward stays close to the fp32 forward, and the
    quality gate reports a high greedy match on a fixed prompt set."""
    cfg = preset("tiny", param_dtype="float32", dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(1), cfg)
    qp = quantize_params_int8(params, cfg)
    prompts = [[1, 5, 9, 2], [3, 3, 7]]
    q = quantization_quality(cfg, params, qp, prompts, max_new=8)
    assert q["tokens_compared"] == 16
    assert q["greedy_match_rate"] >= 0.8, q
    assert q["mean_abs_logprob_delta"] < 0.15, q


def test_engine_int8_generates():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams

    cfg = preset("tiny", param_dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(2), cfg)
    b = BatchingSpec(max_batch_size=2, max_seq_len=128,
                     weights_dtype="bfloat16", quantize="int8",
                     decode_steps=4, page_size=16, chunked_prefill_tokens=16)
    eng = LLMEngine(cfg, b, params=params)
    ref = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=128,
                                      weights_dtype="bfloat16",
                                      decode_steps=4, page_size=16,
                                      chunked_prefill_tokens=16),
                    params=params)
    sp = SamplingParams(max_new_tokens=12, temperature=0.0)
    out_q = eng.generate([4, 8, 15, 16], sp)
    out_ref = ref.generate([4, 8, 15, 16], sp)
    assert len(out_q) == 12
    # Greedy int8 tracks bf16 closely on the same weights (identical is not
    # guaranteed — near-ties can flip — but wholesale divergence means the
    # dequant is wrong).
    agree = sum(a == b_ for a, b_ in zip(out_q, out_ref)) / len(out_ref)
    assert agree >= 0.5, (out_q, out_ref)


def test_engine_rejects_bad_knobs():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = preset("tiny")
    with pytest.raises(ValueError, match="quantize"):
        LLMEngine(cfg, BatchingSpec(quantize="fp4", max_seq_len=128))
    # pallas + int8 is a SUPPORTED pair now (in-kernel dequant): the old
    # "requires paged_attn_impl=gather" ban is gone.
    eng = LLMEngine(cfg, BatchingSpec(kv_cache_dtype="int8", paged=True,
                                      page_size=16, max_seq_len=128,
                                      paged_attn_impl="pallas"))
    assert eng.kv_quant and eng.paged_attn_impl == "pallas"


def test_spec_allows_int8_kv_through_fabric():
    """The two validator bans this feature removed, pinned OPEN: int8 KV
    composes with disaggregated roles (the wire carries scale blobs) and
    with the host tier (demote/promote batches carry them too)."""
    from kubeflow_tpu.core.serving import BatchingSpec

    # pydantic model_validator: construction IS validation.
    BatchingSpec(kv_cache_dtype="int8", paged=True, page_size=16,
                 max_seq_len=128, role="prefill")
    BatchingSpec(kv_cache_dtype="int8", paged=True, page_size=16,
                 max_seq_len=128, role="decode")
    BatchingSpec(kv_cache_dtype="int8", paged=True, page_size=16,
                 max_seq_len=128, host_kv_pages=32, prefix_index="radix")


def test_kv_quantize_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 16)) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (4, 2)
    deq = dequantize_kv(q, s, jnp.float32)
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(s)[..., None] / 2 + 1e-9
    assert np.all(err <= bound)


def test_kv_quantize_extreme_magnitudes():
    """Per-token-per-head scales keep relative error bounded across 12
    orders of magnitude in the same batch — a per-tensor scale would
    flush the small rows to zero."""
    mags = np.asarray([1e-6, 1e-3, 1.0, 1e3, 1e6], np.float32)
    x = (jax.random.normal(jax.random.PRNGKey(7), (5, 3, 8))
         * mags[:, None, None])
    q, s = quantize_kv(x)
    deq = np.asarray(dequantize_kv(q, s, jnp.float32))
    xn = np.asarray(x)
    for i in range(5):
        amax = np.abs(xn[i]).max()
        # Round-to-nearest on a 127-step grid: error <= amax/254 per row.
        assert np.abs(deq[i] - xn[i]).max() <= amax / 127, mags[i]


def test_kv_quantize_zero_rows():
    """All-zero K/V rows (padding, unwritten page tails) must survive
    exactly — the 1e-8 scale floor guards the 0/0, and dequant returns
    exact zeros, not NaN."""
    x = jnp.zeros((3, 2, 16))
    q, s = quantize_kv(x)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s) > 0)          # floored, not 0 (no 0/0)
    deq = np.asarray(dequantize_kv(q, s, jnp.float32))
    assert np.all(deq == 0.0) and not np.any(np.isnan(deq))
    # Mixed: one zero row among live rows stays exact.
    x = x.at[1, 1, :].set(jnp.arange(16, dtype=jnp.float32))
    q, s = quantize_kv(x)
    deq = np.asarray(dequantize_kv(q, s, jnp.float32))
    assert np.all(deq[0] == 0.0)
    assert np.abs(deq[1, 1] - np.arange(16)).max() <= 15.0 / 254 + 1e-6


@pytest.mark.parametrize("dh", [1, 3, 7, 17])
def test_kv_quantize_odd_head_dims(dh):
    """The scheme is shape-agnostic over head_dim (no lane-alignment
    assumption leaks into the math)."""
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 2, dh)) * 2.5
    q, s = quantize_kv(x)
    assert q.shape == x.shape and s.shape == x.shape[:-1]
    deq = np.asarray(dequantize_kv(q, s, jnp.float32))
    bound = np.asarray(s)[..., None] / 2 + 1e-9
    assert np.all(np.abs(deq - np.asarray(x)) <= bound)


def test_packed_param_bytes_estimate_exact():
    """The config-only estimate prices EXACTLY what quantize_params_int8
    packs (the repository books placement off the estimate before any
    params exist — drift here mis-sizes the LRU budget)."""
    from kubeflow_tpu.ops.quantization import packed_param_bytes_estimate

    for name in ("tiny", "tiny-moe"):
        cfg = preset(name, param_dtype="float32")
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        real = packed_param_bytes(quantize_params_int8(params, cfg))
        assert packed_param_bytes_estimate(cfg) == real, name


@pytest.mark.slow  # tier-1 budget: ~8s; quant_smoke gates the int8 paged
# e2e path (band + fabric identity) on every smoke run
def test_paged_int8_kv_engine_e2e():
    """int8 paged pool serves greedy decode; outputs track the bf16 paged
    engine; pool bytes halve (+scale overhead)."""
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams

    cfg = preset("tiny", param_dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(4), cfg)

    def make(kv_dtype):
        return LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, paged=True, page_size=16,
            chunked_prefill_tokens=16, decode_steps=4,
            weights_dtype="bfloat16", kv_cache_dtype=kv_dtype,
            paged_attn_impl="gather"), params=params)

    eng8 = make("int8")
    eng16 = make(None)
    assert eng8.cache["k"].dtype == jnp.int8
    assert "ks" in eng8.cache and eng8.cache["ks"].dtype == jnp.float32
    kv8 = eng8.cache["k"].nbytes + eng8.cache["ks"].nbytes
    kv16 = eng16.cache["k"].nbytes
    # int8 + 4/Dh scale overhead vs bf16: 0.625 at tiny's Dh=16; 0.52 at a
    # real model's Dh=128.
    assert kv8 < kv16 * 0.66
    sp = SamplingParams(max_new_tokens=10, temperature=0.0)
    prompt = [2, 7, 1, 8, 2, 8]
    out8 = eng8.generate(prompt, sp)
    out16 = eng16.generate(prompt, sp)
    assert len(out8) == 10
    agree = sum(a == b for a, b in zip(out8, out16)) / len(out16)
    assert agree >= 0.5, (out8, out16)
    # Multi-request continuity: a second request re-reads quantized pages.
    out8b = eng8.generate(prompt, sp)
    assert len(out8b) == 10 and out8b == out8


@pytest.mark.slow
def test_tp_sharded_quantized_engine():
    """Quantized weights shard per-field (q by the weight's logical spec,
    scale with collapsed dims replicated as needed) and the TP engine
    serves greedy tokens matching the single-device quantized engine."""
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams

    cfg = preset("tiny", param_dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(5), cfg)
    mesh = build_mesh({"model": 2}, jax.devices()[:2])
    b = BatchingSpec(max_batch_size=2, max_seq_len=64,
                     weights_dtype="bfloat16", quantize="int8",
                     decode_steps=4, page_size=16, chunked_prefill_tokens=16)
    eng_tp = LLMEngine(cfg, b, params=params, mesh=mesh)
    eng_1 = LLMEngine(cfg, b, params=params)
    # Per-field shardings really applied: wq's int8 payload is sharded on
    # the head dim, its scale exists with the collapsed contraction dim.
    wq = eng_tp.params["layers"]["attn"]["wq"]
    assert isinstance(wq, QuantizedTensor)
    assert wq.q.dtype == jnp.int8
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    out_tp = eng_tp.generate([3, 1, 4, 1, 5], sp)
    out_1 = eng_1.generate([3, 1, 4, 1, 5], sp)
    assert out_tp == out_1
