"""MoE serving paths (VERDICT r3 #3): prefill runs the training dispatch
path (per-request, batch-independent by construction), decode offers a
zero-drop dispatch variant — both pinned token-exact against the dense
oracle in fp32 (bf16 argmax flips one-ulp across formulations)."""

import dataclasses

import jax
import pytest

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def cfg():
    # fp32 for exactness; ample capacity so the per-request prefill
    # dispatch provably matches dense (zero drops possible).
    c = preset("tiny-moe", dtype="float32")
    return dataclasses.replace(c, capacity_factor=float(c.num_experts))


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **knobs):
    return LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=4, max_seq_len=96,
                     page_size=16, chunked_prefill_tokens=32, **knobs),
        params=params)


PROMPTS = [[5, 17, 3, 99, 42], [7] * 20, [9, 8, 7, 6, 5, 4], [30, 31]]


def _generate_all(eng, n_new=10):
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=n_new))
            for p in PROMPTS]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    return [r.output_tokens for r in reqs]


class TestMoEServingImpls:
    def test_default_resolution(self, cfg, params):
        eng = _engine(cfg, params)
        assert eng._cfg_prefill.moe_impl == "dispatch"
        assert eng._cfg_decode.moe_impl == "dense"

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_prefill_dispatch_token_exact_vs_dense(self, cfg, params):
        dense = _engine(cfg, params, moe_prefill_impl="dense")
        disp = _engine(cfg, params, moe_prefill_impl="dispatch")
        assert _generate_all(dense) == _generate_all(disp)

    def test_zero_drop_decode_token_exact_vs_dense(self, cfg, params):
        dense = _engine(cfg, params, moe_decode_impl="dense")
        zd = _engine(cfg, params, moe_decode_impl="zero_drop")
        assert zd._cfg_decode.moe_impl == "dispatch"
        assert _generate_all(dense) == _generate_all(zd)

    @pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
    def test_trained_capacity_prefill_is_batch_independent(self, params):
        """At the TRAINING capacity factor (drops possible within a
        request), co-batched traffic must still not change any request's
        tokens: solo runs == batched runs, request by request."""
        c = preset("tiny-moe", dtype="float32")   # cf = training default
        eng_batched = _engine(c, init_decoder_params(jax.random.PRNGKey(0), c),
                              moe_prefill_impl="dispatch")
        p2 = init_decoder_params(jax.random.PRNGKey(0), c)
        batched = _generate_all(eng_batched)
        for i, prompt in enumerate(PROMPTS):
            solo = _engine(c, p2, moe_prefill_impl="dispatch")
            got = solo.generate(prompt, SamplingParams(max_new_tokens=10))
            assert got == batched[i], f"request {i} perturbed by co-batching"

    def test_prefill_pads_cannot_displace_choices(self):
        """Bucket padding must not claim expert capacity. At the TRAINING
        capacity factor, a short prompt in a 32-wide bucket brings ~27
        identical pad tokens whose first choices would flood one expert's
        buffer ahead of real tokens' second choices (choice-major priority)
        — the valid_len mask removes them, so prompts with <= C/k real
        choices are exactly the dense oracle."""
        c = preset("tiny-moe", dtype="float32")      # cf = training default
        params = init_decoder_params(jax.random.PRNGKey(0), c)
        prompts = [[5, 17, 3], [7] * 8, [9, 8, 7, 6], [30, 31]]
        dense = _engine(c, params, moe_prefill_impl="dense")
        disp = _engine(c, params, moe_prefill_impl="dispatch")
        for p in prompts:
            want = dense.generate(p, SamplingParams(max_new_tokens=8))
            got = disp.generate(p, SamplingParams(max_new_tokens=8))
            assert got == want, f"prompt {p}: pads perturbed routing"

    def test_unknown_impls_rejected(self, cfg, params):
        with pytest.raises(ValueError, match="moe_prefill_impl"):
            _engine(cfg, params, moe_prefill_impl="ragged")
        with pytest.raises(ValueError, match="moe_decode_impl"):
            _engine(cfg, params, moe_decode_impl="dispatch")
