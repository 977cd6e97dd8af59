"""Explainer hop (VERDICT r3 #5 — the kserve predictor/transformer/
explainer triad's third leg): attribution math sanity (finite differences),
the :explain route, and the ISVC spec wiring."""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.serve.explain import grad_x_input, leave_one_out


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


TOKENS = [5, 17, 3, 99, 42, 7]


class TestAttributionMath:
    def test_grad_x_input_matches_finite_difference(self, cfg, params):
        """score_i is the exact directional derivative of the target
        log-prob along e_i: shrinking token i's embedding by epsilon must
        change the log-prob by ~ -epsilon * score_i."""
        out = grad_x_input(TOKENS, params=params, cfg=cfg)
        target = out["target_token"]
        toks = jnp.asarray([TOKENS], jnp.int32)
        embeds = params["embed"].astype(jnp.float32)[toks]

        def lp_of(e):
            logits, _, _ = decoder_forward(params, toks, cfg, inputs_embeds=e)
            return float(jax.nn.log_softmax(logits[0, -1])[target])

        eps = 1e-3
        for i in (0, 3, len(TOKENS) - 1):
            perturbed = embeds.at[0, i].multiply(1.0 - eps)
            fd = (lp_of(embeds) - lp_of(perturbed)) / eps
            assert fd == pytest.approx(out["scores"][i], rel=0.05, abs=1e-3)

    def test_leave_one_out_scores(self, cfg, params):
        """Occlusion scores must equal per-ablation full forwards, and the
        batched [S,S] formulation must agree with doing them one by one."""
        out = leave_one_out(TOKENS, params=params, cfg=cfg)
        assert len(out["scores"]) == len(TOKENS)
        target = out["target_token"]
        for i in (1, 4):
            ablated = list(TOKENS)
            ablated[i] = 0
            logits, _, _ = decoder_forward(
                params, jnp.asarray([ablated], jnp.int32), cfg)
            lp = float(jax.nn.log_softmax(logits[0, -1])[target])
            assert out["scores"][i] == pytest.approx(
                out["target_logprob"] - lp, abs=1e-4)

    def test_handlers_resolve(self):
        from kubeflow_tpu.serve.explain import build_explainer

        assert build_explainer(None) is None
        assert build_explainer({"handler": "grad_x_input"}) is grad_x_input
        with pytest.raises(KeyError, match="not registered"):
            build_explainer({"handler": "nope"})


def _post(url, body, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class TestExplainRoute:
    def test_explain_route_serves_scores(self, cfg, params):
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.serve.engine import LLMEngine
        from kubeflow_tpu.serve.explain import build_explainer
        from kubeflow_tpu.serve.server import ModelServer

        engine = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                                             page_size=16,
                                             chunked_prefill_tokens=16),
                           params=params)
        server = ModelServer(
            "exp", engine,
            explainer=build_explainer({"handler": "grad_x_input"}))
        server.start()
        try:
            out = _post(server.url + "/v1/models/exp:explain",
                        {"instances": ["hi"]})
            (exp,) = out["explanations"]
            assert exp["method"] == "grad_x_input"
            # byte tokenizer may add BOS: lengths agree, >= the 2 chars
            assert len(exp["scores"]) == len(exp["tokens"]) >= 2
            assert all(np.isfinite(s) for s in exp["scores"])
            assert isinstance(exp["predicted_text"], str)
        finally:
            server.stop()

    def test_overlong_explain_prompt_is_400(self, cfg, params):
        """Attribution is O(S) forwards; an uncapped prompt would OOM the
        live serving chip — reject past the engine's max_seq_len."""
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.serve.engine import LLMEngine
        from kubeflow_tpu.serve.explain import build_explainer
        from kubeflow_tpu.serve.server import ModelServer

        engine = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=32,
                                             page_size=16,
                                             chunked_prefill_tokens=16),
                           params=params)
        server = ModelServer(
            "exp", engine,
            explainer=build_explainer({"handler": "leave_one_out"}))
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(server.url + "/v1/models/exp:explain",
                      {"instances": ["x" * 200]})
            assert ei.value.code == 400
            assert "limit" in json.loads(ei.value.read())["error"]
        finally:
            server.stop()

    def test_explain_without_explainer_is_400(self, cfg, params):
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.serve.engine import LLMEngine
        from kubeflow_tpu.serve.server import ModelServer

        engine = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                                             page_size=16,
                                             chunked_prefill_tokens=16),
                           params=params)
        server = ModelServer("exp", engine)
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(server.url + "/v1/models/exp:explain",
                      {"instances": ["hi"]})
            assert ei.value.code == 400
        finally:
            server.stop()


@pytest.mark.slow
def test_isvc_explainer_e2e(tmp_path):
    """ExplainerSpec wired like the transformer hop: an InferenceService
    with an explainer serves :explain through the routed URL."""
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.serving import (
        BatchingSpec, ExplainerSpec, InferenceService, InferenceServiceSpec,
        ModelSpec, PredictorSpec,
    )
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology

    plane = ControlPlane(ControlPlaneConfig(
        base_dir=str(tmp_path),
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="cpu",
                                              dims=(2, 2))]),
        platform="cpu"))
    plane.start()
    try:
        isvc = plane.submit(InferenceService(
            metadata=ObjectMeta(name="exp"),
            spec=InferenceServiceSpec(
                predictor=PredictorSpec(
                    model=ModelSpec(model_name="exp",
                                    config={"preset": "tiny",
                                            "overrides": {"vocab_size": 512}}),
                    batching=BatchingSpec(max_batch_size=2, max_seq_len=64,
                                          page_size=16,
                                          chunked_prefill_tokens=32)),
                explainer=ExplainerSpec(handler="leave_one_out"))))
        ready = plane.wait_for(isvc, "Ready", timeout=240)
        out = _post(ready.status.url + "/v1/models/exp:explain",
                    {"instances": ["hey"]}, timeout=180)
        (exp,) = out["explanations"]
        assert exp["method"] == "leave_one_out"
        assert len(exp["scores"]) == len(exp["tokens"]) >= 3
    finally:
        plane.stop()


class TestShardedExplain:
    """VERDICT r4 next #7: the triad's third leg on the engine's REAL
    configurations — TP-sharded params, MoE models, quantized weights.
    The handlers jit with the engine mesh so GSPMD partitions attribution
    exactly like serving dispatches."""

    def _explain_via_server(self, cfg, params, mesh=None, handler=None):
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.serve.engine import LLMEngine
        from kubeflow_tpu.serve.explain import build_explainer
        from kubeflow_tpu.serve.server import ModelServer

        engine = LLMEngine(cfg, BatchingSpec(max_batch_size=2,
                                             max_seq_len=64,
                                             page_size=16,
                                             chunked_prefill_tokens=16),
                           params=params, mesh=mesh)
        server = ModelServer(
            "exp", engine,
            explainer=build_explainer(
                {"handler": handler or "grad_x_input"}))
        server.start()
        try:
            out = _post(server.url + "/v1/models/exp:explain",
                        {"instances": ["hello"]})
            return out["explanations"][0]
        finally:
            server.stop()

    def test_tp2_scores_match_single_device(self, cfg, params):
        from kubeflow_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"model": 2}, jax.devices()[:2])
        exp_tp = self._explain_via_server(cfg, params, mesh=mesh)
        exp_1 = self._explain_via_server(cfg, params, mesh=None)
        assert exp_tp["target_token"] == exp_1["target_token"]
        # TP partial-sum rounding differs from the single-device order:
        # scores agree to bf16-accumulation tolerance, not bitwise.
        np.testing.assert_allclose(exp_tp["scores"], exp_1["scores"],
                                   rtol=0.05, atol=1e-3)

    def test_tp2_leave_one_out(self, cfg, params):
        from kubeflow_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"model": 2}, jax.devices()[:2])
        exp_tp = self._explain_via_server(cfg, params, mesh=mesh,
                                          handler="leave_one_out")
        exp_1 = self._explain_via_server(cfg, params, mesh=None,
                                         handler="leave_one_out")
        assert exp_tp["target_token"] == exp_1["target_token"]
        np.testing.assert_allclose(exp_tp["scores"], exp_1["scores"],
                                   rtol=0.05, atol=1e-3)

    def test_moe_sharded_explain_finite(self):
        """MoE model served TP-sharded: explain resolves dense routing
        (batch-independent) and returns finite scores."""
        from kubeflow_tpu.runtime.mesh import build_mesh

        moe_cfg = preset("tiny-moe", dtype="float32")
        moe_params = init_decoder_params(jax.random.PRNGKey(1), moe_cfg)
        mesh = build_mesh({"model": 2}, jax.devices()[:2])
        exp = self._explain_via_server(moe_cfg, moe_params, mesh=mesh)
        assert all(np.isfinite(s) for s in exp["scores"])
        exp_loo = self._explain_via_server(moe_cfg, moe_params, mesh=mesh,
                                           handler="leave_one_out")
        assert all(np.isfinite(s) for s in exp_loo["scores"])

    def test_quantized_engine_explain(self, cfg, params):
        """int8 weights: grads flow through the dequant to the embeddings;
        scores stay close to the full-precision engine's."""
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.serve.engine import LLMEngine
        from kubeflow_tpu.serve.explain import build_explainer
        from kubeflow_tpu.serve.server import ModelServer

        engine = LLMEngine(
            cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                              page_size=16, chunked_prefill_tokens=16,
                              quantize="int8"),
            params=params)
        server = ModelServer(
            "exp", engine,
            explainer=build_explainer({"handler": "grad_x_input"}))
        server.start()
        try:
            out = _post(server.url + "/v1/models/exp:explain",
                        {"instances": ["hello"]})
            exp = out["explanations"][0]
            assert all(np.isfinite(s) for s in exp["scores"])
        finally:
            server.stop()
