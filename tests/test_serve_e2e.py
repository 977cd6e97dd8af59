"""Serving e2e with a real model-server process: InferenceService submitted
to the live control plane → predictor worker spawns → readiness → requests
through the routed URL → crash recovery (SURVEY.md §3.2 end to end)."""

import json
import signal
import time
import urllib.request

import pytest

from kubeflow_tpu.core.jobs import Worker
from kubeflow_tpu.core.object import ObjectMeta
from kubeflow_tpu.core.serving import (
    BatchingSpec, InferenceService, InferenceServiceSpec, ModelSpec,
    PredictorSpec,
)
from kubeflow_tpu.operator.control_plane import ControlPlane, ControlPlaneConfig
from kubeflow_tpu.runtime.topology import Cluster, SliceTopology


@pytest.fixture()
def cp(tmp_path):
    plane = ControlPlane(ControlPlaneConfig(
        base_dir=str(tmp_path),
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="cpu",
                                              dims=(2, 2))]),
        platform="cpu"))
    plane.start()
    yield plane
    plane.stop()


def _post(url: str, body: dict, timeout=120) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_isvc_serves_through_router_and_recovers(cp):
    isvc = cp.submit(InferenceService(
        metadata=ObjectMeta(name="llm"),
        spec=InferenceServiceSpec(predictor=PredictorSpec(
            model=ModelSpec(model_name="llm",
                            config={"preset": "tiny",
                                    "overrides": {"vocab_size": 512}}),
            batching=BatchingSpec(max_batch_size=2, max_seq_len=64,
                                  page_size=16, chunked_prefill_tokens=32)))))
    ready = cp.wait_for(isvc, "Ready", timeout=180)
    url = ready.status.url

    out = _post(url + "/v1/completions", {"prompt": "hi", "max_tokens": 4})
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] >= 1

    out = _post(url + "/v1/models/llm:predict",
                {"instances": ["a"], "max_tokens": 2})
    assert len(out["predictions"]) == 1

    # Crash the replica; the controller must replace it and go Ready again.
    worker = cp.store.list(
        Worker, label_selector={"serving.tpu.kubeflow.dev/service": "llm"})[0]
    cp.runtime.procman.signal(
        f"default.{worker.metadata.name}", signal.SIGKILL)
    deadline = time.monotonic() + 180
    recovered = False
    while time.monotonic() < deadline:
        cur = cp.store.get(InferenceService, "llm")
        ws = cp.store.list(
            Worker, label_selector={"serving.tpu.kubeflow.dev/service": "llm"})
        if (cur.status.ready_replicas >= 1 and ws
                and ws[0].metadata.uid != worker.metadata.uid):
            recovered = True
            break
        time.sleep(0.5)
    assert recovered, "replica was not replaced after crash"
    out = _post(url + "/v1/completions", {"prompt": "yo", "max_tokens": 2})
    assert out["choices"][0]["finish_reason"] in ("length", "stop")


@pytest.mark.slow
def test_scale_to_zero_cold_start_e2e(cp):
    """The serverless path end to end ((U) kserve Knative mode): a
    min_replicas=0 service serves, idles to zero, then a request parks at
    the router, the controller cold-starts a replica, and the request is
    answered — no 503 anywhere."""
    isvc = cp.submit(InferenceService(
        metadata=ObjectMeta(name="szero"),
        spec=InferenceServiceSpec(predictor=PredictorSpec(
            model=ModelSpec(model_name="szero",
                            config={"preset": "tiny",
                                    "overrides": {"vocab_size": 512}}),
            min_replicas=0, max_replicas=1,
            batching=BatchingSpec(max_batch_size=2, max_seq_len=64,
                                  page_size=16, chunked_prefill_tokens=32)))))
    ready = cp.wait_for(isvc, "Ready", timeout=180)
    url = ready.status.url
    out = _post(url + "/v1/completions", {"prompt": "hi", "max_tokens": 2})
    assert out["usage"]["completion_tokens"] >= 1

    # Idle past the cooldown → the controller drops the last replica.
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        cur = cp.store.get(InferenceService, "szero")
        ws = cp.store.list(Worker, label_selector={
            "serving.tpu.kubeflow.dev/service": "szero"})
        if cur.status.desired_replicas == 0 and not ws:
            break
        time.sleep(1.0)
    else:
        raise AssertionError("service never scaled to zero while idle")

    # A request against the zero-scaled URL: parks at the router, replica
    # cold-starts (spawn + model init + compile), request answers.
    out = _post(url + "/v1/completions", {"prompt": "again", "max_tokens": 2},
                timeout=240)
    assert out["usage"]["completion_tokens"] >= 1
    cur = cp.store.get(InferenceService, "szero")
    assert cur.status.ready_replicas >= 1


@pytest.mark.slow
def test_tensor_parallel_predictor_e2e(cp):
    """A tensor-parallel InferenceService: ONE replica process spanning 2
    (virtual) chips, engine GSPMD-sharded over the mesh ((U) kserve
    huggingfaceserver vLLM tensor_parallel_size; SURVEY.md §2.3#27)."""
    from kubeflow_tpu.core.jobs import ParallelismSpec

    isvc = cp.submit(InferenceService(
        metadata=ObjectMeta(name="tp"),
        spec=InferenceServiceSpec(predictor=PredictorSpec(
            model=ModelSpec(model_name="tp",
                            config={"preset": "tiny",
                                    "overrides": {"vocab_size": 512}}),
            parallelism=ParallelismSpec(model=2),
            batching=BatchingSpec(max_batch_size=2, max_seq_len=64,
                                  page_size=16, chunked_prefill_tokens=32)))))
    ready = cp.wait_for(isvc, "Ready", timeout=240)
    # The replica worker is a 2-chip gang member, not two replicas.
    ws = cp.store.list(Worker, label_selector={
        "serving.tpu.kubeflow.dev/service": "tp"})
    assert len(ws) == 1
    assert ws[0].spec.resources.tpu_chips == 2
    assert ws[0].spec.parallelism.get("model") == 2
    out = _post(ready.status.url + "/v1/completions",
                {"prompt": "hi", "max_tokens": 4})
    assert out["usage"]["completion_tokens"] >= 1
