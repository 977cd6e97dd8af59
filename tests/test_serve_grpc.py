"""gRPC v2 open-inference-protocol endpoint: same engine, same answers as
REST ((U) kserve kserve/protocol/grpc; SURVEY.md §2.3#26 — the reference's
v2 is REST+gRPC, so is ours)."""

import json
import urllib.request

import grpc
import jax
import pytest

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine
from kubeflow_tpu.serve.grpc_server import oip_stub
from kubeflow_tpu.serve.protos import oip_pb2 as pb
from kubeflow_tpu.serve.server import ModelServer


@pytest.fixture(scope="module")
def server():
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(cfg, BatchingSpec(
        max_batch_size=2, max_seq_len=96, page_size=16,
        chunked_prefill_tokens=32),
        params=params)
    srv = ModelServer("llm", engine, grpc_port=0)
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def stub(server):
    channel = grpc.insecure_channel(server.grpc_server.target)
    yield oip_stub(channel)
    channel.close()


def test_health_rpcs(stub):
    assert stub.ServerLive(pb.ServerLiveRequest()).live
    assert stub.ServerReady(pb.ServerReadyRequest()).ready
    assert stub.ModelReady(pb.ModelReadyRequest(name="llm")).ready
    assert not stub.ModelReady(pb.ModelReadyRequest(name="nope")).ready


def test_server_and_model_metadata(stub):
    meta = stub.ServerMetadata(pb.ServerMetadataRequest())
    assert meta.name == "llm"
    mm = stub.ModelMetadata(pb.ModelMetadataRequest(name="llm"))
    assert mm.platform == "kubeflow-tpu-llm"
    assert mm.inputs[0].datatype == "BYTES"
    with pytest.raises(grpc.RpcError) as exc:
        stub.ModelMetadata(pb.ModelMetadataRequest(name="nope"))
    assert exc.value.code() == grpc.StatusCode.NOT_FOUND


def test_infer_matches_rest(server, stub):
    """The gRPC and REST v2 surfaces share one engine: greedy answers must
    be identical."""
    req = pb.ModelInferRequest(model_name="llm")
    req.parameters["max_tokens"].int64_param = 6
    req.parameters["temperature"].double_param = 0.0
    tin = req.inputs.add(name="text", datatype="BYTES", shape=[1])
    tin.contents.bytes_contents.append(b"hello tpu")
    out = stub.ModelInfer(req)
    assert out.model_name == "llm"
    grpc_text = out.outputs[0].contents.bytes_contents[0].decode()

    body = json.dumps({"inputs": [{"name": "text", "datatype": "BYTES",
                                   "shape": [1], "data": ["hello tpu"]}],
                       "max_tokens": 6, "temperature": 0.0}).encode()
    http_req = urllib.request.Request(
        server.url + "/v2/models/llm/infer", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(http_req, timeout=120) as r:
        rest_text = json.loads(r.read())["outputs"][0]["data"][0]
    assert grpc_text == rest_text
    assert len(grpc_text) > 0


def test_infer_bad_datatype_rejected(stub):
    req = pb.ModelInferRequest(model_name="llm")
    tin = req.inputs.add(name="ids", datatype="INT32", shape=[2])
    tin.contents.int_contents.extend([1, 2])
    with pytest.raises(grpc.RpcError) as exc:
        stub.ModelInfer(req)
    assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_infer_unknown_model(stub):
    req = pb.ModelInferRequest(model_name="ghost")
    tin = req.inputs.add(name="text", datatype="BYTES", shape=[1])
    tin.contents.bytes_contents.append(b"x")
    with pytest.raises(grpc.RpcError) as exc:
        stub.ModelInfer(req)
    assert exc.value.code() == grpc.StatusCode.NOT_FOUND
