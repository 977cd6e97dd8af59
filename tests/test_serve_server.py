"""Model-server protocol surface tests (≈ kserve's FastAPI TestClient server
tests, SURVEY.md §4.4 — here against the real threaded server over a port)."""

import json
import urllib.request

import pytest
import jax
import jax.numpy as jnp

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine
from kubeflow_tpu.serve.server import ModelServer
from kubeflow_tpu.serve.tokenizer import ByteTokenizer, get_tokenizer


@pytest.fixture(scope="module")
def server():
    cfg = preset("tiny", vocab_size=512)  # roomy enough for byte vocab (259)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg, BatchingSpec(max_batch_size=4, max_seq_len=96,
                          page_size=16, chunked_prefill_tokens=64),
        params=params)
    srv = ModelServer("demo", engine, port=0)
    srv.start()
    yield srv
    srv.stop()


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def test_health_and_metadata(server):
    status, body = _get(server.url + "/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"
    status, body = _get(server.url + "/v2/models/demo")
    meta = json.loads(body)
    assert meta["name"] == "demo"
    assert meta["inputs"][0]["datatype"] == "BYTES"


def test_v1_predict(server):
    out = _post(server.url + "/v1/models/demo:predict",
                {"instances": ["ab", "xyz"], "max_tokens": 4})
    assert len(out["predictions"]) == 2
    assert all(isinstance(p, str) for p in out["predictions"])


def test_v2_infer(server):
    out = _post(server.url + "/v2/models/demo/infer",
                {"inputs": [{"name": "text", "shape": [1],
                             "datatype": "BYTES", "data": ["hello"]}],
                 "max_tokens": 3})
    assert out["model_name"] == "demo"
    assert out["outputs"][0]["shape"] == [1]


def test_openai_completions(server):
    out = _post(server.url + "/v1/completions",
                {"prompt": "hi", "max_tokens": 5, "model": "demo"})
    assert out["object"] == "text_completion"
    assert out["usage"]["completion_tokens"] <= 5
    assert out["choices"][0]["finish_reason"] in ("length", "stop")
    # The ids the engine emitted ride beside the text: a client with its
    # own tokenizer decodes them (the byte tokenizer drops ids > 258).
    ids = out["choices"][0]["token_ids"]
    assert len(ids) == out["usage"]["completion_tokens"]
    assert all(isinstance(t, int) and 0 <= t < 512 for t in ids)


def test_introspected_dispatch_records_each_variant_once(server,
                                                         monkeypatch):
    """The wrapper the engine puts around its jitted programs on the TPU
    (LLMEngine._introspected): one lowering per (token block shape, static
    arguments) variant, the program's own result passed through."""
    from kubeflow_tpu.runtime import device_report

    lowerings = []
    real = device_report.lowered_kernel_calls
    monkeypatch.setattr(
        device_report, "lowered_kernel_calls",
        lambda fn, *a: lowerings.append(a[3]) or real(fn, *a))
    eng = server.engine
    toy = eng._introspected("toy", jax.jit(
        lambda p, c, t, n: t * n + p + c, static_argnums=(3,)))
    one, block = jnp.float32(1.0), jnp.ones((1, 8))
    try:
        for steps in (4, 4, 2):
            out = toy(one, one, block, steps)
            assert float(out[0, 0]) == steps + 2
        assert lowerings == [4, 2]
        assert eng.program_kernels["toy[1x8,4]"] == {}      # CPU: no kernel
        assert set(eng.program_kernels) == {"toy[1x8,4]", "toy[1x8,2]"}
    finally:
        eng.program_kernels.clear()


def test_debug_device_reports_what_the_replica_runs_on(server):
    """GET /debug/device: the parent of a tpu replica must not touch the
    chip, so the replica says what it runs on. On the CPU: the cpu device,
    no memory counters, no compile cache, and no Pallas kernel in any
    program (interpret mode leaves no custom call)."""
    status, body = _get(server.url + "/debug/device")
    rep = json.loads(body)
    assert status == 200 and rep["platform"] == "cpu"
    assert rep["device_count"] == len(jax.devices())
    assert rep["memory"][0]["peak_bytes_in_use"] is None
    assert rep["compile_cache"] is None
    assert rep["programs"] == {"demo": {}}


def test_openai_chat_completions(server):
    out = _post(server.url + "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hey"}],
                 "max_tokens": 4})
    assert out["object"] == "chat.completion"
    assert out["choices"][0]["message"]["role"] == "assistant"


def test_streaming_sse(server):
    req = urllib.request.Request(
        server.url + "/v1/completions",
        data=json.dumps({"prompt": "s", "max_tokens": 4,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert "text/event-stream" in r.headers["Content-Type"]
        payload = r.read().decode()
    events = [ln[6:] for ln in payload.splitlines() if ln.startswith("data: ")]
    assert events[-1] == "[DONE]"
    assert 1 <= len(events) - 1 <= 4
    assert all("choices" in json.loads(e) for e in events[:-1])


def test_metrics_endpoint(server):
    _post(server.url + "/v1/models/demo:predict",
          {"instances": ["m"], "max_tokens": 2})
    status, text = _get(server.url + "/metrics")
    assert status == 200
    assert "kftpu_serving_requests_total" in text
    assert "kftpu_serving_ttft_p50_ms" in text
    # Lifecycle/shedding surface (ISSUE 2): depth gauge, shed/reap
    # counters, queue-delay histogram.
    assert "kftpu_serving_queue_depth" in text
    assert "kftpu_serving_requests_shed_total" in text
    assert "kftpu_serving_requests_cancelled_total" in text
    assert "kftpu_serving_queue_delay_seconds_bucket" in text
    assert 'le="+Inf"' in text


def test_expired_deadline_returns_504_and_reaps(server):
    """A request whose budget is already gone must fail explicitly (504,
    finish_reason='deadline' engine-side) — never hang, never 200-empty."""
    req = urllib.request.Request(
        server.url + "/v1/completions",
        data=json.dumps({"prompt": "ab", "max_tokens": 8,
                         "timeout": 0}).encode(),
        headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False, "expected 504"
    except urllib.error.HTTPError as e:
        assert e.code == 504
        assert "deadline" in json.loads(e.read())["error"]
    assert server.engine.metrics.snapshot()["requests_expired"] >= 1
    # The engine is unharmed: the next request completes normally.
    out = _post(server.url + "/v1/completions",
                {"prompt": "cd", "max_tokens": 3})
    assert out["choices"][0]["finish_reason"] in ("length", "stop")


def test_overload_returns_429_with_retry_after():
    """Bounded admission at the protocol surface: queue full -> immediate
    429 + Retry-After (the engine never sees the shed request)."""
    import threading
    import time as _t

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(1), cfg)
    engine = LLMEngine(
        cfg, BatchingSpec(max_batch_size=1, max_seq_len=64,
                          page_size=16, chunked_prefill_tokens=32,
                          max_queue=1),
        params=params)
    srv = ModelServer("jam", engine, port=0)
    srv.start()
    try:
        engine.stop()          # freeze the scheduler: submissions pile up
        first = threading.Thread(target=lambda: http(
            srv, "POST", "/v1/completions",
            {"prompt": "xy", "max_tokens": 4, "timeout": 2}))
        first.start()
        deadline = _t.monotonic() + 5.0
        while engine.queue_depth() < 1:
            assert _t.monotonic() < deadline
            _t.sleep(0.01)
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": "zz", "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=30)
            assert False, "expected 429"
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert int(e.headers["Retry-After"]) >= 1
            assert "queue full" in json.loads(e.read())["error"]
        first.join(timeout=15.0)
        assert not first.is_alive(), "queued request hung"
        assert engine.metrics.snapshot()["requests_shed"] >= 1
    finally:
        srv.stop()


def test_bad_request_400(server):
    req = urllib.request.Request(
        server.url + "/v1/models/demo:predict",
        data=json.dumps({"wrong": 1}).encode(),
        headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=30)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_byte_tokenizer_roundtrip():
    tok = get_tokenizer("byte")
    assert isinstance(tok, ByteTokenizer)
    ids = tok.encode("héllo ✓")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "héllo ✓"


def http(server, method: str, path: str, body: dict | None = None):
    """(status, parsed-json-or-text) without raising on 4xx/5xx."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(server.url + path, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            raw, code, ctype = r.read(), r.status, r.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")
    return code, (json.loads(raw) if "json" in ctype else raw.decode())


class TestMultiModel:
    """ModelMesh-lite: repository-backed server with LRU load-on-demand and
    the v2 repository API (SURVEY.md §2.3#29)."""

    @pytest.fixture()
    def repo_server(self):
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.serve.repository import ModelRepository

        repo = ModelRepository(max_loaded=1)   # force evictions
        repo.register("alpha", preset("tiny"), batching=BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=16,
            chunked_prefill_tokens=16))
        repo.register("beta", preset("tiny-gemma"), batching=BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=16,
            chunked_prefill_tokens=16))
        srv = ModelServer("alpha", repository=repo, port=0)
        srv.start()
        yield srv
        srv.stop()

    @pytest.mark.slow  # tier-1 budget (ISSUE 17): slowest fast tests re-marked
    def test_index_and_lazy_load(self, repo_server):
        code, out = http(repo_server, "GET", "/v2/repository/index")
        assert code == 200
        states = {m["name"]: m["state"] for m in out["models"]}
        assert states == {"alpha": "UNLOADED", "beta": "UNLOADED"}
        # Serving a request loads on demand.
        code, out = http(repo_server, "POST", "/v1/models/alpha:predict",
                         {"instances": ["hi"], "max_tokens": 4})
        assert code == 200 and len(out["predictions"]) == 1
        states = {m["name"]: m["state"]
                  for m in http(repo_server, "GET",
                                "/v2/repository/index")[1]["models"]}
        assert states["alpha"] == "READY"

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_lru_eviction_on_second_model(self, repo_server):
        http(repo_server, "POST", "/v1/models/alpha:predict",
             {"instances": ["hi"], "max_tokens": 4})
        # Serving beta evicts alpha (max_loaded=1)...
        code, out = http(repo_server, "POST", "/v1/models/beta:predict",
                         {"instances": ["yo"], "max_tokens": 4})
        assert code == 200
        states = {m["name"]: m["state"]
                  for m in http(repo_server, "GET",
                                "/v2/repository/index")[1]["models"]}
        assert states == {"alpha": "UNLOADED", "beta": "READY"}
        # ...and alpha reloads transparently on the next request.
        code, _ = http(repo_server, "POST", "/v1/models/alpha:predict",
                       {"instances": ["back"], "max_tokens": 4})
        assert code == 200

    def test_explicit_load_unload(self, repo_server):
        code, out = http(repo_server, "POST",
                         "/v2/repository/models/beta/load", {})
        assert code == 200 and out["state"] == "READY"
        code, out = http(repo_server, "POST",
                         "/v2/repository/models/beta/unload", {})
        assert code == 200 and out["state"] == "UNLOADED"
        assert http(repo_server, "POST",
                    "/v2/repository/models/nope/load", {})[0] == 404

    def test_openai_model_field_routes(self, repo_server):
        code, out = http(repo_server, "POST", "/v1/completions",
                         {"model": "beta", "prompt": "hello",
                          "max_tokens": 4})
        assert code == 200 and out["model"] == "beta"

    def test_unknown_model_404(self, repo_server):
        code, out = http(repo_server, "POST", "/v1/models/ghost:predict",
                         {"instances": ["x"]})
        assert code == 404

    def test_metrics_labeled_per_model(self, repo_server):
        http(repo_server, "POST", "/v1/models/alpha:predict",
             {"instances": ["hi"], "max_tokens": 4})
        code, text = http(repo_server, "GET", "/metrics")
        assert 'kftpu_serving_requests_total{model="alpha"}' in text


def upcase_transformer(text: str, phase: str) -> str:
    """Test transformer: tags the prompt (pre) and uppercases output (post)."""
    return f"[pre]{text}" if phase == "pre" else text.upper()


class TestTransformer:
    def test_pre_and_post_hooks(self):
        cfg = preset("tiny", vocab_size=512)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        engine = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                                             page_size=16,
                                             chunked_prefill_tokens=16),
                           params=params)
        srv = ModelServer("t", engine, transformer=upcase_transformer, port=0)
        srv.start()
        try:
            code, out = http(srv, "POST", "/v1/models/t:predict",
                             {"instances": ["ab"], "max_tokens": 3})
            assert code == 200
            pred = out["predictions"][0]
            assert pred == pred.upper()     # post hook ran
        finally:
            srv.stop()
