"""Platform tracing (obs/trace.py): tracer mechanics, Chrome export, the
propagation contract (one trace id router → server → engine with nested
queued/prefill/decode spans), failure-status closure on cancelled/expired
requests with a quiescent ring buffer, the slow-request log, and the
controller/pipeline span hooks."""

import json
import threading
import time
import urllib.request

import pytest
import jax

from kubeflow_tpu.obs.trace import (
    Tracer, format_trace_tree, get_tracer, parse_trace_header,
)

TRACER = get_tracer()


@pytest.fixture(autouse=True)
def _fresh_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


# -- tracer mechanics ----------------------------------------------------------

def test_contextvar_nesting_and_status():
    t = Tracer()
    with t.span("root", path="/x") as root:
        with t.span("child") as child:
            assert child.parent_id == root.span_id
            assert child.trace_id == root.trace_id
        assert t.current() is root
    assert t.current() is None
    tr = t.traces()[0]
    assert tr["root"]["name"] == "root"
    assert {s["name"] for s in tr["spans"]} == {"root", "child"}
    assert t.open_spans() == 0


def test_exception_marks_span_error():
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("nope")
    tr = t.traces()[0]
    assert tr["root"]["status"] == "error"
    assert "RuntimeError" in tr["root"]["attrs"]["error"]
    assert t.open_spans() == 0


def test_cross_thread_parenting():
    t = Tracer()
    with t.span("root") as root:
        ctx = root.context
        done = threading.Event()

        def worker():
            sp = t.start_span("engine.work", parent=ctx)
            sp.end()
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
    spans = t.traces()[0]["spans"]
    assert {s["trace_id"] for s in spans} == {root.trace_id}


def test_header_roundtrip_and_garbage():
    t = Tracer()
    with t.span("root") as root:
        hdr = t.inject(root)
    ctx = parse_trace_header(hdr)
    assert ctx.trace_id == root.trace_id
    assert ctx.span_id == root.span_id
    assert parse_trace_header(None) is None
    assert parse_trace_header("not hex at all!") is None
    assert parse_trace_header("deadbeef") is None   # no separator


def test_disabled_tracer_is_noop():
    t = Tracer()
    t.enabled = False
    with t.span("root") as sp:
        sp.set_attrs(x=1)
        sp.add_event("e")
    assert t.traces() == []
    assert t.open_spans() == 0


def test_ring_buffer_bounded():
    t = Tracer(max_traces=4)
    for i in range(10):
        with t.span(f"r{i}"):
            pass
    assert len(t.traces()) == 4


def test_chrome_export_valid():
    t = Tracer()
    with t.span("root"):
        with t.span("child"):
            pass
    doc = json.loads(json.dumps(t.export_chrome()))
    assert doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert isinstance(ev["tid"], int)


def test_slowest_filter():
    t = Tracer()
    with t.span("fast"):
        pass
    with t.span("slow"):
        time.sleep(0.05)
    slowest = t.traces(slowest=1)
    assert len(slowest) == 1
    assert slowest[0]["root"]["name"] == "slow"


def test_slow_request_log(caplog):
    t = Tracer(slow_threshold_s=0.01)
    with caplog.at_level("WARNING", logger="kubeflow_tpu.obs.slow"):
        with t.span("root"):
            with t.span("inner"):
                time.sleep(0.03)
    assert any("slow request" in r.message for r in caplog.records)
    assert any("inner" in r.getMessage() for r in caplog.records)


def test_format_tree_handles_orphans():
    out = format_trace_tree([
        {"span_id": "b", "parent_id": "missing", "name": "orphan",
         "start": 1.0, "duration_ms": 2.0, "status": "ok", "attrs": {}},
    ])
    assert "orphan" in out


# -- engine lifecycle spans ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    return LLMEngine(
        cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                          page_size=16, chunked_prefill_tokens=32),
        params=params)


def test_engine_spans_one_trace(tiny_engine):
    from kubeflow_tpu.serve.engine import SamplingParams

    with TRACER.span("server.request") as root:
        req = tiny_engine.submit([1, 2, 3], SamplingParams(max_new_tokens=3),
                                 trace_parent=root)
        while not req.done.is_set():
            tiny_engine.step()
    tr = TRACER.trace(root.trace_id)
    names = [s["name"] for s in tr["spans"]]
    assert "engine.queued" in names
    assert "engine.prefill" in names
    assert "engine.decode" in names
    decode = next(s for s in tr["spans"] if s["name"] == "engine.decode")
    assert decode["status"] == "ok"
    assert decode["attrs"]["finish_reason"] in ("length", "stop")
    assert any(e["name"] == "decode_round" for e in decode["events"])
    assert TRACER.open_spans() == 0


def test_cancelled_request_closes_span_cancelled(tiny_engine):
    from kubeflow_tpu.serve.engine import SamplingParams

    with TRACER.span("server.request") as root:
        req = tiny_engine.submit([5, 6, 7],
                                 SamplingParams(max_new_tokens=50),
                                 trace_parent=root)
        req.cancel()
        for _ in range(50):
            tiny_engine.step()
            if req.done.is_set():
                break
    assert req.finish_reason == "cancelled"
    tr = TRACER.trace(root.trace_id)
    engine_spans = [s for s in tr["spans"] if s["name"].startswith("engine.")]
    assert engine_spans, "cancelled request left no engine span"
    assert any(s["status"] == "cancelled" for s in engine_spans)
    # the quiescence invariant: nothing left open after the reap
    assert TRACER.open_spans() == 0


def test_expired_request_closes_span_error(tiny_engine):
    from kubeflow_tpu.serve.engine import SamplingParams

    with TRACER.span("server.request") as root:
        req = tiny_engine.submit([9, 10],
                                 SamplingParams(max_new_tokens=50),
                                 trace_parent=root,
                                 deadline=time.monotonic() - 1.0)
        for _ in range(50):
            tiny_engine.step()
            if req.done.is_set():
                break
    assert req.finish_reason == "deadline"
    tr = TRACER.trace(root.trace_id)
    statuses = {s["status"] for s in tr["spans"]
                if s["name"].startswith("engine.")}
    assert "error" in statuses
    assert TRACER.open_spans() == 0


def test_untraced_requests_pay_nothing(tiny_engine):
    from kubeflow_tpu.serve.engine import SamplingParams

    req = tiny_engine.submit([1, 2], SamplingParams(max_new_tokens=2))
    while not req.done.is_set():
        tiny_engine.step()
    assert req.span is None
    assert TRACER.open_spans() == 0
    assert TRACER.traces() == []


# -- HTTP propagation e2e ------------------------------------------------------

@pytest.fixture(scope="module")
def routed_stack(tiny_engine):
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    server = ModelServer("trace-demo", tiny_engine, port=0)
    server.start()
    router = Router(queue_timeout=5.0, upstream_timeout=60.0)
    router.set_backends({"latest": [server.url]})
    router.start()
    yield router, server
    router.stop()
    # the whole server, engine included: a scheduler thread left looping
    # writes engine.* spans into every later profiler capture of the process
    server.stop()


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _wait_for(pred, timeout: float = 10.0) -> bool:
    """The HTTP client can observe the response bytes a beat before the
    router handler's span context manager exits — poll instead of racing
    the handler thread."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _router_rooted_traces():
    return [t for t in TRACER.traces()
            if t["root"] and t["root"]["name"] == "router.request"]


def test_one_trace_id_router_to_engine(routed_stack):
    router, server = routed_stack
    out = _post(router.url + "/v1/completions",
                {"prompt": "hi", "max_tokens": 3})
    assert out["usage"]["completion_tokens"] >= 1
    # one trace, one id, ≥3 nested spans under the router root
    assert _wait_for(lambda: _router_rooted_traces()), \
        "router did not root a trace"
    tr = _router_rooted_traces()[0]
    ids = {s["trace_id"] for s in tr["spans"]}
    assert len(ids) == 1
    names = {s["name"] for s in tr["spans"]}
    assert {"router.request", "server.request", "engine.queued",
            "engine.prefill", "engine.decode"} <= names
    # nesting: server.request under router.request, engine spans under
    # server.request
    by_id = {s["span_id"]: s for s in tr["spans"]}
    srv = next(s for s in tr["spans"] if s["name"] == "server.request")
    assert by_id[srv["parent_id"]]["name"] == "router.request"
    for name in ("engine.queued", "engine.prefill", "engine.decode"):
        sp = next(s for s in tr["spans"] if s["name"] == name)
        assert by_id[sp["parent_id"]]["name"] == "server.request"
    assert _wait_for(lambda: TRACER.open_spans() == 0)


def test_client_supplied_header_joins(routed_stack):
    router, _ = routed_stack
    body = json.dumps({"prompt": "x", "max_tokens": 2}).encode()
    req = urllib.request.Request(
        router.url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json",
                 "X-Kftpu-Trace": "ab12cd34" * 4 + "-" + "12ef" * 4})
    with urllib.request.urlopen(req, timeout=120) as r:
        json.loads(r.read())
    tr = TRACER.trace("ab12cd34" * 4)
    assert tr is not None, "client trace id was not joined"
    assert any(s["name"] == "engine.decode" for s in tr["spans"])


def test_debug_traces_endpoint(routed_stack):
    router, server = routed_stack
    _post(router.url + "/v1/completions", {"prompt": "q", "max_tokens": 2})
    assert _wait_for(lambda: _router_rooted_traces())
    with urllib.request.urlopen(server.url + "/debug/traces?slowest=1",
                                timeout=10) as r:
        doc = json.loads(r.read())
    assert len(doc["traces"]) == 1
    assert doc["traces"][0]["root"] is not None
    with urllib.request.urlopen(
            router.url + "/-/router/debug/traces", timeout=10) as r:
        doc = json.loads(r.read())
    assert doc["traces"]
    with urllib.request.urlopen(server.url + "/debug/traces?chrome=1",
                                timeout=10) as r:
        chrome = json.loads(r.read())
    assert chrome["traceEvents"]


# -- controller + pipeline hooks -----------------------------------------------

def test_controller_reconcile_span(store):
    from kubeflow_tpu.core.jobs import JAXJob
    from kubeflow_tpu.operator.controller import Controller

    class Recon:
        kinds = [JAXJob.KIND]

        def key_for(self, ev):
            return ev.object.metadata.key

        def reconcile(self, key):
            sp = TRACER.current()
            assert sp is not None and sp.name == "reconcile"
            return None

    ctrl = Controller(store, Recon(), name="test-ctrl")
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.jobs import (
        JAXJobSpec, ReplicaSpec, TPUResourceSpec, WorkloadSpec,
    )

    store.apply(JAXJob(
        metadata=ObjectMeta(name="t", namespace="default"),
        spec=JAXJobSpec(replica_specs={"worker": ReplicaSpec(
            replicas=1,
            template=WorkloadSpec(entrypoint="noop", config={}),
            resources=TPUResourceSpec(tpu_chips=1))})))
    assert ctrl.step() >= 1
    spans = [t for t in TRACER.traces()
             if t["root"] and t["root"]["name"] == "reconcile"]
    assert spans
    assert spans[0]["root"]["attrs"]["controller"] == "test-ctrl"
    assert TRACER.open_spans() == 0


def test_crashing_reconcile_span_closes_error(store):
    from kubeflow_tpu.core.jobs import JAXJob
    from kubeflow_tpu.operator.controller import Controller

    class Bad:
        kinds = [JAXJob.KIND]

        def key_for(self, ev):
            return ev.object.metadata.key

        def reconcile(self, key):
            raise RuntimeError("kaboom")

    ctrl = Controller(store, Bad(), name="bad-ctrl")
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.jobs import (
        JAXJobSpec, ReplicaSpec, TPUResourceSpec, WorkloadSpec,
    )

    store.apply(JAXJob(
        metadata=ObjectMeta(name="b", namespace="default"),
        spec=JAXJobSpec(replica_specs={"worker": ReplicaSpec(
            replicas=1,
            template=WorkloadSpec(entrypoint="noop", config={}),
            resources=TPUResourceSpec(tpu_chips=1))})))
    ctrl.step()
    spans = [t for t in TRACER.traces()
             if t["root"] and t["root"]["name"] == "reconcile"]
    assert spans and spans[0]["root"]["status"] == "error"
    assert TRACER.open_spans() == 0


def test_pipeline_run_and_task_spans(tmp_path):
    from kubeflow_tpu.pipelines import dsl
    from kubeflow_tpu.pipelines.artifacts import ArtifactStore
    from kubeflow_tpu.pipelines.compiler import compile_pipeline
    from kubeflow_tpu.pipelines.executor import PipelineExecutor
    from kubeflow_tpu.pipelines.metadata import MetadataStore

    @dsl.component
    def add_one(x: int) -> int:
        return x + 1

    @dsl.component
    def add_two(x: int) -> int:
        return x + 2

    @dsl.pipeline
    def pipe(x: int = 1):
        a = add_one(x=x)
        add_two(x=a.output)

    ir = compile_pipeline(pipe)
    ex = PipelineExecutor(ArtifactStore(str(tmp_path / "cas")),
                          MetadataStore(str(tmp_path / "md.db")))
    result = ex.run(ir, run_name="t1")
    assert result.phase.value == "Succeeded"
    runs = [t for t in TRACER.traces()
            if t["root"] and t["root"]["name"] == "pipeline.run"]
    assert runs
    tr = runs[0]
    tasks = [s for s in tr["spans"] if s["name"] == "pipeline.task"]
    assert len(tasks) == 2
    assert all(s["parent_id"] == tr["root"]["span_id"] for s in tasks)
    assert TRACER.open_spans() == 0
