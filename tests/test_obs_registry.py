"""Unified metrics registry (obs/registry.py): primitives, escaping,
exposition grammar, the metric-name lint, and — the migration contract —
every pre-existing /metrics series name surviving the move onto the
registry (platform render_metrics + model-server metrics_text)."""

import math

import pytest

from kubeflow_tpu.obs.registry import (
    Counter, Gauge, Histogram, MetricsRegistry, escape_label_value,
    format_line, parse_exposition,
)


# -- primitives ----------------------------------------------------------------

def test_counter_gauge_histogram_render_and_parse():
    reg = MetricsRegistry()
    reg.counter("kftpu_reqs_total").inc(3, model="m")
    reg.gauge("kftpu_depth").set(7)
    h = reg.histogram("kftpu_delay_seconds", [0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    samples = dict(((n, tuple(sorted(lbl.items()))), v)
                   for n, lbl, v in parse_exposition(reg.render()))
    assert samples[("kftpu_reqs_total", (("model", "m"),))] == 3
    assert samples[("kftpu_depth", ())] == 7
    # cumulative buckets with the +Inf tail
    assert samples[("kftpu_delay_seconds_bucket", (("le", "0.1"),))] == 1
    assert samples[("kftpu_delay_seconds_bucket", (("le", "1.0"),))] == 2
    assert samples[("kftpu_delay_seconds_bucket", (("le", "+Inf"),))] == 3
    assert samples[("kftpu_delay_seconds_count", ())] == 3


def test_counter_refuses_negative_and_duplicate_type():
    reg = MetricsRegistry()
    c = reg.counter("kftpu_c_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("kftpu_c_total")   # same name, different type
    assert reg.counter("kftpu_c_total") is c   # same type: get-or-create


def test_register_refuses_duplicates():
    reg = MetricsRegistry()
    reg.register(Gauge("kftpu_x"))
    with pytest.raises(ValueError):
        reg.register(Counter("kftpu_x"))


def test_bad_names_rejected():
    with pytest.raises(ValueError):
        Gauge("kftpu bad name")
    with pytest.raises(ValueError):
        Histogram("kftpu_h", [1.0, 0.5])   # unsorted buckets
    g = Gauge("kftpu_ok")
    with pytest.raises(ValueError):
        g.set(1, **{"0bad": "v"})


# -- escaping (the satellite regression) ---------------------------------------

def test_label_escaping_quotes_backslashes_newlines():
    raw = 'he said "hi"\\and\nmoved on'
    line = format_line("kftpu_m", 1, {"name": raw})
    # The escaped line must parse under the strict grammar and round-trip
    # back to the original value.
    ((name, labels, value),) = parse_exposition(line)
    assert name == "kftpu_m" and value == 1
    assert labels["name"] == raw


def test_platform_line_uses_shared_escaper():
    # platform/metrics._line previously emitted invalid exposition text for
    # quotes/backslashes/newlines in object names.
    from kubeflow_tpu.platform.metrics import _line

    line = _line("kftpu_objects", 2, {"kind": 'Job"x\\y\nz'})
    ((_, labels, _),) = parse_exposition(line)
    assert labels["kind"] == 'Job"x\\y\nz'


def test_escape_is_order_correct():
    # Backslash must escape first, or \n in the input would double-escape.
    assert escape_label_value("\\n") == "\\\\n"
    assert escape_label_value("\n") == "\\n"


# -- grammar parser ------------------------------------------------------------

def test_parse_exposition_rejects_garbage():
    with pytest.raises(ValueError):
        parse_exposition('kftpu_m{unterminated="} 1')
    with pytest.raises(ValueError):
        parse_exposition("kftpu_m 1 2 3")
    with pytest.raises(ValueError):
        parse_exposition("# FROB kftpu_m gauge")
    assert parse_exposition("kftpu_m +Inf")[0][2] == math.inf


def test_parse_exposition_empty_payload():
    """An empty (or whitespace-only) scrape parses to zero samples —
    the contract extractor's consumers treat that as "no signal", never
    as an error."""
    assert parse_exposition("") == []
    assert parse_exposition("\n\n   \n") == []


def test_parse_exposition_histogram_suffix_family():
    """A labeled histogram renders the full ``_bucket``/``_sum``/
    ``_count`` family (the suffix grammar the X-rule contract matching
    strips back to the family name): cumulative buckets, a ``le`` label
    per bucket with the ``+Inf`` tail, and consistent count/sum lines."""
    reg = MetricsRegistry()
    h = reg.histogram("kftpu_ct_delay_seconds", [0.1, 1.0])
    h.set_cumulative([2, 3, 1], 7.5, 6, model="m", qos="batch")
    samples = parse_exposition(reg.render())
    names = {n for n, _, _ in samples}
    assert names == {"kftpu_ct_delay_seconds_bucket",
                     "kftpu_ct_delay_seconds_sum",
                     "kftpu_ct_delay_seconds_count"}
    buckets = {lbl["le"]: v for n, lbl, v in samples
               if n == "kftpu_ct_delay_seconds_bucket"}
    assert buckets == {"0.1": 2, "1.0": 5, "+Inf": 6}   # cumulative
    for n, lbl, v in samples:
        assert lbl["model"] == "m" and lbl["qos"] == "batch"
        if n.endswith("_count"):
            assert v == 6
        if n.endswith("_sum"):
            assert v == 7.5


def test_parse_exposition_escaped_label_values_round_trip():
    """Escaped quotes/backslashes/newlines inside label values must
    parse back to the original value — including on histogram suffix
    series, where a bad unescape would split the ``le`` label."""
    raw = 'tenant "a"\\eu\nwest'
    reg = MetricsRegistry()
    reg.counter("kftpu_ct_reqs_total").inc(1, tenant=raw)
    h = reg.histogram("kftpu_ct_lat_seconds", [0.5])
    h.observe(0.2, tenant=raw)
    samples = parse_exposition(reg.render())
    assert samples, "payload must parse"
    for name, labels, _ in samples:
        assert labels["tenant"] == raw
        if name == "kftpu_ct_lat_seconds_bucket":
            assert labels["le"] in ("0.5", "+Inf")


# -- lint ----------------------------------------------------------------------

def test_lint_flags_unprefixed_names():
    reg = MetricsRegistry()
    reg.gauge("kftpu_good")
    reg.gauge("bad_name")
    problems = reg.lint()
    assert any("bad_name" in p for p in problems)
    assert not any("kftpu_good" in p for p in problems)


# -- series-name migration contract --------------------------------------------

#: Every series family the seed's hand-rolled renderers exposed. The
#: registry migration must keep them all (supersets allowed).
SEED_PLATFORM_SERIES = {
    "kftpu_objects", "kftpu_job_step", "kftpu_job_tokens_per_sec_per_chip",
    "kftpu_job_step_time_ms", "kftpu_job_mfu", "kftpu_job_loss",
    "kftpu_workers", "kftpu_chips_total", "kftpu_chips_allocated",
    "kftpu_events_total",
}
SEED_SERVING_SERIES = {
    "kftpu_serving_in_flight", "kftpu_serving_requests_total",
    "kftpu_serving_tokens_total", "kftpu_serving_queue_depth",
    "kftpu_serving_requests_shed_total",
    "kftpu_serving_requests_cancelled_total",
    "kftpu_serving_requests_expired_total",
    "kftpu_serving_requests_per_sec", "kftpu_serving_tokens_per_sec",
    "kftpu_serving_queue_delay_seconds_bucket",
    "kftpu_serving_queue_delay_seconds_sum",
    "kftpu_serving_queue_delay_seconds_count",
    # Decode hot-loop health (ISSUE 4): per-round host gap + pipeline
    # depth, exposed per engine through the same registry path.
    "kftpu_engine_host_gap_seconds_bucket",
    "kftpu_engine_host_gap_seconds_sum",
    "kftpu_engine_host_gap_seconds_count",
    "kftpu_engine_dispatch_depth",
}


def test_platform_series_names_survive_migration():
    from kubeflow_tpu.core.events import EventRecorder
    from kubeflow_tpu.core.jobs import JAXJob, JAXJobSpec, ReplicaSpec, \
        TPUResourceSpec, Worker, WorkerSpec, WorkloadSpec
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.store import ObjectStore
    from kubeflow_tpu.platform.metrics import render_metrics
    from kubeflow_tpu.runtime.allocator import GangAllocator
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology

    store = ObjectStore()
    job = JAXJob(
        metadata=ObjectMeta(name="j", namespace="default"),
        spec=JAXJobSpec(replica_specs={"worker": ReplicaSpec(
            replicas=1,
            template=WorkloadSpec(entrypoint="noop", config={}),
            resources=TPUResourceSpec(tpu_chips=1))}))
    job.status.metrics.step = 5
    job.status.metrics.tokens_per_sec_per_chip = 10.0
    job.status.metrics.step_time_ms = 3.0
    job.status.metrics.mfu = 0.5
    job.status.metrics.loss = 2.0
    store.apply(job)
    store.apply(Worker(
        metadata=ObjectMeta(name="w", namespace="default"),
        spec=WorkerSpec(job="default/j", replica_index=0,
                        template=WorkloadSpec(entrypoint="noop", config={}))))
    recorder = EventRecorder()
    recorder.normal(job, "Created", "x")
    allocator = GangAllocator(Cluster(slices=[
        SliceTopology(name="s0", generation="v5e", dims=(2, 2))]))

    text = render_metrics(store, recorder, allocator)
    names = {n for n, _, _ in parse_exposition(text)}
    missing = SEED_PLATFORM_SERIES - names
    assert not missing, f"series lost in migration: {missing}"


def test_serving_series_names_survive_migration(tiny_engine_server):
    server = tiny_engine_server
    text = server.metrics_text()
    names = {n for n, _, _ in parse_exposition(text)}
    missing = SEED_SERVING_SERIES - names
    assert not missing, f"series lost in migration: {missing}"
    # and the whole scrape parses + is kftpu_-prefixed throughout
    for n in names:
        assert n.startswith("kftpu_"), n


@pytest.fixture(scope="module")
def tiny_engine_server():
    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
    from kubeflow_tpu.serve.server import ModelServer

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg, BatchingSpec(max_batch_size=2, max_seq_len=64,
                          page_size=16, chunked_prefill_tokens=32),
        params=params)
    # One completed request so rate/percentile gauges have data.
    req = engine.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
    while not req.done.is_set():
        engine.step()
    server = ModelServer("tiny", engine, port=0)
    yield server
    server.httpd.server_close()


def test_capacity_accessor():
    from kubeflow_tpu.runtime.allocator import GangAllocator, GangRequest
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology

    alloc = GangAllocator(Cluster(slices=[
        SliceTopology(name="s0", generation="v5e", dims=(2, 2))]))
    assert alloc.capacity() == (4, 4)
    alloc.submit(GangRequest(name="g", num_workers=1, chips_per_worker=3))
    assert alloc.capacity() == (4, 1)
    alloc.release("g")
    assert alloc.capacity() == (4, 4)


def test_render_metrics_does_not_touch_private_cluster(monkeypatch):
    """platform metrics must use the public capacity() accessor, not
    allocator._cluster."""
    from kubeflow_tpu.core.events import EventRecorder
    from kubeflow_tpu.core.store import ObjectStore
    from kubeflow_tpu.platform.metrics import render_metrics

    class PublicOnlyAllocator:
        def capacity(self):
            return (8, 5)

    text = render_metrics(ObjectStore(), EventRecorder(),
                          PublicOnlyAllocator())
    samples = {n: v for n, _, v in parse_exposition(text)}
    assert samples["kftpu_chips_total"] == 8
    assert samples["kftpu_chips_allocated"] == 3
