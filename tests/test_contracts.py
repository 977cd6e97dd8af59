"""Cross-component name contracts, pinned against REAL components
(ISSUE 10): the autoscaler-probe ↔ engine-metrics series pair, the
centralized ``X-Kftpu-*`` header module riding through the chaos
middlebox, and the ``KFTPU_SANITIZE=contract`` runtime auditor agreeing
with the static extraction.

The probe pin is the load-bearing one: ``default_probe`` matches literal
series names against whatever a replica's ``/metrics`` renders, and
before this suite a rename on EITHER side broke nothing until the SLO
autoscaler silently held forever. Here the consumed set is derived from
the static contract extractor (not re-typed), so renaming the probe's
literals, the engine's definition sites, or ``_PROBE_SERIES`` each fail
a test."""

import json
import os
import urllib.request

import pytest
import jax

from kubeflow_tpu.analysis import core as analysis_core
from kubeflow_tpu.analysis import rules_contracts
from kubeflow_tpu.core.headers import (
    DEADLINE_HEADER, DECODE_ALTS_HEADER, DECODE_BACKEND_HEADER,
    FORWARD_HEADERS, HANDOFF_DTYPE_HEADER, HANDOFF_WIRE_HEADER, QOS_HEADER,
    TRACE_HEADER, USER_HEADER,
)
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.obs.registry import parse_exposition
from kubeflow_tpu.runtime import sanitize
from kubeflow_tpu.serve.engine import LLMEngine
from kubeflow_tpu.serve.isvc_controller import _PROBE_SERIES, default_probe
from kubeflow_tpu.serve.server import ModelServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _probe_consumed_series() -> set:
    """The series names ``default_probe`` consumes, per the STATIC
    contract extractor over the real module — the same table ``kftpu
    lint`` X701 checks, so this test and the lint gate can never
    disagree about what the probe reads."""
    mod = analysis_core.load_module(
        os.path.join(REPO, "kubeflow_tpu", "serve", "isvc_controller.py"),
        "kubeflow_tpu/serve/isvc_controller.py")
    return {name for name, _ in
            rules_contracts._extract(mod)["series_consumed"]}


@pytest.fixture(scope="module")
def server():
    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg, BatchingSpec(max_batch_size=2, max_seq_len=96,
                          page_size=16, chunked_prefill_tokens=32),
        params=params)
    srv = ModelServer("contract-pin", engine, port=0)
    srv.start()
    # One real completed request so the latency percentiles (TTFT,
    # queue delay, per-QoS p95s) exist in the engine snapshot — the
    # contract covers the loaded-replica payload, not the idle one.
    body = json.dumps({"prompt": "pin", "max_tokens": 4,
                       "timeout": 30}).encode()
    req = urllib.request.Request(
        srv.url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        r.read()
    yield srv
    srv.stop()


class TestAutoscalerSeriesContract:
    def test_extractor_chain_and_probe_tuple_agree(self):
        """The probe's match chain and its declared ``_PROBE_SERIES``
        must be the same set — a rename applied to one but not the other
        fails here before it can half-work at runtime."""
        assert _probe_consumed_series() == set(_PROBE_SERIES)

    def test_every_probed_series_is_in_a_real_metrics_payload(self, server):
        """Render a REAL engine /metrics payload and assert every series
        name ``default_probe`` matches on is present — fails if either
        the probe literals or the engine definition sites rename."""
        text = server.metrics_text()
        rendered = {name for name, _, _ in parse_exposition(text)}
        missing = _probe_consumed_series() - rendered
        assert not missing, (
            f"probe scrapes series the engine no longer renders: "
            f"{sorted(missing)}")

    def test_probe_parses_the_real_payload(self, server):
        """End to end over HTTP: the probe must come back ready with the
        latency signals populated from the real exposition payload."""
        got = default_probe(server.url, timeout=5.0)
        assert got is not None and got["ready"]
        assert got["requests_total"] >= 1
        assert got["ttft_p95_ms"] is not None
        assert got["queue_delay_p95_ms"] is not None
        assert got["qos_ttft_p95_ms"]       # default class is still a class


class TestHeaderModule:
    def test_one_owner_for_every_header(self):
        """The historical homes re-export the central constants — same
        objects, one spelling."""
        from kubeflow_tpu.obs import trace
        from kubeflow_tpu.serve import router

        assert trace.TRACE_HEADER is TRACE_HEADER
        assert router.DEADLINE_HEADER is DEADLINE_HEADER
        assert router.QOS_HEADER is QOS_HEADER
        assert USER_HEADER == "X-Kftpu-User"

    def test_forward_list_covers_the_serving_path(self):
        from kubeflow_tpu.core.headers import MODEL_HEADER

        assert set(FORWARD_HEADERS) == {
            DEADLINE_HEADER, QOS_HEADER, TRACE_HEADER,
            DECODE_BACKEND_HEADER, DECODE_ALTS_HEADER, MODEL_HEADER,
            HANDOFF_DTYPE_HEADER, HANDOFF_WIRE_HEADER}

    def test_chaos_proxy_forwards_the_whole_list(self):
        """The ChaosProxy's forward-list is DERIVED from core/headers —
        every serving-path header (trace included, which the old
        re-typed list dropped) rides through the middlebox."""
        import threading
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from kubeflow_tpu.serve.faults import ChaosProxy

        seen: dict = {}

        class Echo(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def do_POST(self):
                for h in FORWARD_HEADERS:
                    if self.headers.get(h):
                        seen[h] = self.headers[h]
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                data = b'{"ok": true}'
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), Echo)
        httpd.daemon_threads = True
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        proxy = ChaosProxy(f"http://127.0.0.1:{httpd.server_address[1]}")
        proxy.start()
        try:
            req = urllib.request.Request(
                proxy.url + "/x", data=b"{}",
                headers={"Content-Type": "application/json",
                         DEADLINE_HEADER: "1000",
                         QOS_HEADER: "interactive",
                         DECODE_BACKEND_HEADER: "http://127.0.0.1:1",
                         DECODE_ALTS_HEADER: "http://127.0.0.1:2",
                         HANDOFF_DTYPE_HEADER: "int8",
                         HANDOFF_WIRE_HEADER: "2",
                         "X-Kftpu-Model": "tenant-a",
                         TRACE_HEADER: "ab" * 16 + "-" + "cd" * 8})
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
        finally:
            proxy.stop()
            httpd.shutdown()
            httpd.server_close()
        assert set(seen) == set(FORWARD_HEADERS)


class TestRuntimeContractAuditor:
    def test_probe_scrape_records_consumed_series(self, server):
        """Under the auditor, a real probe scrape records exactly the
        statically-declared consumed set — the runtime half agreeing
        with the AST half."""
        sanitize.install_contract_auditor()
        try:
            sanitize.contract_auditor().reset()
            got = default_probe(server.url, timeout=5.0)
            assert got is not None
            report = sanitize.contract_report()
            consumed = set(report["series_consumed"])
            assert consumed
            assert consumed <= set(_PROBE_SERIES)
            # Rendering the scrape response also recorded the produced
            # side, and nothing runtime-observed is statically undeclared.
            assert set(report["series_produced"]) >= consumed
            doc = rules_contracts.contract_manifest(
                analysis_core.build_program(
                    [os.path.join(REPO, "kubeflow_tpu")], root=REPO))
            diff = sanitize.contract_diff(report, doc)
            assert diff["undeclared_series"] == []
            assert diff["undeclared_headers"] == []
        finally:
            sanitize.uninstall_contract_auditor()

    def test_auditor_off_is_free(self, server):
        sanitize.uninstall_contract_auditor()
        assert sanitize.contract_report() == {}
        got = default_probe(server.url, timeout=5.0)   # hooks are no-ops
        assert got is not None
