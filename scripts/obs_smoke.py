"""Observability smoke stage for scripts/smoke.sh: fire traffic through a
real router → model-server → engine stack, then assert the observability
contract end to end:

- every /metrics endpoint (model server AND router) parses under the strict
  exposition grammar (obs/registry.parse_exposition);
- every exposed series name carries the platform ``kftpu_`` prefix (the
  metric-name lint);
- /debug/traces returns a well-formed trace: one trace id spanning
  router.request → server.request → engine.{queued,prefill,decode}, and the
  Chrome export is valid JSON with complete events;
- the tracer is quiescent after traffic (zero open spans — no leaked spans
  from any request path).

Prints one JSON line with the verdict; exit code 0 iff "obs_smoke": "ok".

    JAX_PLATFORMS=cpu python scripts/obs_smoke.py [--requests 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--concurrency", type=int, default=4)
    args = ap.parse_args()

    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import NAME_PREFIX, parse_exposition
    from kubeflow_tpu.obs.trace import get_tracer
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=96, paged=True,
                     page_size=16, chunked_prefill_tokens=16,
                     decode_steps=4),
        params=params)
    server = ModelServer("obs-smoke", engine, port=0)
    server.start()
    router = Router(queue_timeout=5.0, upstream_timeout=60.0)
    router.set_backends({"latest": [server.url]})
    router.start()

    verdict: dict = {"obs_smoke": "ok"}
    problems: list[str] = []

    def one_request(i: int) -> None:
        body = json.dumps({"prompt": f"smoke {i}", "max_tokens": 8,
                           "timeout": 30}).encode()
        req = urllib.request.Request(
            router.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()
        except Exception as exc:  # noqa: BLE001 — counted, not fatal
            problems.append(f"request {i}: {exc}")

    try:
        threads = [threading.Thread(target=one_request, args=(i,))
                   for i in range(args.requests)]
        for batch in range(0, len(threads), args.concurrency):
            chunk = threads[batch:batch + args.concurrency]
            for t in chunk:
                t.start()
            for t in chunk:
                t.join(timeout=90)

        # -- /metrics grammar + name lint, both endpoints ---------------------
        scrapes = {
            "server": server.url + "/metrics",
            "router": router.url + "/-/router/metrics",
        }
        series = 0
        for which, url in scrapes.items():
            with urllib.request.urlopen(url, timeout=10) as r:
                text = r.read().decode()
            try:
                samples = parse_exposition(text)
            except ValueError as exc:
                problems.append(f"{which} /metrics: {exc}")
                continue
            series += len(samples)
            for name, _, _ in samples:
                base = name
                for suffix in ("_bucket", "_sum", "_count"):
                    if base.endswith(suffix):
                        base = base[:-len(suffix)]
                        break
                if not base.startswith(NAME_PREFIX):
                    problems.append(
                        f"{which}: series {name} missing {NAME_PREFIX}")
        verdict["series"] = series

        # -- /debug/traces shape ----------------------------------------------
        # The client can observe response bytes a beat before the router
        # handler's span closes — give the handler threads a moment to
        # quiesce before asserting on trace shape and open-span count.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and (
                get_tracer().open_spans() != 0):
            time.sleep(0.02)
        with urllib.request.urlopen(server.url + "/debug/traces",
                                    timeout=10) as r:
            doc = json.loads(r.read())
        traces = doc.get("traces", [])
        verdict["traces"] = len(traces)
        full = None
        for t in traces:
            names = {s["name"] for s in t["spans"]}
            if {"router.request", "server.request", "engine.queued",
                    "engine.prefill", "engine.decode"} <= names:
                full = t
                break
        if full is None:
            problems.append("no trace spans router→server→engine")
        else:
            ids = {s["trace_id"] for s in full["spans"]}
            if len(ids) != 1:
                problems.append(f"trace id not unified: {ids}")
            if any(s["end"] is None for s in full["spans"]):
                problems.append("trace contains unclosed spans")

        with urllib.request.urlopen(
                server.url + "/debug/traces?chrome=1", timeout=10) as r:
            chrome = json.loads(r.read())
        evs = chrome.get("traceEvents", [])
        if not evs:
            problems.append("chrome export is empty")
        # Complete ("X") events carry a duration; instant ("i") events —
        # span events such as per-round decode_round markers — carry a
        # scope instead (Chrome trace-event format).
        for e in evs:
            need = {"name", "ph", "ts", "pid", "tid"}
            need |= {"dur"} if e.get("ph") == "X" else {"s"}
            if not need <= set(e):
                problems.append("chrome export has malformed events")
                break

        open_spans = get_tracer().open_spans()
        verdict["open_spans"] = open_spans
        if open_spans != 0:
            problems.append(f"{open_spans} spans still open after traffic")
    finally:
        router.stop()
        server.stop()

    if problems:
        verdict["obs_smoke"] = "FAIL"
        verdict["problems"] = problems
    print(json.dumps(verdict))
    return 0 if not problems else 1


if __name__ == "__main__":
    raise SystemExit(main())
