#!/usr/bin/env python
"""Name-contract audit stage for scripts/smoke.sh (ISSUE 10).

Cross-checks the STATIC contract table (``kftpu lint --contracts-json``:
metric series produced/consumed, ``X-Kftpu-*`` headers set/read — the
X7xx rules' extraction) against what a real serve run ACTUALLY
exchanges, recorded by the ``KFTPU_SANITIZE=contract`` runtime auditor:

1. The manifest round-trips: the ``--contracts-json`` CLI output parses
   and equals the in-process extraction over the same scan set.
2. Traffic runs through a real router → model-server → engine stack with
   QoS + deadline headers, the autoscaler's ``default_probe`` scrapes a
   replica, and the router's own /metrics is scraped — covering every
   exchange class the serving path has.
3. ``contract_report()`` must show ZERO undeclared exchanges against the
   static table (``contract_diff``): every series actually rendered or
   matched, and every header actually read or stamped, was visible to
   the AST extractor. A dynamically-built name the static table missed
   fails here — the gap the runtime half exists to close.

Prints one JSON line; exit 0 iff ``"contract_smoke": "ok"``.

    JAX_PLATFORMS=cpu python scripts/contract_smoke.py [--requests 6]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The auditor must be live before kubeflow_tpu (and its locks/engines)
# import — same contract as the other sanitizer modes.
os.environ["KFTPU_SANITIZE"] = "contract"

SCAN = ["kubeflow_tpu", "scripts", "bench.py", "bench_serve.py"]


def static_manifest() -> tuple[dict, list[str]]:
    """The contract table, via the CLI (proving the --contracts-json
    surface) AND in-process (proving the round-trip)."""
    problems: list[str] = []
    proc = subprocess.run(
        [sys.executable, "-m", "kubeflow_tpu.analysis",
         "--contracts-json", *SCAN],
        capture_output=True, text=True, cwd=REPO, timeout=600)
    if proc.returncode != 0:
        return {}, [f"--contracts-json failed: {proc.stderr.strip()}"]
    try:
        cli_doc = json.loads(proc.stdout)
    except ValueError as exc:
        return {}, [f"--contracts-json output is not JSON: {exc}"]

    from kubeflow_tpu.analysis import build_program
    from kubeflow_tpu.analysis.rules_contracts import contract_manifest

    local_doc = json.loads(json.dumps(
        contract_manifest(build_program(
            [os.path.join(REPO, p) for p in SCAN], root=REPO))))
    if cli_doc != local_doc:
        problems.append("--contracts-json does not round-trip: CLI and "
                        "in-process manifests differ")
    return cli_doc, problems


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()
    os.chdir(REPO)

    verdict: dict = {"contract_smoke": "ok"}
    doc, problems = static_manifest()
    verdict["static_series_produced"] = len(
        doc.get("series", {}).get("produced", {}))
    if problems:
        verdict.update(contract_smoke="FAIL", problems=problems)
        print(json.dumps(verdict))
        return 1

    import jax

    from kubeflow_tpu.core.headers import (
        DEADLINE_HEADER, QOS_HEADER, TRACE_HEADER,
    )
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.runtime import sanitize
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.isvc_controller import default_probe
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    if sanitize.contract_auditor() is None:
        problems.append("contract auditor not installed at import")

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=96, paged=True,
                     page_size=16, decode_steps=4),
        params=params)
    server = ModelServer("contract-smoke", engine, port=0)
    server.start()
    router = Router(queue_timeout=5.0, upstream_timeout=60.0)
    router.set_backends({"latest": [server.url]})
    router.start()

    def one_request(i: int) -> None:
        body = json.dumps({"prompt": f"contract {i}", "max_tokens": 8,
                           "timeout": 30}).encode()
        req = urllib.request.Request(
            router.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json",
                     QOS_HEADER: "interactive" if i % 2 else "batch",
                     DEADLINE_HEADER: "30000"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()
        except Exception as exc:  # noqa: BLE001 — counted, not fatal
            problems.append(f"request {i}: {exc}")

    try:
        threads = [threading.Thread(target=one_request, args=(i,))
                   for i in range(args.requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)

        # The autoscaler's scrape (records CONSUMED series) and the
        # router's own exposition surface (dynamic kftpu_router_* family).
        probe = default_probe(server.url, timeout=5.0)
        if probe is None or not probe.get("ready"):
            problems.append("default_probe found the replica not ready")
        with urllib.request.urlopen(
                router.url + "/-/router/metrics", timeout=10) as r:
            parse_exposition(r.read().decode())

        report = sanitize.contract_report()
        verdict["series_produced"] = len(report.get("series_produced", ()))
        verdict["series_consumed"] = len(report.get("series_consumed", ()))
        verdict["headers_set"] = report.get("headers_set", [])
        verdict["headers_read"] = report.get("headers_read", [])
        if not report.get("series_produced"):
            problems.append("auditor recorded no produced series")
        if not report.get("series_consumed"):
            problems.append("auditor recorded no consumed series "
                            "(default_probe matched nothing)")
        for h in (DEADLINE_HEADER, QOS_HEADER, TRACE_HEADER):
            if h not in report.get("headers_set", ()):
                problems.append(f"auditor never saw header {h} set")
        diff = sanitize.contract_diff(report, doc)
        verdict["undeclared_series"] = diff["undeclared_series"]
        verdict["undeclared_headers"] = diff["undeclared_headers"]
        if diff["undeclared_series"] or diff["undeclared_headers"]:
            problems.append(
                "runtime exchanged names the static contract table does "
                f"not declare: {diff}")
    finally:
        router.stop()
        server.stop()

    if problems:
        verdict["contract_smoke"] = "FAIL"
        verdict["problems"] = problems
    print(json.dumps(verdict))
    return 0 if not problems else 1


if __name__ == "__main__":
    raise SystemExit(main())
