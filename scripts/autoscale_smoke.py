#!/usr/bin/env python
"""Autoscaling/QoS smoke stage for scripts/smoke.sh (ISSUE 6): a tiny CPU
run that closes the loop end to end —

1. a 2-class burst (interactive + batch, ``X-Kftpu-Qos`` headers) through
   a real router + model server must shed in priority order: batch takes
   every 429/shed, interactive is never shed and all-200s;
2. the SLO autoscaler, scraping the REAL replica's /metrics through
   ``default_probe``, must make exactly one scale-up decision off the
   burst's latency signals (and hold, not flap, while the fleet is
   partial);
3. scale-down must retire through the graceful drain path: a busy
   trimmed replica survives (Draining event) until idle, then tears down;
4. the new QoS/router metric names must pass ``kftpu lint``'s M2xx
   definition-site rules and render on /metrics under the exposition
   grammar with the ``kftpu_`` prefix.

Prints one JSON object; ``"autoscale_smoke": "ok"`` is the pass marker
smoke.sh greps for.

    JAX_PLATFORMS=cpu python scripts/autoscale_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: Files whose metric definition sites this PR added/changed — the M2xx
#: lint surface for the new series names.
METRIC_FILES = [
    "kubeflow_tpu/serve/server.py",
    "kubeflow_tpu/serve/router.py",
    "kubeflow_tpu/serve/isvc_controller.py",
]

#: Series the QoS/autoscaling loop introduces; all must render.
NEW_SERIES = [
    "kftpu_serving_qos_requests_total",
    "kftpu_serving_qos_requests_shed_total",
    "kftpu_serving_qos_preemptions_total",
    "kftpu_serving_qos_ttft_p95_ms",
    "kftpu_serving_qos_queue_delay_seconds_bucket",
    "kftpu_serving_ttft_p95_ms",
    "kftpu_serving_preemptions_total",
    "kftpu_router_panic_total",
    "kftpu_router_probe_total",
]


def completion(url: str, qos: str, timeout_s: float = 10.0) -> int:
    from kubeflow_tpu.serve.router import DEADLINE_HEADER, QOS_HEADER

    body = json.dumps({"prompt": "smoke", "max_tokens": 6,
                       "timeout": timeout_s}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json", QOS_HEADER: qos,
                 DEADLINE_HEADER: str(int(timeout_s * 1e3))})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
            return r.status
    except urllib.error.HTTPError as exc:
        exc.read()
        return exc.code
    except OSError:
        return 502


def fire(url: str, qos: str, n: int, concurrency: int,
         out: list[int]) -> None:
    lock = threading.Lock()
    it = iter(range(n))

    def client():
        while True:
            with lock:
                nxt = next(it, None)
            if nxt is None:
                return
            status = completion(url, qos)
            with lock:
                out.append(status)

    threads = [threading.Thread(target=client) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
        assert not t.is_alive(), "client thread hung"


def main() -> int:
    problems: list[str] = []
    report: dict = {}

    # -- stage 4 first (pure static): M2xx lint over the metric files ------
    from kubeflow_tpu.analysis.core import lint_source

    m2xx = []
    for rel in METRIC_FILES:
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        m2xx += [f.render() for f in lint_source(src, rel)
                 if f.rule.startswith("M2")]
    report["m2xx_findings"] = m2xx
    if m2xx:
        problems.append(f"M2xx lint findings in metric files: {m2xx}")

    import jax  # noqa: F401  (force backend selection before engines)

    from kubeflow_tpu.core.serving import (
        BatchingSpec, QoSClassPolicy, QoSSpec,
    )
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    eng = LLMEngine(
        cfg,
        BatchingSpec(max_batch_size=2, max_seq_len=96, paged=True,
                     page_size=16, chunked_prefill_tokens=16,
                     decode_steps=4, max_queue=4,
                     qos=QoSSpec(classes={
                         "batch": QoSClassPolicy(max_queue=1),
                         "interactive": QoSClassPolicy(
                             queue_delay_budget=5.0)})),
        params=params)
    server = ModelServer("smoke-svc", eng, port=0)
    server.start()
    router = Router(queue_timeout=5.0)
    router.set_backends({"latest": [server.url]})
    router.start()

    try:
        # -- stage 1: 2-class burst, shed ordering ------------------------
        got: dict[str, list[int]] = {"interactive": [], "batch": []}
        pools = [threading.Thread(
            target=fire, args=(router.url, cls, 8, 3, got[cls]))
            for cls in got]
        for t in pools:
            t.start()
        for t in pools:
            t.join(timeout=120.0)
        snap = eng.metrics.snapshot()
        shed = {c: snap.get("qos", {}).get(c, {}).get("shed", 0)
                for c in ("interactive", "batch")}
        report["statuses"] = {c: sorted(set(v)) for c, v in got.items()}
        report["shed"] = shed
        if shed["interactive"] != 0 or any(
                s != 200 for s in got["interactive"]):
            problems.append(f"interactive degraded: shed={shed}, "
                            f"statuses={report['statuses']}")
        if 429 in got["batch"] and shed["batch"] == 0:
            problems.append("batch 429s with no batch shed counter")

        # -- stage 4b: the live exposition renders + lints ----------------
        text = server.metrics_text()
        names = {name for name, _, _ in parse_exposition(text)}
        router_text = urllib.request.urlopen(
            router.url + "/-/router/metrics", timeout=5).read().decode()
        names |= {name for name, _, _ in parse_exposition(router_text)}
        missing = [s for s in NEW_SERIES if s not in names]
        report["missing_series"] = missing
        if missing:
            problems.append(f"series missing from /metrics: {missing}")
        reg = server.metrics_registry()
        lint = reg.lint()
        if lint:
            problems.append(f"registry lint: {lint}")

        # -- stage 2: SLO autoscaler scrapes the REAL replica -------------
        from kubeflow_tpu.core.jobs import Worker, WorkerPhase
        from kubeflow_tpu.core.object import ObjectMeta
        from kubeflow_tpu.core.serving import (
            InferenceService, InferenceServiceSpec, ModelSpec,
            PredictorSpec, SLOPolicy,
        )
        from kubeflow_tpu.operator.control_plane import (
            ControlPlane, ControlPlaneConfig,
        )
        from kubeflow_tpu.serve.isvc_controller import default_probe

        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            cp = ControlPlane(ControlPlaneConfig(
                base_dir=tmp, launch_processes=False,
                metrics_sync_interval=None))
            # Every replica probe scrapes the REAL loaded server: the
            # signal path under test is engine → /metrics → parse →
            # decision. The burst above left ttft/queue-delay p95s far
            # over the (deliberately microscopic) targets.
            cp.isvc_reconciler.probe = lambda url: default_probe(server.url)
            cp.submit(InferenceService(
                metadata=ObjectMeta(name="svc"),
                spec=InferenceServiceSpec(predictor=PredictorSpec(
                    model=ModelSpec(config={"preset": "tiny"}),
                    min_replicas=1, max_replicas=2,
                    slo=SLOPolicy(target_ttft_ms=0.01,
                                  target_queue_delay_ms=0.01,
                                  cooldown_s=0.2)))))
            key = "default/svc"
            recon = lambda: cp.isvc_reconciler.reconcile(key)  # noqa: E731

            def mark_running():
                for w in cp.store.list(Worker):
                    if w.status.phase != WorkerPhase.RUNNING:
                        w.status.phase = WorkerPhase.RUNNING
                        cp.store.update_status(w)

            recon()                   # create replica 1
            mark_running()
            recon()                   # ready; first sight starts the clock
            time.sleep(0.25)          # cooldown elapses
            recon()                   # hot signals → ONE scale-up decision
            isvc = cp.store.get(InferenceService, "svc")
            report["desired_after_burst"] = isvc.status.desired_replicas
            if isvc.status.desired_replicas != 2:
                problems.append(
                    f"no scale-up decision off the burst signals "
                    f"(desired={isvc.status.desired_replicas})")
            # Partial fleet (replica 2 created but not ready): hold.
            time.sleep(0.25)
            recon()
            isvc = cp.store.get(InferenceService, "svc")
            if isvc.status.desired_replicas != 2:
                problems.append("autoscaler flapped while fleet partial")

            # -- stage 3: scale-down completes drain before teardown ------
            mark_running()            # replica 2 comes up
            probe_state = {"in_flight": 1}

            def idle_probe(url):
                return {"ready": True, "in_flight": probe_state["in_flight"],
                        "requests_total": 0, "ttft_p95_ms": 0.001,
                        "queue_delay_p95_ms": 0.001,
                        "qos_ttft_p95_ms": {}, "qos_queue_delay_p95_ms": {}}

            cp.isvc_reconciler.probe = idle_probe
            time.sleep(0.25)
            recon()
            isvc = cp.store.get(InferenceService, "svc")
            if isvc.status.desired_replicas != 1:
                problems.append(
                    f"no scale-down on idle signals "
                    f"(desired={isvc.status.desired_replicas})")
            recon()       # trim pass: replica 1 enters draining (busy)
            n_workers = len(cp.store.list(Worker))
            if n_workers != 2:
                problems.append(
                    f"busy replica deleted before drain ({n_workers})")
            events = [e.reason for e in cp.recorder.for_object(isvc)]
            if "Draining" not in events:
                problems.append(f"no Draining event (events={events})")
            probe_state["in_flight"] = 0       # in-flight work finished
            recon()
            n_workers = len(cp.store.list(Worker))
            if n_workers != 1:
                problems.append(
                    f"drained replica not torn down ({n_workers})")
            report["events"] = events
            cp.isvc_reconciler.shutdown()
    finally:
        router.stop()
        server.stop()

    report["autoscale_smoke"] = "ok" if not problems else "FAIL"
    report["problems"] = problems
    print(json.dumps(report, indent=2))
    return 0 if not problems else 1


if __name__ == "__main__":
    sys.exit(main())
