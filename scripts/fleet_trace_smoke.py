#!/usr/bin/env python
"""Fleet observability gate (scripts/smoke.sh): cross-host trace
stitching, metrics history + SLO burn rate, flight recorder (ISSUE 18).

A 3-replica disaggregated fleet (1 prefill + 2 decode) behind the
hardened router takes a loadgen scenario while one decode replica is
SIGKILLed mid-session. The gate then asserts the fleet plane saw the
whole story:

- **one stitched trace per request**: the collector drains every
  replica's ``/debug/spans/export`` plus the router's, joins by trace
  id, and a single causal tree covers router → prefill → KV handoff →
  decode with per-hop wire-time attribution, every hop's skew-corrected
  ordering monotone;
- **the SIGKILL failover is a first-class hop**: a handoff placed on
  the dead decode replica lands on the retry alternate and stitches as
  kind ``failover`` — attributed, timed, in the same tree;
- **burn rate**: the metrics-history scrape loop ran against the real
  ``/metrics`` expositions during the run; a seeded SLO breach (targets
  under the observed TTFT) raises the per-class alert series while a
  clean evaluation over the SAME history does not;
- **flight recorder**: stopping a replica's engine leaves a dump
  (history window + stitched traces + SLO state) that ``kftpu trace``
  re-loads;
- **hygiene**: ``open_spans() == 0`` after settling, zero leaked KV
  pages on every engine (including the killed one), and every
  ``kftpu_fleet_*``/``kftpu_obs_*`` series parsing off the rendered
  fleet registry (the consumer half of the X7xx contract).

Prints one JSON object; ``{"fleet_trace_smoke": "ok"}`` is the gate
line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ISSUE 20: the whole gate runs under the thread sanitizer — every
# threading.Thread created by app code is stamped with its creation
# site/owner, the component stop() paths assert per-owner quiescence,
# and step 10 proves the PROCESS ends quiescent even after the SIGKILL
# chaos. Set BEFORE any kubeflow_tpu import so maybe_install sees it.
_san = os.environ.get("KFTPU_SANITIZE", "")
if "threads" not in _san.split(","):
    os.environ["KFTPU_SANITIZE"] = ",".join(
        x for x in (_san, "threads") if x)

#: Fleet-plane series this gate consumes off the rendered fleet
#: registry — the consumer half of the kftpu_fleet_*/kftpu_obs_*
#: metric contract (X7xx).
FLEET_OBS_SERIES = (
    "kftpu_fleet_spans_total",
    "kftpu_fleet_spans_duplicate_total",
    "kftpu_fleet_drain_errors_total",
    "kftpu_fleet_traces_stitched",
    "kftpu_fleet_clock_skew_ms",
    "kftpu_fleet_hops_total",
    "kftpu_fleet_hop_wire_ms",
    "kftpu_obs_history_points",
    "kftpu_obs_history_scrapes_total",
    "kftpu_obs_history_scrape_errors_total",
    "kftpu_obs_slo_burn_rate",
    "kftpu_obs_slo_alert",
    "kftpu_obs_flight_dumps_total",
)

MAX_NEW = 8


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=4.0)
    args = ap.parse_args()

    import jax

    from kubeflow_tpu.core.headers import (
        DECODE_ALTS_HEADER, DECODE_BACKEND_HEADER,
    )
    from kubeflow_tpu.core.serving import QOS_DEFAULT, BatchingSpec
    from kubeflow_tpu.loadgen import ServerTarget, build_report, run_scenario
    from kubeflow_tpu.loadgen.scenario import (
        Arrival, LengthDist, Scenario,
    )
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs import fleet
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.obs.trace import format_dump, get_tracer, load_dump
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.faults import kill_model_server
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    result: dict = {}

    def fail(msg: str) -> int:
        result["fleet_trace_smoke"] = msg
        print(json.dumps(result, indent=2))
        return 1

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    tracer = get_tracer()
    tracer.reset()

    def mk(name: str, role: str) -> ModelServer:
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4,
                         role=role),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    pre = mk("pre", "prefill")
    dec1 = mk("dec1", "decode")
    dec2 = mk("dec2", "decode")
    servers = [pre, dec1, dec2]
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.5,
                    max_retries=2, upstream_timeout=30.0)
    router.set_pools({"prefill": [pre.url], "decode": [dec1.url, dec2.url]})
    router.start()

    # The fleet plane: collector sources (router FIRST so shared-ring
    # root spans attribute to it), history scrape loop over every
    # replica's real /metrics, flight recorder installed module-wide so
    # engine stops snapshot on their own.
    collector = fleet.FleetTraceCollector()
    collector.add_source("router",
                         router.url + fleet.ROUTER_SPANS_EXPORT_PATH)
    for srv in servers:
        collector.add_source(f"server:{srv.name}",
                             srv.url + fleet.SPANS_EXPORT_PATH)
    history = fleet.MetricsHistory(retention_s=120.0, interval_s=0.25)
    for srv in servers:
        history.add_target(srv.name, srv.url + "/metrics")
    history.start()
    flight_dir = tempfile.mkdtemp(prefix="fleet-flight-")
    recorder = fleet.FlightRecorder(flight_dir, window_s=120.0,
                                    history=history, collector=collector)
    prev_recorder = fleet.install_flight_recorder(recorder)

    def completion(url, prompt, headers=()):
        body = json.dumps({"prompt": prompt, "max_tokens": MAX_NEW,
                           "timeout": 20}).encode()
        req = urllib.request.Request(
            url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json", **dict(headers)})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["choices"][0]["text"]

    try:
        # Warm the disaggregated path (compiles stay out of the run).
        completion(router.url, "fleet observability warmup")

        # 1) Loadgen scenario through the router, SIGKILL dec1 when a
        #    third of the schedule has elapsed.
        sc = Scenario(
            name="fleet_uniform", num_requests=args.requests,
            arrival=Arrival(process="poisson", rate_rps=args.rate),
            prompt_len=LengthDist(kind="fixed", value=24),
            output_len=LengthDist(kind="fixed", value=MAX_NEW),
            slo_ttft_ms=5000.0, request_timeout_s=30.0)
        kill_delay = (args.requests / args.rate) / 3.0
        killer = threading.Timer(kill_delay,
                                 lambda: kill_model_server(dec1))
        killer.start()
        run = run_scenario(ServerTarget(router.url), sc,
                           vocab_size=cfg.vocab_size, max_prompt_len=30,
                           tracer=tracer)
        killer.join(timeout=10.0)
        ok_outs = [o for o in run.outcomes if o.ok]
        result["requests"] = {"offered": len(run.outcomes),
                              "completed": len(ok_outs)}
        if not ok_outs:
            return fail("no request survived the fleet run")

        # 2) Deterministic failover seeding: a handoff PLACED on the
        #    dead decode replica with the survivor as alternate — the
        #    exact pick-then-die race, minus the race.
        completion(pre.url, "failover seed", headers=[
            (DECODE_BACKEND_HEADER, dec1.url),
            (DECODE_ALTS_HEADER, dec2.url)])

        # 3) Drain + stitch. dec1 is dead: its drain must fail and be
        #    counted, never fatal (the missing-source tolerance).
        collector.drain()
        if collector.stats["drain_errors"] < 1:
            return fail("dead replica's drain did not error")
        for name, st in collector.sources().items():
            if name != "server:dec1" and st["errors"]:
                return fail(f"live source {name} failed to drain: {st}")
            if abs(st["offset_s"]) > 1.0:
                return fail(f"implausible clock offset for {name}: {st}")

        # 4) ONE stitched trace covers router → prefill → handoff →
        #    decode; every hop attributed and monotone.
        full = None
        for out in ok_outs:
            tr = collector.trace(out.trace_id) if out.trace_id else None
            if not tr:
                continue
            kinds = {h["kind"] for h in tr["hops"]}
            if ("route" in kinds or "failover" in kinds) and \
                    ("handoff" in kinds or "failover" in kinds) and \
                    len(tr["hops"]) >= 2:
                full = tr
                break
        if full is None:
            return fail("no stitched trace covers route + handoff")
        procs = {h["from"] for h in full["hops"]} \
            | {h["to"] for h in full["hops"]}
        if "router" not in procs or "server:pre" not in procs:
            return fail(f"hop attribution incomplete: {sorted(procs)}")
        if not procs & {"server:dec1", "server:dec2"}:
            return fail(f"no decode replica in the tree: {sorted(procs)}")
        if any("?" in (h["from"], h["to"]) for h in full["hops"]):
            return fail(f"unattributed hop endpoints: {full['hops']}")
        bad = [h for h in collector.hops() if not h["monotone"]]
        if bad:
            return fail(f"non-monotone hops after skew correction: {bad}")
        if any(h["wire_ms"] is None for h in collector.hops()):
            return fail("hop without wire-time attribution")
        result["stitched"] = {
            "trace_id": full["trace_id"],
            "hops": [{k: h[k] for k in ("kind", "from", "to", "wire_ms")}
                     for h in full["hops"]]}

        # 5) The SIGKILL failover hop: placed on the dead replica,
        #    landed on the survivor, stitched as kind "failover" in ONE
        #    tree together with its route + handoff context.
        failover_traces = [t for t in collector.traces(limit=256)
                           if any(h["kind"] == "failover"
                                  for h in t["hops"])]
        if not failover_traces:
            return fail("SIGKILL failover never stitched as a hop")
        ft = failover_traces[0]
        fh = [h for h in ft["hops"] if h["kind"] == "failover"]
        if not any(h["to"] == "server:dec2" for h in fh):
            return fail(f"failover hop missed the survivor: {fh}")
        if not all(h["monotone"] and h["wire_ms"] is not None for h in fh):
            return fail(f"failover hop unattributed: {fh}")
        result["failover"] = {"trace_id": ft["trace_id"],
                              "hops": len(fh),
                              "wire_ms": fh[0]["wire_ms"]}
        # The stitched tree renders (the kftpu trace view).
        if "engine.handoff" not in collector.format_tree(ft["trace_id"]):
            return fail("stitched tree render lost the handoff span")

        # 6) Burn rate over the run's REAL scraped history: a seeded
        #    breach (target far under the observed TTFT) alerts; a clean
        #    evaluation over the same rings does not.
        history.stop()
        if history.points_total() <= 0:
            return fail("metrics history scraped no points")
        result["history_points"] = history.points_total()
        breach = fleet.SloBurnRateMonitor(
            history, {QOS_DEFAULT: {"ttft_p95_ms": 1e-3}},
            fast_window_s=30.0, slow_window_s=120.0)
        clean = fleet.SloBurnRateMonitor(
            history, {QOS_DEFAULT: {"ttft_p95_ms": 1e9}},
            fast_window_s=30.0, slow_window_s=120.0)
        if breach.evaluate() != breach.state():
            return fail("monitor state diverged from evaluation")
        if breach.alerting() != [QOS_DEFAULT]:
            return fail(f"seeded SLO breach did not alert: "
                        f"{breach.state()}")
        if clean.evaluate()[QOS_DEFAULT]["alert"]:
            return fail(f"clean run raised a burn-rate alert: "
                        f"{clean.state()}")
        reg = fleet.fleet_obs_registry(collector=collector,
                                       history=history, monitor=breach,
                                       recorder=recorder)
        samples = parse_exposition(reg.render())
        by_name = {n for n, _, _ in samples}
        missing = [s for s in FLEET_OBS_SERIES if s not in by_name]
        if missing:
            return fail(f"fleet series missing from exposition: {missing}")
        alerts = {lab.get("class"): v for n, lab, v in samples
                  if n == "kftpu_obs_slo_alert"}
        if alerts.get(QOS_DEFAULT) != 1.0:
            return fail(f"alert series not raised: {alerts}")
        result["burn_rate"] = {
            cls: round(st["fast"], 2)
            for cls, st in breach.state().items() if st["fast"]}

        # 7) Loadgen attribution report with the fleet-hop block.
        rep = build_report(run, tracer=tracer, collector=collector)
        hops_rep = rep.get("fleet_hops") or {}
        if hops_rep.get("trace_coverage", 0) < 1:
            return fail(f"report joined no fleet hops: {hops_rep}")
        if hops_rep.get("non_monotone_hops"):
            return fail(f"report saw non-monotone hops: {hops_rep}")
        result["fleet_hops"] = hops_rep

        # 8) Flight recorder: stopping a replica's engine snapshots on
        #    its own (the installed-recorder hook); the dump re-loads
        #    through the kftpu trace loader.
        pre.stop()
        if not recorder.dumps():
            return fail("engine stop left no flight-recorder dump")
        doc = load_dump(recorder.dumps()[-1])
        rendered = format_dump(doc)
        if not rendered.startswith("flight recorder:"):
            return fail("dump lost its flight-recorder header")
        if "router.request" not in rendered:
            return fail("dump lost the stitched traces")
        if not doc.get("flight_recorder", {}).get("history"):
            return fail("dump lost the metrics-history window")
        result["flight_dump"] = os.path.basename(recorder.dumps()[-1])

        # 9) Hygiene: zero open spans, zero leaked KV pages everywhere
        #    (the kill strands in-flight work; cancel and reap it).
        for srv in servers:
            eng = srv.engine
            for s in eng.slots:
                if s is not None:
                    s.request.cancel()
            for req in list(eng._backlog) + list(eng._preempted):
                req.cancel()
            for ch in list(eng._chunkings):
                ch.request.cancel()
            deadline = time.monotonic() + 20.0
            while eng.kv_pages_in_use() > 0 and \
                    time.monotonic() < deadline:
                eng.step()
            if eng.kv_pages_in_use() != 0:
                return fail(f"{srv.name}: leaked KV pages")
        deadline = time.monotonic() + 5.0
        while tracer.open_spans() and time.monotonic() < deadline:
            time.sleep(0.02)
        if tracer.open_spans():
            return fail(f"{tracer.open_spans()} leaked open spans")
        result["hygiene"] = "ok"

        # 10) Liveness (ISSUE 20): orderly stop of every component —
        #     the stop() paths each assert their own threads quiescent
        #     under KFTPU_SANITIZE=threads — then the fleet-wide
        #     backstop: no stamped thread anywhere survives the stops,
        #     including anything the SIGKILL chaos stranded. The finally
        #     block's stops become no-ops (every path is idempotent).
        from kubeflow_tpu.runtime import sanitize

        if sanitize.thread_sanitizer() is None:
            return fail("thread sanitizer not installed — "
                        "KFTPU_SANITIZE=threads did not take")
        router.stop()
        for srv in servers:
            srv.stop()
        sanitize.assert_threads_quiescent(grace_s=10.0)
        leaked = sanitize.thread_leak_report_by_owner()
        if leaked:
            return fail(f"threads survived orderly stop: {leaked}")
        result["thread_sanitizer"] = {"mode": "threads", "leaked": 0}

        result["fleet_trace_smoke"] = "ok"
        print(json.dumps(result, indent=2))
        return 0
    finally:
        fleet.install_flight_recorder(prev_recorder)
        history.stop()
        router.stop()
        for srv in servers:
            try:
                srv.stop()
            except OSError:
                pass


if __name__ == "__main__":
    sys.exit(main())
