#!/usr/bin/env python
"""Fleet-wide KV fabric gate (scripts/smoke.sh): cross-host handoff,
remote-tier conversation failover, and steady-state compile stability
(ISSUE 17).

What must hold, on small paged CPU engines:

- **handoff identity**: completions through a real prefill server →
  HTTP handoff → decode server are byte-identical to the unified
  single-engine reference, with the exported/adopted counters moving
  and ZERO fallbacks;
- **failover-resume beats cold recompute**: conversations generated on
  replica A and drained to the artifact store (the scale-down/SIGKILL
  survival path) resume on replica B — which shares only the store
  root, never a live connection — token-identical to a cold engine AND
  with better TTFT p95 than recomputing the whole history (the third
  tier's whole case: a promote must be cheaper than the prefill it
  replaces);
- **zero steady-state recompiles**: with KFTPU_SANITIZE=refcount,
  recompile on for the whole stage, a post-warm remote-tier resume and
  a post-warm handoff round trip compile NOTHING;
- **hygiene**: the new fabric series parse off the real exposition
  (the consumer half of the X7xx metric contract), per-owner refcount
  books balance to zero on every engine.

Writes ``BENCH_SERVE_r06.json`` (the fleet-KV bench round); prints one
JSON object; ``{"fleet_kv_smoke": "ok"}`` is the gate line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Refcount (per-owner page books) + recompile (steady-state watchdog)
# for the whole stage.
os.environ["KFTPU_SANITIZE"] = "refcount,recompile"

#: Fabric series this gate consumes off the engine exposition — the
#: consumer half of the kftpu_engine_kv_remote_*/handoff contract.
FLEET_SERIES = (
    "kftpu_engine_kv_pages_remote",
    "kftpu_engine_kv_remote_demoted_bytes_total",
    "kftpu_engine_kv_remote_promoted_bytes_total",
    "kftpu_engine_kv_remote_promote_timeouts_total",
    "kftpu_engine_kv_remote_blobs_corrupt_total",
    "kftpu_engine_kv_tier_pressure",
    "kftpu_engine_handoffs_retried_total",
    "kftpu_engine_handoffs_fallback_total",
)

TURN1_LEN = 160
MAX_NEW = 8
CONVS = 6          # conversation 0 is held back for the post-warm resume


def turn1_tokens(i: int) -> list:
    return [(i * 31 + j * 7) % 500 + 1 for j in range(TURN1_LEN)]


def wait(req, timeout=60.0):
    assert req.done.wait(timeout), "request never finished"
    return req


def p95(xs: list) -> float:
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(round(0.95 * (len(ys) - 1))))]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.parse_args()

    import jax

    from kubeflow_tpu.core.headers import DECODE_BACKEND_HEADER
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.runtime.sanitize import (
        mark_compile_warm, recompile_report, recompile_watchdog,
    )
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
    from kubeflow_tpu.serve.server import (
        ModelServer, serving_metrics_registry,
    )

    result: dict = {}

    def fail(msg: str) -> int:
        result["fleet_kv_smoke"] = msg
        print(json.dumps(result, indent=2))
        return 1

    wd = recompile_watchdog()
    if wd is None:
        return fail("recompile watchdog not installed")

    # A notch above "tiny": resumed-vs-recomputed TTFT is an avoided-
    # prefill-compute claim, so prefill must cost real wall time.
    cfg = preset("tiny", vocab_size=512, max_seq_len=256, hidden=128,
                 n_layers=4, mlp_dim=256)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    tiny = preset("tiny", vocab_size=512)
    tiny_params = init_decoder_params(jax.random.PRNGKey(0), tiny)

    tmp = tempfile.mkdtemp(prefix="fleet-kv-")
    cold_root = tempfile.mkdtemp(prefix="fleet-kv-cold-")

    def fabric_spec(root):
        # Long idle timer: demotion happens only through the FORCED
        # drain (pre-warm), so no background demote batch can introduce
        # a fresh gather shape after mark_compile_warm().
        return BatchingSpec(
            max_batch_size=4, max_seq_len=256, paged=True, page_size=16,
            chunked_prefill_tokens=32, decode_steps=4,
            prefix_index="radix", host_kv_pages=256,
            kv_demote_after_s=60.0, remote_kv_root=root)

    sp = SamplingParams(max_new_tokens=MAX_NEW, temperature=0.0)
    sp1 = SamplingParams(max_new_tokens=1, temperature=0.0)
    engines: list = []
    servers: list = []

    def mk_engine(spec_, c=cfg, p=None):
        eng = LLMEngine(c, spec_, params=(p if p is not None else params))
        eng.start()
        engines.append(eng)
        return eng

    def completion(url, prompt, headers=()):
        body = json.dumps({"prompt": prompt, "max_tokens": 8,
                           "timeout": 30}).encode()
        req = urllib.request.Request(
            url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json", **dict(headers)})
        with urllib.request.urlopen(req, timeout=40) as r:
            return json.loads(r.read())["choices"][0]["text"]

    try:
        # 1) Cross-host handoff identity over real HTTP: prefill server
        #    → v2 wire → decode server vs the unified reference.
        def srv_spec(role):
            return BatchingSpec(max_batch_size=2, max_seq_len=96,
                                paged=True, page_size=16,
                                chunked_prefill_tokens=16, decode_steps=4,
                                role=role)

        pre = ModelServer("pre", LLMEngine(tiny, srv_spec("prefill"),
                                           params=tiny_params), port=0)
        dec = ModelServer("dec", LLMEngine(tiny, srv_spec("decode"),
                                           params=tiny_params), port=0)
        uni = ModelServer("uni", LLMEngine(tiny, srv_spec("unified"),
                                           params=tiny_params), port=0)
        for s in (pre, dec, uni):
            s.start()
            servers.append(s)
        prompts = ["fleet kv fabric handoff %d" % i for i in range(4)]
        hdr = [(DECODE_BACKEND_HEADER, dec.url)]
        for p in prompts:
            got = completion(pre.url, p, headers=hdr)
            want = completion(uni.url, p)
            if got != want:
                return fail(f"handoff output diverged on {p!r}: "
                            f"{got!r} != {want!r}")
        pre_snap = pre.engine.metrics.snapshot()
        if pre_snap["handoffs_exported"] < len(prompts):
            return fail(f"handoffs not exported: {pre_snap}")
        if pre_snap["handoffs_fallback"] != 0:
            return fail(f"unexpected handoff fallbacks: {pre_snap}")
        if dec.engine.metrics.snapshot()["handoffs_adopted"] < len(prompts):
            return fail("decode side adopted fewer handoffs than sent")
        result["handoff_identity"] = "ok"

        # 2) Failover-resume: conversations born on A, drained to the
        #    store (the replica-leaves-the-fleet path), resumed on B.
        a = mk_engine(fabric_spec(tmp))
        turns1 = {}
        for i in range(CONVS):
            turns1[i] = wait(a.submit(turn1_tokens(i), sp))
        drained = a.drain_kv_to_remote()
        if drained <= 0:
            return fail("drain_kv_to_remote published no pages")
        result["pages_drained"] = drained
        a.stop()
        engines.remove(a)

        b = mk_engine(fabric_spec(tmp))               # the survivor
        cold = mk_engine(fabric_spec(cold_root))      # same code, no blobs

        def turn2_tokens(i: int) -> list:
            r = turns1[i]
            return (list(r.prompt_tokens) + list(r.output_tokens)
                    + [9, 17, 25, 33])

        # Warm both sides' full path shapes — including B's remote
        # promote (its OWN warmup conversation through the store) — so
        # the timing loop and the post-warm replay measure the fabric,
        # not XLA compiles.
        wreq = wait(b.submit(turn1_tokens(97), sp))
        b.drain_kv_to_remote()
        wait(b.submit(list(wreq.prompt_tokens) + list(wreq.output_tokens)
                      + [9, 17, 25, 33], sp))
        wait(cold.submit(turn1_tokens(98), sp))

        resume_ms, cold_ms = [], []
        for i in range(1, CONVS):                     # conv 0 held back
            toks = turn2_tokens(i)
            t0 = time.monotonic()
            wait(b.submit(list(toks), sp1))
            resume_ms.append((time.monotonic() - t0) * 1e3)
            t0 = time.monotonic()
            wait(cold.submit(list(toks), sp1))
            cold_ms.append((time.monotonic() - t0) * 1e3)
        tier = b.kv_tier_stats()
        if tier["remote_registry_hits"] <= 0:
            return fail(f"no remote registry hits on the survivor: {tier}")
        if tier["pages_promoted_remote"] < (CONVS - 1) * 2:
            return fail(f"too few remote promotes: {tier}")
        r_p95, c_p95 = p95(resume_ms), p95(cold_ms)
        result["ttft"] = {"resume_p95_ms": round(r_p95, 2),
                          "cold_p95_ms": round(c_p95, 2),
                          "speedup": round(c_p95 / max(r_p95, 1e-6), 3)}
        if r_p95 >= c_p95:
            return fail(f"failover resume did not beat cold recompute: "
                        f"{result['ttft']}")

        # Token identity of the resumed turns against the cold engine.
        for i in range(1, CONVS):
            toks = turn2_tokens(i)
            rb = wait(b.submit(list(toks), sp))
            rc = wait(cold.submit(list(toks), sp))
            if list(rb.output_tokens) != list(rc.output_tokens):
                return fail(f"resumed conversation {i} diverged")
        result["failover_identity"] = "ok"

        # 3) Zero steady-state recompiles: the held-back conversation
        #    rides the WHOLE fabric (registry probe, blob fetch, verify,
        #    promote upload) post-warm, plus one more handoff roundtrip.
        mark_compile_warm()
        rb = wait(b.submit(turn2_tokens(0), sp))
        rc = wait(cold.submit(turn2_tokens(0), sp))
        if list(rb.output_tokens) != list(rc.output_tokens):
            return fail("post-warm resumed conversation diverged")
        if b.kv_tier_stats()["pages_promoted_remote"] <= \
                tier["pages_promoted_remote"]:
            return fail("post-warm resume never touched the remote tier")
        got = completion(pre.url, prompts[0], headers=hdr)
        want = completion(uni.url, prompts[0])
        if got != want:
            return fail("post-warm handoff output diverged")
        rep = recompile_report()
        result["recompiles"] = {"warmup": len(rep["warmup"]),
                                "steady": len(rep["steady"])}
        if rep["steady"]:
            return fail(f"steady-state recompiles: {rep['steady']}")

        # 4) Hygiene: fabric series parse off the real exposition;
        #    per-owner books balance to zero everywhere.
        text = serving_metrics_registry(
            [("b", b), ("pre", pre.engine), ("dec", dec.engine)]).render()
        names = {n for n, _, _ in parse_exposition(text)}
        missing = [s for s in FLEET_SERIES if s not in names]
        if missing:
            return fail(f"fabric series missing from exposition: {missing}")
        vals = {(n, lab.get("model")): v
                for n, lab, v in parse_exposition(text)}
        if vals[("kftpu_engine_kv_remote_promoted_bytes_total", "b")] <= 0:
            return fail("remote promote bytes never counted")
        for eng in engines + [s.engine for s in servers]:
            deadline = time.monotonic() + 20.0
            while eng.kv_pages_in_use() > 0:
                time.sleep(0.02)
                if time.monotonic() > deadline:
                    return fail("KV pages failed to drain")
            report = eng._allocator.leak_report_by_owner()
            if report:
                return fail(f"per-owner page leaks: {report}")
            eng._allocator.assert_quiescent()
        result["hygiene"] = "ok"

        bench = {
            "bench": "serve_r06_fleet_kv_fabric",
            "model": "tiny-cpu-smoke",
            "handoff_identity": result["handoff_identity"],
            "failover_identity": result["failover_identity"],
            "ttft": result["ttft"],
            "pages_drained": result["pages_drained"],
            "remote_tier": {k: tier[k] for k in
                            ("remote_registry_hits",
                             "pages_promoted_remote",
                             "remote_promote_bytes")},
            "recompiles": result["recompiles"],
        }
        with open(os.path.join(REPO, "BENCH_SERVE_r06.json"), "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
        result["fleet_kv_smoke"] = "ok"
        print(json.dumps(result, indent=2))
        return 0
    finally:
        for s in servers:
            try:
                s.stop()
            except OSError:
                pass
        for eng in engines:
            eng.stop()


if __name__ == "__main__":
    sys.exit(main())
