#!/usr/bin/env python
"""Multi-tenant LoRA serving gate (scripts/smoke.sh): one engine, N
adapters over shared base weights — token-exact, bounded-degradation,
recompile-free, leak-free (ISSUE 14).

What must hold, on small f32 CPU engines:

- **token identity**: greedy decode under every registered adapter
  is token-identical to a single-model engine running
  the MERGED weights, while base traffic through the same batched
  dispatch matches a LoRA-free engine exactly;
- **the degradation band**: the ``multi_adapter`` loadgen scenario at
  8 / 32 / 64 concurrent adapters (zipf-skewed mix over 16 packed
  slots — the 64 case churns hot-loads/evictions continuously) must
  keep decode tok/s within ``TOKS_DROP_MAX_PCT`` and TTFT p95 within
  ``TTFT_RISE_MAX_PCT`` of the single-model baseline at the same
  offered load (best-of-two segments per side, the anti-noise
  discipline);
- **zero steady-state recompiles**: the whole stage runs under
  ``KFTPU_SANITIZE=refcount,recompile``; after the warm segments the
  compile cache is marked warm and every measured segment — including
  the full 64-adapter churn — must compile NOTHING (the packed buffer
  is the fixed dispatch shape; churn swaps slot contents, never
  shapes);
- **seeded adapter-churn wedge**: a sleep wedged into the registry's
  hot-load (exactly how a slow artifact-store pull would starve
  admissions) MUST be flagged by the loadgen gate with the attribution
  diff naming the ``adapter_load`` phase / load counters;
- **hygiene**: per-owner zero leaks for BOTH resources — KV pages and
  adapter-slot references — after every run (evict-under-traffic
  included), and a SIGKILL mid-hot-load behind the model-id router
  resolves every request (survivor serves the adapter; the victim's
  audit balances to zero per owner).

Writes ``BENCH_SERVE_r04.json`` (the multi-adapter serving bench
round); prints one JSON object; ``{"lora_smoke": "ok"}`` is the gate
line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Refcount (owner-stamped page + adapter references) AND recompile
# watchdog on for the whole stage.
os.environ.setdefault("KFTPU_SANITIZE", "refcount,recompile")

#: Adapter series this gate consumes off the engine exposition — the
#: consumer half of the kftpu_engine_adapter* metric contract (X7xx).
ADAPTER_SERIES = (
    "kftpu_engine_adapters_resident",
    "kftpu_engine_adapter_loads_total",
    "kftpu_engine_adapter_evictions_total",
)

#: The declared degradation band vs single-model at the same offered
#: load (acceptance criterion: "degrade ≤ a declared threshold").
TOKS_DROP_MAX_PCT = 40.0
TTFT_RISE_MAX_PCT = 150.0

ADAPTER_COUNTS = (8, 32, 64)
LORA_SLOTS = 16
RANK = 4
PROMPT_LEN = 32
MAX_NEW = 12


def mk_cfg():
    from kubeflow_tpu.models.config import preset

    # f32: the factored delta and the merged matmul are mathematically
    # equal; bf16 would round the two paths differently (argmax flips
    # on near-ties), and CPU bf16 is emulated anyway.
    return preset("tiny", dtype="float32")


def mk_engine(cfg, params, *, n_register: int = 0, slots: int = LORA_SLOTS,
              seed0: int = 100):
    import jax

    from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.lora import AdapterSpec, init_adapter_weights

    lora = (LoRASpec(max_adapters=slots, rank=RANK) if n_register
            else LoRASpec())
    eng = LLMEngine(cfg, BatchingSpec(
        max_batch_size=8, max_seq_len=128, paged=True, page_size=16,
        chunked_prefill_tokens=32,
        decode_steps=8, lora=lora), params=params)
    for i in range(n_register):
        eng._lora.register(AdapterSpec(
            f"adpt-{i}", rank=RANK,
            weights=init_adapter_weights(jax.random.PRNGKey(seed0 + i),
                                         cfg, RANK)))
    return eng


def scenario_for(n_adapters: int, requests: int, rate: float):
    from kubeflow_tpu.loadgen import standard_matrix

    return next(s for s in standard_matrix(
        num_requests=requests, rate_rps=rate, prompt_len=PROMPT_LEN,
        max_new=MAX_NEW, slo_ttft_ms=5000.0, adapter_skew=0.5,
        adapter_ids=tuple(f"adpt-{i}" for i in range(n_adapters)))
        if s.name == "multi_adapter")


def warm_widths(engine, cfg, adapters=()):
    """Compile the width-shaped dispatch set BEFORE measuring (the
    serve_perf_smoke discipline): first-token sampler batches compile
    per power-of-two size, so a measured segment whose arrivals happen
    to co-complete N chunked prefills for the first time would eat a
    fresh compile mid-measurement. Two passes per depth (a racy admit
    split in pass 1 leaves widths pass 2 covers); adapter traffic rides
    along so the LoRA dispatch variants warm too."""
    from kubeflow_tpu.serve.engine import SamplingParams

    params = SamplingParams(max_new_tokens=4, temperature=0.0)
    names = list(adapters) or [None]
    for _ in range(2):
        for depth in (8, 4, 2, 1):
            reqs = [engine.submit(
                [1 + (7 * i + j) % (cfg.vocab_size - 2)
                 for j in range(PROMPT_LEN)], params,
                adapter=names[i % len(names)])
                for i in range(depth)]
            for r in reqs:
                r.result(timeout=60.0)


def run_segment(engine, sc, cfg):
    from kubeflow_tpu.loadgen import EngineTarget, build_report, run_scenario
    from kubeflow_tpu.obs.trace import get_tracer
    from kubeflow_tpu.serve.server import serving_metrics_registry

    tracer = get_tracer()
    tracer.reset()
    run = run_scenario(EngineTarget(engine), sc, vocab_size=cfg.vocab_size,
                       max_prompt_len=100, tracer=tracer)
    text = serving_metrics_registry([("lora", engine)]).render()
    return build_report(run, metrics_text=text, tracer=tracer), text


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=24.0)
    args = ap.parse_args()

    import jax

    from kubeflow_tpu.loadgen import compare_scenario, noise_band_pct, \
        spread_pct
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.runtime.sanitize import (
        assert_no_steady_recompiles, mark_compile_warm,
    )
    from kubeflow_tpu.serve.engine import SamplingParams
    from kubeflow_tpu.serve.lora import AdapterSpec, init_adapter_weights, \
        merged_params

    result: dict = {}

    def fail(msg: str) -> int:
        result["lora_smoke"] = msg
        print(json.dumps(result, indent=2))
        return 1

    cfg = mk_cfg()
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    prompt = [(13 * i) % 250 + 1 for i in range(PROMPT_LEN)]

    # ---- 1) token identity: adapters vs merged references
    from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec
    from kubeflow_tpu.serve.engine import LLMEngine

    ident_specs = [AdapterSpec(
        f"adpt-{i}", rank=RANK,
        weights=init_adapter_weights(jax.random.PRNGKey(100 + i), cfg,
                                     RANK)) for i in range(2)]

    def mk(b_lora, p):
        return LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
            lora=b_lora), params=p)

    eng = mk(LoRASpec(max_adapters=2, rank=RANK), params)
    for s in ident_specs:
        eng._lora.register(s)
    base = eng.generate(prompt, SamplingParams(max_new_tokens=MAX_NEW))
    want_base = mk(LoRASpec(), params).generate(
        prompt, SamplingParams(max_new_tokens=MAX_NEW))
    if base != want_base:
        return fail("identity: base traffic diverged")
    for s in ident_specs:
        req = eng.submit(prompt, SamplingParams(max_new_tokens=MAX_NEW),
                         adapter=s.name)
        while not req.done.is_set():
            eng.step()
        got = req.result(5)
        want = mk(LoRASpec(), merged_params(params, cfg, s)).generate(
            prompt, SamplingParams(max_new_tokens=MAX_NEW))
        if got != want or got == base:
            return fail(
                f"identity: adapter {s.name} got={got} want={want}")
    eng._lora.assert_quiescent()
    eng._allocator.assert_quiescent()
    result["token_identity"] = "ok"

    # ---- 2) degradation band + recompile-free churn
    # Build + WARM every engine first (each engine owns fresh jitted
    # closures; their compiles are warmup), then mark the cache warm —
    # every measured segment after that must compile nothing.
    baseline_eng = mk_engine(cfg, params, n_register=0)
    base_sc = scenario_for(0, args.requests, args.rate)
    churn_engines = {n: mk_engine(cfg, params, n_register=n)
                     for n in ADAPTER_COUNTS}
    baseline_eng.start()
    for eng in churn_engines.values():
        eng.start()
    try:
        warm_widths(baseline_eng, cfg)
        run_segment(baseline_eng, base_sc, cfg)              # warm
        for n, eng in churn_engines.items():
            warm_widths(eng, cfg,
                        adapters=[f"adpt-{i}" for i in range(min(n, 8))])
            run_segment(eng, scenario_for(n, args.requests, args.rate),
                        cfg)                                 # warm
        mark_compile_warm()

        segs = [run_segment(baseline_eng, base_sc, cfg)[0]
                for _ in range(2)]
        base_best_toks = max(s["tokens_per_sec"] for s in segs)
        base_best_ttft = min(s["ttft_ms"].get("p95", 1e9) for s in segs)
        base_spread = spread_pct(segs[0]["tokens_per_sec"],
                                 segs[1]["tokens_per_sec"])
        result["baseline"] = {"tokens_per_sec": base_best_toks,
                              "ttft_p95_ms": base_best_ttft}

        bench_rows = []
        unwedged_64 = None
        for n, eng in churn_engines.items():
            sc = scenario_for(n, args.requests, args.rate)
            reps = [run_segment(eng, sc, cfg) for _ in range(2)]
            rep = max((r for r, _ in reps),
                      key=lambda r: r["tokens_per_sec"])
            text = reps[-1][1]
            if n == 64:
                unwedged_64 = rep
            if rep["by_status"].get("ok", 0) < args.requests * 0.9:
                return fail(f"{n} adapters: too many failures: "
                            f"{rep['by_status']}")
            toks_drop = 100.0 * (1.0 - rep["tokens_per_sec"]
                                 / max(base_best_toks, 1e-9))
            ttft = min(r["ttft_ms"].get("p95", 1e9) for r, _ in reps)
            ttft_rise = 100.0 * (ttft / max(base_best_ttft, 1e-9) - 1.0)
            row = {"adapters": n,
                   "tokens_per_sec": rep["tokens_per_sec"],
                   "ttft_p95_ms": ttft,
                   "toks_drop_pct": round(toks_drop, 1),
                   "ttft_rise_pct": round(ttft_rise, 1),
                   "adapter_report": rep.get("adapters", {}),
                   "engine_adapters": rep["engine"].get("adapters", {})}
            bench_rows.append(row)
            if toks_drop > TOKS_DROP_MAX_PCT:
                return fail(f"{n} adapters: tok/s degraded "
                            f"{toks_drop:.0f}% > {TOKS_DROP_MAX_PCT}%")
            if ttft_rise > TTFT_RISE_MAX_PCT:
                return fail(f"{n} adapters: ttft p95 rose "
                            f"{ttft_rise:.0f}% > {TTFT_RISE_MAX_PCT}%")
            from kubeflow_tpu.loadgen import build_schedule
            distinct = len({r.adapter for r in build_schedule(
                sc, vocab_size=cfg.vocab_size, max_prompt_len=100)})
            if distinct > LORA_SLOTS and not rep["engine"].get(
                    "adapters", {}).get("evictions"):
                return fail(
                    f"{n} adapters ({distinct} distinct drawn) over "
                    f"{LORA_SLOTS} slots must have evicted")
            # per-adapter client split must cover the mix
            if len(rep.get("adapters", {})) < min(n, 4):
                return fail(f"{n} adapters: per-adapter report split "
                            f"missing: {list(rep.get('adapters', {}))}")
            # X7xx consumer half: the adapter series parse off the real
            # exposition.
            names = {nm for nm, _, _ in parse_exposition(text)}
            missing = [s for s in ADAPTER_SERIES if s not in names]
            if missing:
                return fail(f"adapter series not rendered: {missing}")
            eng._lora.assert_quiescent()
            eng._allocator.assert_quiescent()
        result["degradation"] = bench_rows

        # Zero steady-state recompiles across ALL measured churn.
        try:
            assert_no_steady_recompiles()
        except Exception as exc:
            return fail(f"steady-state recompiles under churn: {exc}")
        result["recompiles_steady"] = 0

        # ---- 3) seeded adapter-churn wedge (on the warmed 64 engine —
        # no fresh compiles; the wedge is pure host latency in the
        # hot-load, exactly a slow artifact-store pull).
        eng64 = churn_engines[64]
        real_load = eng64._lora._load_slot

        def wedged_load(spec):
            time.sleep(0.25)
            return real_load(spec)

        eng64._lora._load_slot = wedged_load
        try:
            wedged_rep, _ = run_segment(
                eng64, scenario_for(64, args.requests, args.rate), cfg)
        finally:
            eng64._lora._load_slot = real_load
        band = noise_band_pct([base_spread])
        problems = compare_scenario(unwedged_64, wedged_rep,
                                    band_pct=band)
        if not problems:
            return fail("seeded adapter-load wedge NOT flagged by the "
                        f"gate (band {band:.0f}%)")
        wedge_attr = {
            "problems": problems,
            "baseline_phases": unwedged_64.get("phases", {}),
            "wedged_phases": wedged_rep.get("phases", {}),
            "wedged_loads": wedged_rep["engine"].get("adapters", {}),
        }
        if "adapter_load_ms" not in wedged_rep.get("phases", {}):
            return fail("wedge flagged but adapter_load phase missing "
                        "from the attribution")
        result["seeded_wedge"] = wedge_attr
    finally:
        baseline_eng.stop()
        for eng in churn_engines.values():
            eng.stop()

    # ---- 4) chaos: SIGKILL mid-hot-load behind the model-id router
    rc = chaos_kill_mid_hot_load(cfg, params, result, fail)
    if rc is not None:
        return rc

    # ---- 5) bench round
    bench = {
        "bench": "serve_r04_multi_adapter",
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "host": "cpu",
        "baseline": result["baseline"],
        "declared_band": {"toks_drop_max_pct": TOKS_DROP_MAX_PCT,
                          "ttft_rise_max_pct": TTFT_RISE_MAX_PCT},
        "rows": result["degradation"],
    }
    with open(os.path.join(REPO, "BENCH_SERVE_r04.json"), "w") as f:
        json.dump(bench, f, indent=2)
        f.write("\n")
    result["lora_smoke"] = "ok"
    print(json.dumps(result, indent=2, default=str))
    return 0


def chaos_kill_mid_hot_load(cfg, params, result, fail):
    """Two LoRA replicas behind the model-id router; the victim's
    hot-loads are wedged slow, and it is killed MID-LOAD. Every client
    request must still resolve (router retries/ejects onto the
    survivor), the survivor must serve the adapter, and the victim's
    audit must balance pages AND adapter references per owner."""
    import threading
    import urllib.error
    import urllib.request

    import jax

    from kubeflow_tpu.core.headers import MODEL_HEADER
    from kubeflow_tpu.serve.faults import kill_model_server
    from kubeflow_tpu.serve.lora import AdapterSpec, init_adapter_weights
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    def mk_server(name, load_delay=0.0):
        # register through sources so the victim's pulls can be slow
        from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec
        from kubeflow_tpu.serve.engine import LLMEngine
        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
            decode_steps=4,
            lora=LoRASpec(max_adapters=4, rank=RANK)), params=params)
        for i in range(4):
            w = init_adapter_weights(jax.random.PRNGKey(100 + i), cfg, RANK)

            def source(w=w):
                if load_delay:
                    time.sleep(load_delay)
                return w

            eng._lora.register(AdapterSpec(f"adpt-{i}", rank=RANK,
                                           source=source))
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    survivor = mk_server("lora-a")
    victim = mk_server("lora-b", load_delay=0.6)
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.5,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [survivor.url, victim.url]})
    router.start()

    def completion(model, timeout_s=10.0):
        body = json.dumps({"prompt": "chaos" * 4, "max_tokens": 6,
                           "timeout": timeout_s}).encode()
        req = urllib.request.Request(
            router.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json",
                     MODEL_HEADER: model})
        try:
            with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
                r.read()
                return r.status
        except urllib.error.HTTPError as exc:
            exc.read()
            return exc.code
        except OSError:
            return 502

    statuses: list[int] = []
    lock = threading.Lock()

    def client(i):
        st = completion(f"adpt-{i % 4}")
        with lock:
            statuses.append(st)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    # Kill the victim while its (wedged, 0.6 s) hot-load is in flight.
    time.sleep(0.3)
    kill_model_server(victim)
    hung = 0
    for t in threads:
        t.join(timeout=60.0)
        hung += t.is_alive()
    try:
        if hung:
            return fail(f"chaos: {hung} client(s) hung after SIGKILL")
        ok = sum(1 for s in statuses if s == 200)
        if ok < len(statuses) // 2:
            return fail(f"chaos: only {ok}/{len(statuses)} resolved 200: "
                        f"{statuses}")
        if completion("adpt-1") != 200:
            return fail("chaos: survivor does not serve the adapter "
                        "after the kill")
        # Victim audit: drive its (halted) scheduler so the reaper
        # releases stranded slots/pages/adapter refs, then balance.
        deadline = time.monotonic() + 30.0
        veng = victim.engine
        while time.monotonic() < deadline:
            veng.step()
            if veng.kv_pages_in_use() == 0 and not \
                    veng._lora.leak_report_by_owner():
                break
            time.sleep(0.05)
        veng._allocator.assert_quiescent()
        veng._lora.assert_quiescent()
        survivor.engine._allocator.assert_quiescent()
        survivor.engine._lora.assert_quiescent()
        result["chaos_kill_mid_hot_load"] = {
            "statuses": statuses, "survivor_ok": True,
            "victim_leaks_by_owner": {}}
    finally:
        router.stop()
        survivor.stop()
    return None


if __name__ == "__main__":
    sys.exit(main())
