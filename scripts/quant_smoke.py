#!/usr/bin/env python
"""Quantized-serving gate (scripts/smoke.sh): int8 KV through the whole
fabric — paged pool, in-kernel dequant, handoff wire, host tier (ISSUE
16 tentpole).

What must hold, on small paged CPU engines:

- **token band**: int8-pool greedy decode tracks the full-dtype engine
  inside the DECLARED tolerance band (quantization legitimately flips
  argmax near-ties, so identity is banded, not exact: mean per-prompt
  agreement >= 0.65, min >= 0.3 over the prompt set — one early flip
  cascades for the rest of a greedy trajectory);
- **fabric identity**: int8 prefill → v2 wire → int8 decode adoption is
  token-IDENTICAL to the int8 unified engine (same quantized KV on both
  paths — the wire/adopt rebuild may not introduce any divergence);
- **density**: at a real head dim (128), the int8 pool holds >= 1.9x
  the resident KV tokens of the full-dtype pool at equal HBM
  (tokens-per-MiB ratio off ``engine.kv_pool_density``);
- **wire bytes**: the v2 handoff payload and the tier's demote batches
  ship < 0.6x the full-dtype bytes at head dim 128 (~halved);
- **gather vs kernel A/B**: the in-kernel dequant path (pallas,
  interpret off-TPU) produces tokens IDENTICAL to gather+dequant on the
  same int8 pool (f32 config: the two dequant sites are the same math);
- **zero steady-state recompiles**: a warmed int8 engine replaying the
  same traffic shape (decode + a handoff round trip) compiles NOTHING
  (KFTPU_SANITIZE=recompile);
- **hygiene**: the quant metric series parse off the real exposition
  (the consumer half of the X7xx contract) and per-owner refcounts
  balance to zero.

Writes ``BENCH_SERVE_r05.json`` (the quantized-serving bench round);
prints one JSON object; ``{"quant_smoke": "ok"}`` is the gate line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Refcount (per-owner page books) + recompile (steady-state watchdog)
# for the whole stage.
os.environ["KFTPU_SANITIZE"] = "refcount,recompile"

#: Quant/wire series this gate consumes off the engine exposition — the
#: consumer half of the kftpu_engine_kv_quant_*/wire-bytes contract.
QUANT_SERIES = (
    "kftpu_engine_kv_quant_enabled",
    "kftpu_engine_kv_quant_tokens_per_mib",
    "kftpu_engine_kv_handoff_bytes_exported_total",
    "kftpu_engine_kv_handoff_bytes_adopted_total",
    "kftpu_engine_kv_wire_bytes_demoted_total",
    "kftpu_engine_kv_wire_bytes_promoted_total",
)

# The declared tolerance band: int8 KV legitimately flips greedy
# near-ties, so the A/B is banded agreement, never exact identity.
TOKEN_BAND_MEAN = 0.65
TOKEN_BAND_MIN = 0.30
MAX_NEW = 16

PROMPTS = [
    [5, 17, 3, 99, 42, 8, 8, 1] * 3,
    list(range(2, 34)),
    [7, 9, 11] * 9,
    [2] * 28,
    [13, 5, 13, 7, 13, 9, 13, 11] * 3,
    [101, 3, 55, 3, 101, 3, 55, 3] * 2,
    [41, 42, 43, 44] * 6,
    [9, 8, 7, 6, 5, 4, 3, 2, 1] * 3,
]


def wait(req, timeout=60.0):
    assert req.done.wait(timeout), "request never finished"
    return req


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.parse_args()

    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.runtime.sanitize import (
        mark_compile_warm, recompile_report, recompile_watchdog,
    )
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
    from kubeflow_tpu.serve.handoff import HandoffPayload
    from kubeflow_tpu.serve.server import serving_metrics_registry

    result: dict = {}

    def fail(msg: str) -> int:
        result["quant_smoke"] = msg
        print(json.dumps(result, indent=2))
        return 1

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    # f32 twin for the gather-vs-kernel identity A/B: both impls read
    # the SAME int8 pages, so at f32 accumulation the greedy paths match
    # exactly (bf16 would re-round the two dequant sites differently).
    fcfg = preset("tiny", vocab_size=512, dtype="float32")
    fparams = init_decoder_params(jax.random.PRNGKey(0), fcfg)

    def spec(kv=None, role="unified", impl="auto", host=0):
        return BatchingSpec(
            max_batch_size=4, max_seq_len=128, paged=True, page_size=16,
            chunked_prefill_tokens=16,
            decode_steps=4, kv_cache_dtype=kv, role=role,
            paged_attn_impl=impl, host_kv_pages=host,
            prefix_index="radix",
            kv_demote_after_s=(0.05 if host else 2.0))

    def mk(c=cfg, p=None, **kw):
        eng = LLMEngine(c, spec(**kw), params=(params if p is None else p))
        eng.start()
        return eng

    sp = SamplingParams(max_new_tokens=MAX_NEW, temperature=0.0)
    engines = []
    try:
        wd = recompile_watchdog()
        if wd is None:
            return fail("recompile watchdog not installed")

        # 1) Token band: int8 pool vs full-dtype pool, banded agreement.
        eng8 = mk(kv="int8")
        eng16 = mk()
        engines += [eng8, eng16]
        agrees = []
        for prompt in PROMPTS:
            r8 = wait(eng8.submit(list(prompt), sp))
            r16 = wait(eng16.submit(list(prompt), sp))
            got, want = list(r8.output_tokens), list(r16.output_tokens)
            agrees.append(sum(a == b for a, b in zip(got, want))
                          / max(len(want), 1))
        band = {"mean_agreement": round(sum(agrees) / len(agrees), 3),
                "min_agreement": round(min(agrees), 3),
                "declared_mean": TOKEN_BAND_MEAN,
                "declared_min": TOKEN_BAND_MIN,
                "prompts": len(PROMPTS), "max_new": MAX_NEW}
        result["token_band"] = band
        if band["mean_agreement"] < TOKEN_BAND_MEAN \
                or band["min_agreement"] < TOKEN_BAND_MIN:
            return fail(f"int8 drifted outside the declared band: {band}")

        # 2) Fabric identity: int8 prefill → v2 wire → int8 decode must
        #    equal the int8 unified engine token for token.
        # Fresh unified engine: eng8's warm prefix cache would replay
        # its prompts down the prefix-hit path, whose bf16 padding
        # differs from the cold chunked prefill the disagg pair runs —
        # an LSB there legitimately flips a later greedy near-tie.
        uni8 = mk(kv="int8")
        pre8 = mk(kv="int8", role="prefill")
        dec8 = mk(kv="int8", role="decode")
        engines += [uni8, pre8, dec8]
        wire8 = 0
        for prompt in PROMPTS[:4]:
            want = list(wait(uni8.submit(list(prompt), sp)).output_tokens)
            p_req = wait(pre8.submit(list(prompt), sp))
            if p_req.finish_reason != "handoff":
                return fail(f"prefill engine did not hand off: "
                            f"{p_req.finish_reason}")
            blob = p_req.handoff.to_wire()
            wire8 += len(blob)
            payload = HandoffPayload.from_wire(blob)
            if payload.cache_dtype != "int8":
                return fail("v2 wire lost the cache-dtype tag")
            d_req = wait(dec8.submit_handoff(payload))
            got = [payload.first_token] + list(d_req.output_tokens)
            pre8.complete_handoff(p_req.id)
            if got != want:
                return fail(f"fabric identity broken: {got} != {want}")
        result["fabric_identity"] = "ok"

        # 3) Density + wire bytes at a real head dim (128).
        dcfg = preset("tiny", vocab_size=512, head_dim=128)
        dparams = init_decoder_params(jax.random.PRNGKey(1), dcfg)
        d8 = mk(dcfg, dparams, kv="int8", role="prefill", host=32)
        d16 = mk(dcfg, dparams, role="prefill", host=32)
        engines += [d8, d16]
        den8 = d8.kv_pool_density()
        den16 = d16.kv_pool_density()
        ratio = den8["tokens_per_mib"] / den16["tokens_per_mib"]
        result["density"] = {
            "head_dim": 128,
            "int8_tokens_per_mib": round(den8["tokens_per_mib"], 1),
            "full_tokens_per_mib": round(den16["tokens_per_mib"], 1),
            "resident_tokens_at_equal_hbm_x": round(ratio, 3),
        }
        if ratio < 1.9:
            return fail(f"density win below 1.9x: {result['density']}")
        # Handoff wire bytes: same prompt, both pools, payload sizes.
        prompt = list(range(3, 43))
        h8 = wait(d8.submit(list(prompt), sp))
        h16 = wait(d16.submit(list(prompt), sp))
        hb8, hb16 = h8.handoff.wire_bytes, h16.handoff.wire_bytes
        d8.complete_handoff(h8.id)
        d16.complete_handoff(h16.id)
        # Tier wire bytes: let both engines demote the released pages,
        # then compare bytes-per-demoted-page.
        deadline = time.monotonic() + 20.0
        while (d8.kv_tier_stats()["pages_demoted"] == 0
               or d16.kv_tier_stats()["pages_demoted"] == 0):
            time.sleep(0.02)
            if time.monotonic() > deadline:
                return fail("host tier never demoted on the Dh=128 pair")
        t8, t16 = d8.kv_tier_stats(), d16.kv_tier_stats()
        m8 = t8["demote_wire_bytes"] / t8["pages_demoted"]
        m16 = t16["demote_wire_bytes"] / t16["pages_demoted"]
        result["wire_bytes"] = {
            "handoff_int8": hb8, "handoff_full": hb16,
            "handoff_ratio": round(hb8 / hb16, 3),
            "demote_per_page_int8": round(m8, 1),
            "demote_per_page_full": round(m16, 1),
            "demote_ratio": round(m8 / m16, 3),
        }
        if hb8 / hb16 > 0.6 or m8 / m16 > 0.6:
            return fail(f"wire bytes not ~halved: {result['wire_bytes']}")

        # 4) Gather vs in-kernel dequant A/B on the SAME int8 pool
        #    (f32 config → exact identity; wall time reported only —
        #    interpret mode is not a perf statement).
        g8 = mk(fcfg, fparams, kv="int8", impl="gather")
        k8 = mk(fcfg, fparams, kv="int8", impl="pallas")
        engines += [g8, k8]
        ab = {}
        outs = {}
        for name, eng in (("gather", g8), ("kernel", k8)):
            t0 = time.perf_counter()
            outs[name] = [list(wait(eng.submit(list(p), sp)).output_tokens)
                          for p in PROMPTS[:3]]
            ab[name + "_s"] = round(time.perf_counter() - t0, 3)
        result["gather_vs_kernel"] = ab
        if outs["gather"] != outs["kernel"]:
            return fail("in-kernel dequant diverged from gather+dequant")

        # 5) Zero steady-state recompiles: replay the SAME traffic shape
        #    (decode + a handoff round trip) on the warmed engines.
        #    Nothing is constructed after the warm mark.
        warm_prompt = PROMPTS[0]
        p_req = wait(pre8.submit(list(warm_prompt), sp))
        d_req = wait(dec8.submit_handoff(p_req.handoff))
        pre8.complete_handoff(p_req.id)
        mark_compile_warm()
        r8 = wait(eng8.submit(list(warm_prompt), sp))
        p_req2 = wait(pre8.submit(list(warm_prompt), sp))
        d_req2 = wait(dec8.submit_handoff(p_req2.handoff))
        pre8.complete_handoff(p_req2.id)
        if list(d_req2.output_tokens) != list(d_req.output_tokens):
            return fail("steady-state handoff replay changed output")
        rep = recompile_report()
        result["recompiles"] = {"warmup": len(rep["warmup"]),
                               "steady": rep["steady_count"]}
        if rep["steady_count"] != 0:
            return fail(f"steady-state recompiles: {rep['steady']}")
        _ = r8

        # 6) Hygiene: quant series parse off the real exposition;
        #    per-owner books balance to zero everywhere.
        text = serving_metrics_registry(
            [("q", eng8), ("pre", pre8), ("dec", dec8),
             ("d128", d8)]).render()
        names = {n for n, _, _ in parse_exposition(text)}
        missing = [s for s in QUANT_SERIES if s not in names]
        if missing:
            return fail(f"quant series missing from exposition: {missing}")
        vals = {(n, lab.get("model")): v
                for n, lab, v in parse_exposition(text)}
        if vals[("kftpu_engine_kv_quant_enabled", "q")] != 1:
            return fail("quant_enabled gauge not set on the int8 engine")
        if vals[("kftpu_engine_kv_handoff_bytes_exported_total",
                 "pre")] <= 0:
            return fail("handoff wire bytes never counted")
        for eng in engines:
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
            "bench": "serve_r05_int8_kv_fabric",
            "model": "tiny-cpu-smoke",
            "token_band": band,
            "density": result["density"],
            "wire_bytes": result["wire_bytes"],
            "gather_vs_kernel": ab,
            "recompiles": result["recompiles"],
            "handoff_wire_bytes_total_int8": wire8,
        }
        with open(os.path.join(REPO, "BENCH_SERVE_r05.json"), "w") as f:
            json.dump(bench, f, indent=2)
            f.write("\n")
        result["quant_smoke"] = "ok"
        print(json.dumps(result, indent=2))
        return 0
    finally:
        for eng in engines:
            eng.stop()


if __name__ == "__main__":
    sys.exit(main())
