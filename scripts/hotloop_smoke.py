"""Hot-loop smoke stage (scripts/smoke.sh): a short pipelined-dispatch
on/off A/B on CPU asserting CORRECTNESS + PLUMBING, never perf —

- greedy outputs token-identical with pipelining on and off
  (the tentpole's output contract);
- steady-state decode rounds perform zero full-array host→device uploads
  of scheduler state (the device_state counters stay at their
  construction values while rounds accumulate);
- traced decode spans carry well-formed ``host_gap_ms`` decode_round
  event attributes (the PR 3 tracer plumbing end-to-end);
- the model server's /metrics exposes the ``kftpu_engine_host_gap_seconds``
  histogram and ``kftpu_engine_dispatch_depth`` gauge, parsing under the
  exposition grammar.

Prints one JSON object; {"hotloop_smoke": "ok"} is the gate line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mk_engine(cfg, params, *, pipelined):
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    return LLMEngine(cfg, BatchingSpec(
        max_batch_size=4, max_seq_len=128, chunked_prefill_tokens=32,
        paged=True, page_size=16,
        decode_steps=4, pipelined_decode=pipelined), params=params)


def gen_all(eng, prompts, max_new, trace_parent=None):
    from kubeflow_tpu.serve.engine import SamplingParams

    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    reqs = [eng.submit(list(p), sp, trace_parent=trace_parent)
            for p in prompts]
    for _ in range(1200):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            break
    if not all(r.done.is_set() for r in reqs):
        raise AssertionError("engine did not finish the smoke prompts")
    return [list(r.output_tokens) for r in reqs]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=48)
    args = ap.parse_args()

    import jax

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.obs.trace import get_tracer

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 17, 3, 99, 42], list(range(1, 40)), [7] * 20]

    result: dict = {}

    # 1) Token identity: pipelining on/off.
    outputs = {}
    engines = {}
    for tag, pipelined in (("paged_off", False), ("paged_on", True)):
        eng = mk_engine(cfg, params, pipelined=pipelined)
        outputs[tag] = gen_all(eng, prompts, args.max_new)
        engines[tag] = eng
    if outputs["paged_on"] != outputs["paged_off"]:
        result["hotloop_smoke"] = "token mismatch: paged_on"
        print(json.dumps(result))
        return 1
    result["token_identity"] = "ok"

    # 2) Zero full uploads of scheduler state past construction.
    for tag, eng in engines.items():
        stats = eng._dstate.stats
        if eng.decode_rounds < 2:
            result["hotloop_smoke"] = f"{tag}: too few rounds to judge"
            print(json.dumps(result))
            return 1
        if stats["full_state_uploads"] != 1 or \
                stats["full_table_uploads"] != 1:
            result["hotloop_smoke"] = f"{tag}: full upload leak {stats}"
            print(json.dumps(result))
            return 1
        if eng.kv_pages_in_use() != 0:
            result["hotloop_smoke"] = f"{tag}: page leak"
            print(json.dumps(result))
            return 1
    result["state_uploads"] = {t: dict(e._dstate.stats)
                               for t, e in engines.items()}

    # 3) Traced decode rounds carry a well-formed host_gap_ms attribute.
    tracer = get_tracer()
    tracer.reset()
    eng = mk_engine(cfg, params, pipelined=True)
    with tracer.span("hotloop.smoke") as root:
        gen_all(eng, [list(range(1, 30))], args.max_new, trace_parent=root)
    gaps = []
    for t in tracer.traces():
        for s in t["spans"]:
            if s["name"] != "engine.decode":
                continue
            for ev in s.get("events", []):
                if ev["name"] == "decode_round" and "host_gap_ms" in ev:
                    gaps.append(ev["host_gap_ms"])
    bad = [g for g in gaps if not isinstance(g, (int, float)) or g < 0]
    if not gaps or bad:
        result["hotloop_smoke"] = \
            f"host_gap_ms malformed/missing (gaps={gaps[:8]}, bad={bad[:8]})"
        print(json.dumps(result))
        return 1
    if tracer.open_spans() != 0:
        result["hotloop_smoke"] = "open spans leaked"
        print(json.dumps(result))
        return 1
    result["decode_span_host_gap_ms_samples"] = len(gaps)

    # 4) /metrics exposes the hot-loop series and parses.
    from kubeflow_tpu.obs.registry import parse_exposition
    from kubeflow_tpu.serve.server import ModelServer

    srv = ModelServer("smoke", engines["paged_on"], port=0)
    try:
        text = srv.metrics_text()
        names = {n for n, _, _ in parse_exposition(text)}
        for need in ("kftpu_engine_host_gap_seconds_bucket",
                     "kftpu_engine_dispatch_depth"):
            if need not in names:
                result["hotloop_smoke"] = f"missing /metrics series {need}"
                print(json.dumps(result))
                return 1
    finally:
        srv.httpd.server_close()
    result["metrics_series"] = "ok"

    result["hotloop_smoke"] = "ok"
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
