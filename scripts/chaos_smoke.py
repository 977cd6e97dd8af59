"""Chaos smoke stage for scripts/smoke.sh: a bench_serve-style closed-loop
run through the hardened router with one replica SIGKILLed mid-run.

Asserts the serving-path robustness contract end to end on a real stack
(2 model-server replicas, paged engines, router with retries + ejection):

- the bench completes — zero hung requests (every client thread joins);
- every request resolves explicitly (200 or an HTTP error status);
- the router recovers: post-kill requests succeed on the survivor;
- paged-KV page refcounts balance to zero leaks on both engines.

Prints one JSON line with the verdict; exit code 0 iff "chaos_smoke": "ok".

    JAX_PLATFORMS=cpu python scripts/chaos_smoke.py [--requests 24]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def completion(url: str, timeout_s: float) -> int:
    body = json.dumps({"prompt": "smoke", "max_tokens": 8,
                       "timeout": timeout_s}).encode()
    from kubeflow_tpu.serve.router import DEADLINE_HEADER

    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json",
                 DEADLINE_HEADER: str(int(timeout_s * 1e3))})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
            return r.status
    except urllib.error.HTTPError as exc:
        exc.read()
        return exc.code
    except OSError:
        return 502


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--kill-after", type=int, default=4,
                    help="completed requests before the SIGKILL fires")
    ap.add_argument("--timeout", type=float, default=8.0,
                    help="per-request deadline (seconds)")
    args = ap.parse_args()

    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.faults import kill_model_server
    from kubeflow_tpu.serve.router import Router
    from kubeflow_tpu.serve.server import ModelServer

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def mk(name: str) -> ModelServer:
        eng = LLMEngine(
            cfg,
            BatchingSpec(max_batch_size=2, max_seq_len=96,
                         paged=True, page_size=16,
                         chunked_prefill_tokens=16, decode_steps=4),
            params=params)
        srv = ModelServer(name, eng, port=0)
        srv.start()
        return srv

    a, b = mk("replica-a"), mk("replica-b")
    router = Router(queue_timeout=5.0, eject_threshold=2, eject_period=0.5,
                    max_retries=2, upstream_timeout=30.0)
    router.set_backends({"latest": [a.url, b.url]})
    router.start()

    results: list[int] = []
    lock = threading.Lock()
    it = iter(range(args.requests))
    killed = threading.Event()

    def client() -> None:
        while True:
            with lock:
                nxt = next(it, None)
            if nxt is None:
                return
            status = completion(router.url, args.timeout)
            with lock:
                results.append(status)
                if not killed.is_set() and len(results) >= args.kill_after:
                    killed.set()
                    kill_model_server(b)

    t0 = time.monotonic()
    threads = [threading.Thread(target=client)
               for _ in range(max(1, args.concurrency))]
    for t in threads:
        t.start()
    hung = 0
    for t in threads:
        t.join(timeout=120.0)
        hung += t.is_alive()
    wall = time.monotonic() - t0

    # Router recovered? The survivor must serve fresh traffic.
    recovered = all(completion(router.url, args.timeout) == 200
                    for _ in range(3))

    # Refcount audit: cancel anything the kill stranded, drive the reaper.
    leaks = {}
    for srv in (a, b):
        eng = srv.engine
        for s in eng.slots:
            if s is not None:
                s.request.cancel()
        for req in list(eng._backlog) + list(eng._preempted):
            req.cancel()
        for ch in list(eng._chunkings):
            ch.request.cancel()
        deadline = time.monotonic() + 20.0
        while eng.kv_pages_in_use() > 0 and time.monotonic() < deadline:
            eng.step()
        leaks[srv.name] = eng.kv_pages_in_use()

    statuses = sorted(set(results))
    ok = (hung == 0 and len(results) == args.requests and killed.is_set()
          and recovered and all(v == 0 for v in leaks.values())
          and all(s in (200, 429, 500, 502, 503, 504) for s in results))
    print(json.dumps({
        "chaos_smoke": "ok" if ok else "FAIL",
        "requests": len(results), "hung": hung,
        "completed_200": results.count(200), "statuses": statuses,
        "router_recovered": recovered, "kv_page_leaks": leaks,
        "router_stats": router.snapshot(), "wall_s": round(wall, 2),
    }))
    router.stop()
    try:
        a.stop()
    except OSError:
        pass
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
