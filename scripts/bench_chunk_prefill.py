"""Paged chunk-prefill microbench over a per-head pool, on one chip: what a
chunk costs against the context its slot holds, in both forms of
``serve/paged.py::paged_chunk_prefill`` and for the kernel alone.

- the PROGRAM (two layers at Mixtral's attention widths, a small MLP): the
  in-place form ("pallas": rows written where they belong,
  ``paged_chunk_attention`` over the pages where they lie; it takes the
  table whole, the kernel skips what lies behind the chunk) beside the
  gathered form ("gather") at the engine's context bucket;
- the KERNEL alone, 32 query heads over 8 KV heads of 128, a chunk of 512:
  milliseconds a call (sixteen calls chained inside one program, each
  taking the last one's output as its queries, so the host's dispatch is
  not in the number) and its share of the matrix unit's peak, from the
  operations causal attention needs (4 x heads x head width x the
  (query, key) pairs at or under the diagonal).

Run (chip): python scripts/bench_chunk_prefill.py   (prints JSON lines)
Rehearse (CPU, tiny, interpreted): ... --tiny
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, reps):
    """Best of two windows of ``reps`` calls, in ms a call."""
    import jax

    jax.block_until_ready(fn())                     # compile
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps * 1e3
        best = dt if best is None else min(best, dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: small widths, few repetitions")
    ap.add_argument("--kernel-only", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.peaks import PEAKS
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.ops import paged_attention as pa
    from kubeflow_tpu.serve.paged import (
        context_bucket, paged_chunk_prefill, pool_shapes,
    )

    pg, C, max_len, reps = (16, 32, 256, 2) if args.tiny \
        else (128, 512, 8320, 10)
    dt = "float32" if args.tiny else "bfloat16"
    cfg = preset("llama3-8b", n_layers=2, mlp_dim=1024, vocab_size=1024,
                 max_seq_len=max_len, dtype=dt, param_dtype=dt,
                 **({"hidden": 256, "n_heads": 8, "n_kv_heads": 2}
                    if args.tiny else {}))
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    mpp = max_len // pg
    num_pages = mpp + 8
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.permutation(num_pages)[:mpp].astype(np.int32))
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, C), np.int32))
    cache = {n: jnp.zeros(shape, d) for n, (shape, d)
             in pool_shapes(cfg, num_pages, pg).items()}
    device = jax.devices()[0]
    # No peak off the chip: a rehearsal's time is the interpreter's.
    peak = PEAKS.get(device.device_kind, {}).get("bf16_flops")
    print(json.dumps({"device": device.platform,
                      "device_kind": device.device_kind}), flush=True)

    def program(impl):
        fn = jax.jit(
            lambda p, c, st, ncp: paged_chunk_prefill(
                p, c, tokens, table[None], st[None],
                jnp.full((1,), C, jnp.int32), cfg, context_pages=ncp,
                paged_attn_impl=impl),
            static_argnums=(3,), donate_argnums=(1,))
        return lambda *a: fn(params, *a)

    def run(fn, pos, ctx):
        nonlocal cache

        def call():
            nonlocal cache
            logits, cache = fn(cache, jnp.int32(pos), ctx)
            return logits

        return round(_time(call, reps), 3)

    in_place, gathered = program("pallas"), program("gather")
    starts = (0, 64, 192) if args.tiny else (0, 3584, 7680)
    for pos in () if args.kernel_only else starts:
        ctx = context_bucket(pos, C, pg, mpp)
        print(json.dumps({
            "metric": "paged_chunk_prefill_ms", "pos": pos, "ctx_pages": ctx,
            "in_place": run(in_place, pos, ctx),
            "gathered": run(gathered, pos, ctx)}), flush=True)

    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (h, C, d), cfg.activation_dtype)
    pool_k = jax.random.normal(keys[1], (num_pages, pg, kv, d),
                               cfg.activation_dtype)
    pool_v = jax.random.normal(keys[2], (num_pages, pg, kv, d),
                               cfg.activation_dtype)
    at = {pos: jnp.int32(pos) for pos in starts}    # on the device, once
    chain = 2 if args.tiny else 16
    kernel = jax.jit(lambda q, k, v, row, start: jax.lax.fori_loop(
        0, chain, lambda _, x: pa.paged_chunk_attention(x, k, v, row, start),
        q))
    for pos in starts:
        ms = _time(lambda: kernel(q, pool_k, pool_v, table, at[pos]),
                   reps) / chain
        flops = 4.0 * h * d * (C * pos + C * (C + 1) / 2)
        print(json.dumps({
            "metric": "paged_chunk_attention_ms", "pos": pos,
            "ms": round(ms, 4), "gflop": round(flops / 1e9, 2),
            "share_of_peak": peak and round(flops / (ms * 1e-3) / peak, 4)}),
            flush=True)


if __name__ == "__main__":
    main()
