#!/usr/bin/env python3
"""Chip microbenchmarks behind three choices of the latent / sorted-expert
path (PERF.md, PR 28 records the readings), at GLM-4.7-Flash's widths:

  python3 scripts/bench_latent.py [part ...] [--root .parent] [--tiny]
                                  # on the TPU; prints JSON lines

``--root DIR`` takes ``kubeflow_tpu`` from another checkout (``git archive
<parent> | tar -x -C .parent``), so that one call to the chip times the
parent's kernels and the change's one after the other.

- experts: the drop-free expert layer at a chunk's 512 tokens and a decode
  step's 16, as rows sorted into a grouped matmul ("sorted": the Pallas
  kernel at whole row tiles, else ``ragged_dot``; "sorted-ragged_dot":
  ``ragged_dot`` always), as the capacity buffers with a capacity that
  cannot overflow (C = T: "buffers") and as every expert for every token
  ("dense").
- chunk: a 512-token chunk's attention over its slot's cached rows: the
  paged kernel (absorbed, pages where they lie, blocks behind the chunk
  skipped) against XLA over the gathered rows, absorbed and re-expanded per
  head, at 4k, 10k and 16k of context under the engine's page buckets.
- decode: ``paged_latent_decode_attention`` alone at the longctx cell's
  shape (16 rows of a 130-page table over the flat 7 x 2080-page pool), 12
  rows at contexts drawn from 2048-12288 beside 4 idle ones (the cell's own
  mix), then every row at 4k, 10k and 16k and the same with 4 rows idle: the
  kernel's device time a call (the trace's own op events) beside the live
  pages' bytes at the bus's peak, and its largest difference from the
  gathered form.
- packed: ``paged_packed_decode_attention`` the same way at the longanswer
  cell's shape (64 rows of a 25-page table over 2 x 1600 pages, 32 heads over
  8 KV heads of 64): contexts drawn from 512-3072, and every row at 3072.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if "--root" in sys.argv:                # before anything of the program's
    ROOT = sys.argv[sys.argv.index("--root") + 1]
    sys.path.insert(0, os.path.abspath(ROOT))
    del sys.argv[sys.argv.index("--root"):sys.argv.index("--root") + 2]
else:
    ROOT = "."
# --tiny: the decode and packed parts at a sixteenth of the contexts and
# pools, a rehearsal on the CPU (the kernels interpreted, no device time)
TINY = 16 if "--tiny" in sys.argv else 1
sys.argv = [a for a in sys.argv if a != "--tiny"]

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.ops.attention import causal_mask, multi_head_attention
from kubeflow_tpu.ops.paged_attention import (
    paged_latent_chunk_attention, paged_latent_decode_attention,
    paged_packed_decode_attention,
)
from kubeflow_tpu.serve.paged import _decode_attention, paged_gather
from scripts.exaone_kernels_chip import traced

BF16 = jnp.bfloat16
CFG = preset("glm-4.7-flash", n_layers=7, dtype="bfloat16",
             param_dtype="bfloat16")


def timed(fn, *args, n=20):
    """Seconds a call of ``fn`` jitted (compiled once, before the clock)."""
    fn = jax.jit(fn)
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n


def emit(**row):
    print(json.dumps(row), flush=True)


def normal(key, shape, scale=1.0, dtype=BF16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def experts():
    d, m, e, k = CFG.hidden, CFG.expert_mlp_dim, CFG.num_experts, \
        CFG.experts_per_token
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    p = {"router": normal(ks[0], (d, e), d ** -0.5),
         "router_bias": 0.05 * jax.random.normal(ks[1], (e,)),
         "gate": normal(ks[2], (e, d, m), d ** -0.5),
         "up": normal(ks[3], (e, d, m), d ** -0.5),
         "down": normal(ks[4], (e, m, d), m ** -0.5),
         "shared": {"gate": normal(ks[5], (d, m), d ** -0.5),
                    "up": normal(ks[6], (d, m), d ** -0.5),
                    "down": normal(ks[7], (m, d), m ** -0.5)}}
    for tokens in (512, 16):
        x = normal(jax.random.PRNGKey(tokens), (1, tokens, d))
        for name, over in (("sorted", {"moe_impl": "sorted"}),
                           ("sorted-ragged_dot", {"moe_impl": "sorted",
                                                  "fused_kernels": "off"}),
                           ("buffers", {"moe_impl": "dispatch",
                                        "capacity_factor": e / k}),
                           ("dense", {"moe_impl": "dense"})):
            cfg = dataclasses.replace(CFG, **over)
            emit(bench="experts", tokens=tokens, impl=name,
                 ms=timed(lambda p, x, cfg=cfg: L.moe_block(p, x, cfg)[0],
                          p, x) * 1e3)


def page_table(lengths, pages, mpp, pg=128, layer=0):
    """A table row a context: ``length // pg + 1`` pages of ``layer``'s,
    scattered over its ``pages``; a length below 0 is an idle row."""
    table = np.full((len(lengths), mpp), -1, np.int32)
    free, at = np.random.default_rng(0).permutation(pages), 0
    for b, length in enumerate(lengths):
        n = int(length) // pg + 1 if length >= 0 else 0
        table[b, :n] = layer * pages + free[at:at + n]
        at += n
    return jnp.asarray(table)


def latent_pool(pages=2080, pg=128, layers=7):
    """The longctx cell's pool viewed flat."""
    return normal(jax.random.PRNGKey(2),
                  (layers * pages, pg, L.latent_row_width(CFG)))


def pool_and_table(slots, lengths, pages=2080, pg=128, mpp=130):
    """That pool, and page tables into layer 3's pages."""
    return latent_pool(pages, pg), page_table(lengths[:slots], pages, mpp,
                                              pg, layer=3)


def chunk():
    h, r, rope = CFG.n_heads, CFG.kv_lora_rank, CFG.qk_rope_dim
    nope, c = CFG.qk_nope_dim, 512
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    a = {"wkvb": normal(ks[0], (r, h, nope + CFG.v_head_dim), r ** -0.5)}
    q_nope = normal(ks[1], (1, c, h, nope))
    q_rope = normal(ks[2], (1, c, h, rope))
    scale = L.latent_scale(CFG)

    def kernel(a, q_nope, q_rope, pool, row, start):
        q = L.latent_query(a, q_nope[0], q_rope[0], CFG)
        o = paged_latent_chunk_attention(jnp.swapaxes(q, 0, 1), pool, row,
                                         start, sm_scale=scale)
        return L.latent_output(a, jnp.swapaxes(o, 0, 1), CFG)

    def absorbed(a, q_nope, q_rope, pool, row, start):
        rows = paged_gather(pool, row[None])
        mask = causal_mask(c, rows.shape[1], q_offset=start)
        return L.latent_absorbed_attention(a, q_nope, q_rope, rows,
                                           mask[None, None], CFG)

    def expanded(a, q_nope, q_rope, pool, row, start):
        rows = paged_gather(pool, row[None])
        kv = jnp.einsum("bsr,rhk->bshk", rows[..., :r], a["wkvb"])
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                rows[:, :, None, r:r + rope], (*kv.shape[:3], rope))], -1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return multi_head_attention(q, k, kv[..., nope:], causal=True,
                                    q_offset=start)

    # (context the chunk ends at, pages of the table: the engine's bucket)
    for ctx, bucket in ((4096, 32), (10240, 128), (16384, 128)):
        pool, table = pool_and_table(1, [ctx])
        row, start = table[0, :bucket], jnp.int32(ctx - c)
        for name, fn in (("kernel", kernel), ("absorbed", absorbed),
                         ("expanded", expanded)):
            emit(bench="chunk", context=ctx, bucket_pages=bucket, form=name,
                 ms=timed(fn, a, q_nope, q_rope, pool, row, start) * 1e3)


def decode_cases(slots, contexts, idle):
    """(name, lengths): every row at each context, and the same with the
    last ``idle`` rows idle (length -1, no page)."""
    for ctx in contexts:
        full = np.full(slots, ctx - 1, np.int32)
        yield f"{slots}x{ctx}", full
        yield f"{slots - idle}x{ctx}+{idle}idle", np.where(
            np.arange(slots) < slots - idle, full, -1).astype(np.int32)


def kernel_against_gather(bench, name, kernel, gather, args, lengths,
                          table, page_bytes, op):
    """One JSON line: the kernel's device time a call beside its live
    pages' bytes at the bus's peak, and how far it is from ``gather``."""
    fn = jax.jit(kernel)
    found = traced(lambda: fn(*args), 20, {"kernel": op})
    live = np.asarray(lengths) >= 0
    diff = jnp.abs(fn(*args).astype(jnp.float32)
                   - jax.jit(gather)(*args).astype(jnp.float32))
    need = int((np.asarray(table) >= 0).sum()) * page_bytes
    row = {"bench": bench, "root": ROOT, "case": name,
           "live_pages": need // page_bytes,
           "ms_at_bus_peak": round(1e3 * need / 819e9, 4),
           "max_abs_diff": float(diff[live].max())}
    if "kernel" in found:               # none on the CPU: no device plane
        row["ms"] = found["kernel"][1]
        row["bus_share_pct"] = round(
            100 * row["ms_at_bus_peak"] / row["ms"], 1)
    emit(**row)


def decode():
    h, slots, pg = CFG.n_heads, 16, 128
    w = L.latent_row_width(CFG)
    q = normal(jax.random.PRNGKey(3), (slots, h, w))
    scale = L.latent_scale(CFG)
    drawn = np.random.default_rng(1).integers(
        2048 // TINY, 12288 // TINY, slots).astype(np.int32)
    drawn[-4:] = -1                     # the cell: 12 of 16 slots live
    pool = latent_pool(2080 // TINY)
    for name, lengths in [("12x2048-12288+4idle", drawn), *decode_cases(
            slots, [c // TINY for c in (4096, 10240, 16384)], 4)]:
        table = page_table(lengths, 2080 // TINY, 130, pg, layer=3)
        lens = jnp.asarray(lengths)

        def kernel(q, pool):
            return paged_latent_decode_attention(q, pool, table, lens,
                                                 sm_scale=scale)

        def gather(q, pool):
            rows = paged_gather(pool, table)
            s = jnp.einsum("bhw,bsw->bhs", q, rows,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.arange(rows.shape[1])[None, :] <= lens[:, None]
            p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
            return jnp.einsum("bhs,bsw->bhw", p.astype(rows.dtype), rows)

        kernel_against_gather(
            "decode", name, kernel, gather, (q, pool), lengths, table,
            pg * w * 2, r"^%?paged_latent_decode_attention[.\d]* =")


def packed():
    slots, h, kv, d, pg, mpp = 64, 32, 8, 64, 128, 25
    pages = 1600 if TINY == 1 else 200
    drawn = np.random.default_rng(1).integers(
        512 // TINY, 3072 // TINY, slots).astype(np.int32)
    pool_k, pool_v = (normal(jax.random.PRNGKey(i), (2 * pages, pg, kv * d))
                      for i in (4, 5))
    q = normal(jax.random.PRNGKey(6), (slots, 1, h, d))
    for name, lengths in [("64x512-3072", drawn),
                          *decode_cases(slots, (3072 // TINY,), 16)]:
        table = page_table(lengths, pages, mpp, pg, layer=1)
        lens = jnp.asarray(lengths)

        def kernel(q, pool_k, pool_v):
            return paged_packed_decode_attention(q, pool_k, pool_v, table,
                                                 lens, kv)

        def gather(q, pool_k, pool_v):
            k, v = (paged_gather(pool, table).reshape(slots, -1, kv, d)
                    for pool in (pool_k, pool_v))
            return _decode_attention(q, k, v, lens, CFG)

        kernel_against_gather(
            "packed", name, kernel, gather, (q, pool_k, pool_v), lengths,
            table, 2 * pg * kv * d * 2,
            r"^%?paged_packed_decode_attention[.\d]* =")


if __name__ == "__main__":
    emit(device=jax.devices()[0].device_kind, platform=jax.default_backend())
    for part in (experts, chunk, decode, packed):
        if len(sys.argv) < 2 or part.__name__ in sys.argv[1:]:
            part()
