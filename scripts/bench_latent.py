#!/usr/bin/env python3
"""Chip microbenchmarks behind three choices of the latent / sorted-expert
path (PERF.md, PR 28 records the readings), at GLM-4.7-Flash's widths:

  python3 scripts/bench_latent.py            # on the TPU; prints JSON lines

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
- decode: the paged latent decode kernel against the gather form, 16 slots
  with 4k-16k of context each, and the kernel's share of the memory bus.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.ops.attention import causal_mask, multi_head_attention
from kubeflow_tpu.ops.paged_attention import (
    paged_latent_chunk_attention, paged_latent_decode_attention,
)
from kubeflow_tpu.serve.paged import paged_gather

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


def pool_and_table(slots, lengths, pages=2080, pg=128, mpp=130, layers=7):
    """The cell's pool viewed flat, and page tables into layer 3's pages."""
    w = L.latent_row_width(CFG)
    pool = normal(jax.random.PRNGKey(2), (layers * pages, pg, w))
    rng = np.random.default_rng(0)
    table = np.full((slots, mpp), -1, np.int32)
    free, at = rng.permutation(pages), 0
    for b in range(slots):
        n = int(lengths[b]) // pg + 1
        table[b, :n] = 3 * pages + free[at:at + n]
        at += n
    return pool, jnp.asarray(table)


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


def decode():
    h, slots = CFG.n_heads, 16
    w = L.latent_row_width(CFG)
    lengths = np.random.default_rng(0).integers(4096, 16384, slots).astype(
        np.int32)
    pool, table = pool_and_table(slots, lengths)
    q = normal(jax.random.PRNGKey(3), (slots, h, w))
    lens = jnp.asarray(lengths)
    scale = L.latent_scale(CFG)

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

    need = float(lengths.sum() + slots) * w * 2       # bytes of rows read
    for name, fn in (("kernel", kernel), ("gather", gather)):
        s = timed(fn, q, pool, n=50)
        emit(bench="decode", form=name, ms=s * 1e3,
             context_tokens=int(lengths.sum()),
             bus_share_pct=100 * need / 819e9 / s)
    diff = jnp.abs(jax.jit(kernel)(q, pool).astype(jnp.float32)
                   - jax.jit(gather)(q, pool).astype(jnp.float32))
    emit(bench="decode", max_abs_diff=float(diff.max()))


if __name__ == "__main__":
    emit(device=jax.devices()[0].device_kind, platform=jax.default_backend())
    for part in (experts, chunk, decode):
        if len(sys.argv) < 2 or part.__name__ in sys.argv[1:]:
            part()
