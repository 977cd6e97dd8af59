"""What GLM-5's indexer, selection and masked latent attention cost on the
chip at the cell's sizes, and the proof that the comparison which decides
``correct`` sees the selection.

    python3 scripts/glm5_kernels_chip.py --seed <n> [--parts kernels,blind]

``kernels``: ``ops/paged_attention.py`` alone at the cell's shapes (64 heads
over 640-wide rows, 32 index heads of 128, pages of 128, a table row of 98
pages), over a pool of 16 contexts each on pages of its own. The decode side
at 16 rows x 8k and 12k of context: ``paged_index_scores`` (one query a row),
``dsa_select`` (the sixteen queries one tile), ``paged_latent_decode_attention``
with the selection as a second mask beside the same kernel without one, and
the OTHER way to attend to a selection, in XLA: ``lax.top_k`` indices, the
2048 rows a stream gathered, dense attention over them (ISSUE 55: the builder
reads both and keeps the faster). The chunk side at starts 3584, 7680 and
11776 (contexts of 4k, 8k, 12k): the three kernels for one row of 512
queries, and the selection's two exact forms in XLA beside the kernel
(``layers.select_keys``, the threshold by counting; ``lax.top_k`` and a
scatter). Each beside its floor: the index keys' bytes at the bus's peak or
the products at the matrix unit's, the selected rows' bytes, the selected
pairs' operations.

``blind``: the comparison's own two sequences through the engine's programs
against the float32 reference on the chip: the program's numbers, then the
reference computed in float8, with the
selection replaced by the most recent 2048 positions, and with the index
keys zeroed, each against the sound float32 reference
(``reference.logits(selection=)``): what the comparison reads beside a
program that lacks the mechanism. The three controls must read OVER the
configuration's limits, the program under them.

One JSON line a reading; times in milliseconds a call
(``scripts/exaone_kernels_chip.py::traced``). ``--tiny`` rehearses it on the
CPU at the tiny preset (no device plane: the parts print their shapes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "glm-5.batch-agentcontext"
OPS = {"index_scores": r"^%?paged_index_scores[.\d]* =",
       "select": r"^%?dsa_select[.\d]* =",
       "decode_attention": r"^%?paged_latent_decode_attention[.\d]* =",
       "chunk_attention": r"^%?paged_latent_chunk_attention[.\d]* ="}
BUS, PEAK = 819e9, 197e12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny preset")
    ap.add_argument("--parts", default="kernels,blind")
    args = ap.parse_args(argv)
    parts = set(args.parts.split(","))

    from benchmark import architecture, correctness, device, reference
    from benchmark import manifest as mf
    from benchmark.weights import make_params
    from scripts.exaone_kernels_chip import traced

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-glm5.json")
        traffic = mf.load_traffic("rehearsal-closed-dsa")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models import layers as L
    from kubeflow_tpu.ops import paged_attention as PA
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    batching = BatchingSpec(**traffic["engine"])
    pg, C = batching.page_size, batching.chunked_prefill_tokens
    mpp, slots = batching.max_seq_len // pg, batching.max_batch_size
    topk = cfg.index_topk
    rng = np.random.default_rng(args.seed & 0x7FFFFFFF)
    dt = cfg.activation_dtype

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    if "kernels" in parts:
        h, w = cfg.n_heads, L.latent_row_width(cfg)
        hi, di = cfg.index_heads, cfg.index_head_dim
        pages = slots * mpp
        key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)
        ks = jax.random.split(key, 8)
        pool = jax.random.normal(ks[0], (pages, pg, w), jnp.float32
                                 ).astype(dt)
        idx = jax.random.normal(ks[1], (pages, pg, di), jnp.float32
                                ).astype(dt)
        table = jnp.asarray(np.arange(pages, dtype=np.int32).reshape(
            slots, mpp))
        sm = L.latent_scale(cfg)
        contexts = (2 * C, 3 * C) if args.tiny else (8192, 12288)
        for context in contexts:
            lens = jnp.full((slots,), context - 1, jnp.int32)
            q = jax.random.normal(ks[2], (slots, h, w), jnp.float32
                                  ).astype(dt)
            qi = jax.random.normal(ks[3], (slots, 1, hi, di), jnp.float32
                                   ).astype(dt)
            wi = jax.random.normal(ks[4], (slots, 1, hi), jnp.float32)

            @jax.jit
            def masked(q, qi, wi, pool, idx, table, lens):
                scores = PA.paged_index_scores(qi, wi, idx, table, lens)
                sel = jnp.swapaxes(PA.paged_select_keys(
                    jnp.swapaxes(scores, 0, 2), jnp.max(lens)[None], topk),
                    0, 2)[:, :, 0]
                return PA.paged_latent_decode_attention(
                    q, pool, table, lens, sm_scale=sm, selected=sel)

            @jax.jit
            def unmasked(q, pool, table, lens):
                return PA.paged_latent_decode_attention(
                    q, pool, table, lens, sm_scale=sm)

            @jax.jit
            def gathered(q, qi, wi, pool, idx, table, lens):
                """The other way: top_k indices, the selected rows copied,
                dense attention over them."""
                scores = PA.paged_index_scores(qi, wi, idx, table, lens)
                _, at = jax.lax.top_k(scores[:, 0], min(topk, context))
                page = jnp.take_along_axis(table, at // pg, axis=1)
                rows = pool[page, at % pg]                   # [B, k, W]
                s = jnp.einsum("bhw,bkw->bhk", q, rows,
                               preferred_element_type=jnp.float32) * sm
                p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
                return jnp.einsum("bhk,bkw->bhw", p, rows)

            sel_rows = slots * min(topk, context)
            emit(part="decode_kernels", context=context, rows=slots,
                 index_keys_ms_at_the_bus=round(1e3 * counts.index_scores_bytes(
                     conf, slots * context, 2) / BUS, 4),
                 selected_rows_ms_at_the_bus=round(
                     1e3 * counts.latent_decode_bytes(conf, sel_rows, 2)
                     / BUS, 4),
                 all_rows_ms_at_the_bus=round(
                     1e3 * counts.latent_decode_bytes(
                         conf, slots * context, 2) / BUS, 4),
                 masked=traced(lambda: masked(q, qi, wi, pool, idx, table,
                                              lens), args.calls, OPS),
                 unmasked=traced(lambda: unmasked(q, pool, table, lens),
                                 args.calls, OPS),
                 gathered=traced(lambda: gathered(q, qi, wi, pool, idx,
                                                  table, lens),
                                 args.calls, OPS, top=6))
        starts = (C, 2 * C) if args.tiny else (3584, 7680, 11776)
        for start in starts:
            bucket = min(mpp, 1 << max(0, (-(-(start + C) // pg) - 1)
                                       ).bit_length())
            row = table[:1, :bucket]
            st = jnp.asarray([start], jnp.int32)
            q = jax.random.normal(ks[5], (h, C, w), jnp.float32).astype(dt)
            qi = jax.random.normal(ks[6], (1, C, hi, di), jnp.float32
                                   ).astype(dt)
            wi = jax.random.normal(ks[7], (1, C, hi), jnp.float32)
            tile = min(C, PA.INDEX_QUERY_TILE)

            @jax.jit
            def chunk(q, qi, wi, pool, idx, row, st):
                scores = PA.paged_index_scores(qi, wi, idx, row, st)
                last = st[0] + tile * (1 + jnp.arange(C // tile)) - 1
                sel = PA.paged_select_keys(scores, last, topk)
                return PA.paged_latent_chunk_attention(
                    q, pool, row[0], st[0], sm_scale=sm, selected=sel)

            @jax.jit
            def chunk_unmasked(q, pool, row, st):
                return PA.paged_latent_chunk_attention(
                    q, pool, row[0], st[0], sm_scale=sm)

            @jax.jit
            def by_counting(qi, wi, idx, row, st):
                return L.select_keys(PA.paged_index_scores(
                    qi, wi, idx, row, st), topk)

            @jax.jit
            def by_sorting(qi, wi, idx, row, st):
                scores = PA.paged_index_scores(qi, wi, idx, row, st)[0]
                _, at = jax.lax.top_k(scores, min(topk, scores.shape[1]))
                return jnp.zeros(scores.shape, jnp.bool_).at[
                    jnp.arange(C)[:, None], at].set(True)

            pairs = counts.visible_pairs(C, start)
            chosen = counts.selected_pairs(conf, C, start)
            emit(part="chunk_kernels", start=start, bucket_pages=bucket,
                 index_ms_at_the_peak=round(
                     1e3 * counts.index_scores_flops(conf, pairs) / PEAK, 4),
                 selected_pairs_ms_at_the_peak=round(
                     1e3 * counts.latent_chunk_attention_flops(conf, chosen)
                     / PEAK, 4),
                 visible_pairs_ms_at_the_peak=round(
                     1e3 * counts.latent_chunk_attention_flops(conf, pairs)
                     / PEAK, 4),
                 masked=traced(lambda: chunk(q, qi, wi, pool, idx, row, st),
                               args.calls, OPS),
                 unmasked=traced(lambda: chunk_unmasked(q, pool, row, st),
                                 args.calls, OPS),
                 select_by_counting_in_xla=traced(
                     lambda: by_counting(qi, wi, idx, row, st), args.calls,
                     OPS, top=4),
                 select_by_top_k_in_xla=traced(
                     lambda: by_sorting(qi, wi, idx, row, st), args.calls,
                     OPS, top=4))
        del pool, idx

    if "blind" not in parts:
        return 0
    spec = conf["correctness"]
    ref = architecture.part(conf, "reference")
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, batching, params=params, seed=args.seed & 0x7FFFFFFF)
    got = correctness.engine_side(eng, conf, spec, args.seed)
    want = correctness.reference_side(params, conf, spec, args.seed, C)
    emit(part="blind", side="program", limits=spec["limits"],
         **correctness.compare_sides(got, want, spec, C))
    del got, eng

    def control(quant=None, selection="indexer"):
        fn = jax.jit(lambda p, t, last: ref.logits(
            p, t, conf, quant or reference.same, last=last,
            selection=selection), static_argnums=2)
        with jax.default_matmul_precision("highest"):
            return [fn(params, jnp.asarray(toks),
                       correctness.last_chunk_len(plen, C) + n_dec)
                    for toks, plen, n_dec in correctness.sample_sequences(
                        spec, args.seed, conf["vocab_size"])]

    for side, kw in (
            ("reference in float8", {"quant": reference.fp8_round_trip}),
            ("reference selecting the most recent keys",
             {"selection": "recent"}),
            ("reference with the index keys zeroed",
             {"selection": "keys_zeroed"})):
        emit(part="blind", side=side,
             **correctness.compare_sides(control(**kw), want, spec, C))
    return 0


if __name__ == "__main__":
    sys.exit(main())
