"""ISSUE 43 on the chip, beside the benchmark and editing none of it: what the
two gated delta-rule kernels and the XLA part of the chunked form cost, read
off a trace of the long-document cell's OWN programs at the cell's sizes.

    python3 scripts/solar_kernels_chip.py --seed <n> [--root .parent]
        [--parts decode]

``--root DIR`` takes ``kubeflow_tpu`` and ``benchmark`` from another checkout
(``git archive <parent> | tar -x -C .parent``), so that one call to the chip
reads the parent and the change one after the other:
``... --root .parent --parts decode; ... --parts decode``.

Builds the cell's engine as the benchmark does (weights from the seed, the
``BatchingSpec`` of the traffic file; no reference, no server) and traces

1. the decode step (``paged._paged_decode_step``, the program ``correct``
   drives) over all 32 slots, a token of its own each, at contexts of 512,
   4096 and 16384 (the GQA call's time at the first is what a call costs
   whatever it reads, the slope between the other two a live page's rate:
   PR 45), every slot on pages of its own with its first page (its
   state's entry) from the entries' ids: per call the three ``kda_step`` (a
   stream's [64, 128, 128] float32 state in and out where it lies), the GQA
   layer's ``paged_decode_attention`` and the grouped matmuls, each beside
   its bytes at the bus's peak;
2. the engine's two-row chunk program at starts 0 (the state from zeros) and
   15872 (the state read from its entry): per call the three ``kda_chunk``
   and, in front of each, the ``kda_operands`` call that computes what the
   scan takes (PR 44; beside the bytes it reads and writes at the bus's
   peak), ``paged_chunk_attention`` and the grouped matmuls, and the
   program's heaviest instructions, so that PERF.md section 5 can say which
   of convolution, norms, gates, the blocks' solves and the scan the time is
   in.

One JSON line a part, times in milliseconds a call (mean over the traced
calls; ``scripts/exaone_kernels_chip.py::traced``). ``--tiny`` rehearses it on
the CPU at the tiny-solar preset (no device plane: the parts print their
shapes alone).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "solar-open2-250b.batch-longdoc"
OPS = {"kda_step": r"^%?kda_step[.\d]* =",
       "kda_chunk": r"^%?kda_chunk[.\d]* =",
       "kda_operands": r"^%?kda_operands[.\d]* =",
       "gqa_decode": r"^%?paged_decode_attention[.\d]* =",
       "gqa_chunk": r"^%?paged_chunk_attention[.\d]* =",
       "gmm": r"^%?gmm[.\d]* ="}


def operands_bytes(cfg, tokens: int) -> int:
    """What one ``kda_operands`` call moves for ``tokens`` positions of one
    layer, float32: q, k, v, g and beta in; qg, w, ut, a block's [T, T]
    ``bm``, ``kdt`` and a block's ``gt`` out."""
    from kubeflow_tpu.ops.kda import BLOCK

    h, dk = cfg.linear_heads, cfg.linear_head_dim
    return 4 * tokens * h * (4 * dk + 1 + 4 * dk + BLOCK) \
        + 4 * (tokens // BLOCK) * h * dk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny-solar preset")
    ap.add_argument("--root", default=None,
                    help="another checkout to take the program from")
    ap.add_argument("--parts", default="decode,chunk")
    args = ap.parse_args(argv)
    if args.root:
        sys.path.insert(0, os.path.abspath(args.root))
    side = {"root": args.root or "."}

    from benchmark import architecture, device
    from benchmark import manifest as mf
    from benchmark.weights import make_params
    from scripts.exaone_kernels_chip import traced

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-solar.json")
        traffic = mf.load_traffic("rehearsal-closed-state")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.paged import _paged_decode_step

    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, BatchingSpec(**traffic["engine"]), params=params,
                    seed=args.seed & 0x7FFFFFFF)
    slots, mpp = eng.num_slots, eng._mpp
    C, pg = eng.chunk_size, eng.page_size
    # every slot on pages of its own: its first from the entries' ids (its
    # own slot's number), the rest from above them
    table = np.zeros((slots, mpp), np.int32)
    for b in range(slots):
        table[b, 0] = b
        table[b, 1:] = slots + b * (mpp - 1) + np.arange(mpp - 1)
    assert table.max() < eng._num_pages
    dcfg, impl = eng._cfg_decode, eng.paged_attn_impl
    step = jax.jit(lambda p, c, tbl, t, ln, lv: _paged_decode_step(
        p, {**c, "table": tbl}, t, ln, lv, dcfg, attn_impl=impl),
        donate_argnums=(1,))
    live = jnp.ones((slots,), bool)
    rng = np.random.default_rng(args.seed)
    tok = jnp.asarray(rng.integers(3, conf["vocab_size"], slots).astype(
        np.int32))

    def decode_at(context: int):
        lens = jnp.full((slots,), context - 1, jnp.int32)
        tbl = jnp.asarray(np.where(
            np.arange(mpp)[None, :] < -(-context // pg), table, -1))

        def run():
            lg, cache = step(eng.params, eng.cache, tbl, tok, lens, live)
            cache.pop("table", None)
            eng.cache = eng._pin(cache)
            return lg
        return run

    contexts = (C, mpp * pg) if args.tiny else (512, 4096, 16384)
    long_ = contexts[-1]
    bus = 819e9
    for context in contexts if "decode" in args.parts else ():
        print(json.dumps({
            **side, "part": "decode_step", "context": context,
            "slots": slots,
            "kda_step_ms_at_the_bus": round(
                1e3 * counts.kda_step_bytes(conf, slots) / bus, 4),
            "gqa_decode_ms_at_the_bus": round(
                1e3 * counts.decode_attention_bytes(
                    conf, slots * context, 2) / bus, 4),
            **traced(decode_at(context), args.calls, OPS, top=12)}),
            flush=True)

    block = rng.integers(3, conf["vocab_size"], (2, C)).astype(np.int32)
    rows_program = eng._programs.ask("rows")
    for start in (0, long_ - C) if "chunk" in args.parts else ():
        packed = tuple(map(jnp.asarray, eng._programs.pack(
            [(block[r], table[r], start, True) for r in range(2)], 2)))

        def run():
            logits, eng.cache = rows_program(
                eng.params, eng.cache, *packed, mpp)
            return logits
        print(json.dumps({
            **side, "part": "chunk_program", "rows": 2, "start": start,
            "kda_operands_ms_at_the_bus": round(
                1e3 * operands_bytes(cfg, 2 * C) / bus, 4),
            "kda_chunk_ms_at_the_bus": round(
                1e3 * counts.kda_chunk_bytes(conf, 2 * C, 2) / bus, 4),
            "kda_chunk_ms_at_the_peak": round(
                1e3 * counts.kda_chunk_flops(conf, 2 * C) / 197e12, 4),
            **traced(run, args.calls, OPS, top=30)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
