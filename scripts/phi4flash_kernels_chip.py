"""ISSUE 47 on the chip, beside the benchmark and editing none of it: what the
selective-scan kernel, the one-token step and the decode step's attention
calls cost, read off a trace of the reasoning cell's OWN programs at the
cell's sizes.

    python3 scripts/phi4flash_kernels_chip.py --seed <n> [--parts decode]

Builds the cell's engine as the benchmark does (weights from the seed, the
``BatchingSpec`` of the traffic file; no reference, no server) and traces

1. the decode step (``paged._paged_decode_step``, the program ``correct``
   drives) over all 32 slots, a token of its own each, at contexts of 512,
   2048 and 8192, every slot on pages of its own (its first page from the
   first pages' ids, its ring's from the ring's): per step the EIGHT calls
   of ``paged_decode_attention`` (the full layer and the seven cross layers
   over ONE layer's rows) and the eight of the window form, each beside its
   bytes at the bus's peak; the nine Mamba layers' one-token step is XLA's
   (gather, step, scatter: ``top_ops`` has its fusions), beside the bytes a
   step's states are (``counts.ssm_step_bytes``);
2. the engine's two-row program over rows at starts 0 (the states from
   zeros) and 7680 (read from their entries), with an end (the tail at one
   position a row) and without (no tail at all), and the one-row ``[C, V]``
   program the harness's ``correct`` drives (the tail at every position):
   per call the nine ``ssm_scan`` beside their bytes at the bus's peak
   (``counts.ssm_scan_bytes``) and their exponentials a second
   (``counts.ssm_scan_elements`` over the call's time), the chunk attention
   calls, and the program's heaviest instructions.

One JSON line a part, times in milliseconds a call (mean over the traced
calls; ``scripts/exaone_kernels_chip.py::traced``). ``--tiny`` rehearses it on
the CPU at the tiny preset (no device plane: the parts print their shapes
alone).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "phi-4-mini-flash.batch-reasoning"
OPS = {"ssm_scan": r"^%?ssm_scan[.\d]* =",
       "global_decode": r"^%?paged_decode_attention[.\d]* =",
       "window_decode": r"^%?paged_window_decode_attention[.\d]* =",
       "global_chunk": r"^%?paged_chunk_attention[.\d]* =",
       "window_chunk": r"^%?paged_window_chunk_attention[.\d]* ="}
BUS = 819e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny preset")
    ap.add_argument("--parts", default="decode,chunk")
    args = ap.parse_args(argv)

    from benchmark import architecture, device
    from benchmark import manifest as mf
    from benchmark.weights import make_params
    from scripts.exaone_kernels_chip import traced

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-phi4flash.json")
        traffic = mf.load_traffic("rehearsal-closed-ssm")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.paged import _paged_decode_step, context_bucket

    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, BatchingSpec(**traffic["engine"]), params=params,
                    seed=args.seed & 0x7FFFFFFF)
    slots, mpp, ring = eng.num_slots, eng._mpp, eng._ring
    C, pg = eng.chunk_size, eng.page_size
    # every slot on pages of its own, as the allocator hands them: the first
    # from the first pages' ids, the ring's others from the ring's, the rest
    # from above
    table = np.zeros((slots, mpp), np.int32)
    for b in range(slots):
        table[b, 0] = b
        table[b, 1:ring] = slots + b * (ring - 1) + np.arange(ring - 1)
        table[b, ring:] = slots * ring + b * (mpp - ring) \
            + np.arange(mpp - ring)
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

    contexts = (C, mpp * pg) if args.tiny else (512, 2048, 8192)
    for context in contexts if "decode" in args.parts else ():
        window = min(context, conf["sliding_window"])
        print(json.dumps({
            "part": "decode_step", "context": context, "slots": slots,
            "weights_ms_at_the_bus": round(
                1e3 * counts.decode_weight_bytes(conf, 2) / BUS, 3),
            "ssm_steps_ms_at_the_bus": round(
                1e3 * 9 * counts.ssm_step_bytes(conf, slots) / BUS, 4),
            "global_decode_ms_at_the_bus": round(
                1e3 * counts.decode_attention_bytes(
                    conf, slots * context, 2) / BUS, 4),
            "window_decode_ms_at_the_bus": round(
                1e3 * counts.decode_attention_bytes(
                    conf, slots * window, 2) / BUS, 4),
            **traced(decode_at(context), args.calls, OPS, top=16)}),
            flush=True)

    block = rng.integers(3, conf["vocab_size"], (2, C)).astype(np.int32)
    long_ = contexts[-1]

    def rows_program(start: int, ends: bool):
        packed = tuple(map(jnp.asarray, eng._programs.pack(
            [(block[r], table[r], start, ends) for r in range(2)], 2)))

        def run():
            logits, eng.cache = eng._programs.ask("rows")(
                eng.params, eng.cache, *packed, mpp)
            return logits
        return run

    def one_row(start: int):
        def run():
            logits, eng.cache = eng._paged_chunk(
                eng.params, eng.cache, jnp.asarray(block[:1]),
                jnp.asarray(table[0]), jnp.int32(start), jnp.int32(C),
                context_bucket(start, C, pg, mpp))
            return logits
        return run

    programs = [("rows[2]", 2, start, ends, rows_program(start, ends))
                for start in (0, long_ - C) for ends in (False, True)] \
        + [("chunk[1] all positions", 1, long_ - C, True, one_row(long_ - C))]
    for name, n, start, ends, run in programs if "chunk" in args.parts \
            else ():
        out = traced(run, args.calls, OPS, top=24)
        line = {"part": name, "rows": n, "start": start,
                "some_row_ends": ends,
                "ssm_scan_ms_at_the_bus": round(
                    1e3 * counts.ssm_scan_bytes(conf, n * C, n) / BUS, 4)}
        if "ssm_scan" in out:
            line["ssm_scan_exponentials_per_s"] = round(
                counts.ssm_scan_elements(conf, n * C)
                / (out["ssm_scan"][1] * 1e-3))
        print(json.dumps({**line, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
