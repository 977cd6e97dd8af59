"""ISSUE 40 on the chip, beside the benchmark and editing none of it: what a
window layer's calls and a held share's grouped matmuls cost, read off a
trace of the mixed-length cell's OWN programs at the cell's sizes.

    python3 scripts/exaone_kernels_chip.py --seed <n> [--root .parent]
        [--parts decode]

``--root DIR`` takes ``kubeflow_tpu`` and ``benchmark`` from another checkout
(``git archive <parent> | tar -x -C .parent``), so that one call to the chip
reads the parent and the change one after the other.

Builds the cell's engine as the benchmark does (weights from the seed, the
``BatchingSpec`` of the traffic file; no reference, no server) and traces

1. the decode step (``paged._paged_decode_step``, the program ``correct``
   drives) over all 32 slots, a token of its own each, at contexts of 512,
   4096 and 8192 (what a call costs whatever it reads, and a live page's
   rate: PR 45), every slot on pages of its own with its first pages from the
   ring's ids and the pages its context has not reached unmapped: per call the
   global layer's ``paged_decode_attention``, the window layers'
   ``paged_window_decode_attention`` (its grid is two pages a stream whatever
   the context) and the expert layers' grouped matmuls;
2. the engine's two-row chunk program (``[2, V]`` logits: the head at each
   row's last valid position, here with both rows' wanted, PR 41) at a short
   and at a long start: per call ``paged_chunk_attention``,
   ``paged_window_chunk_attention`` and the
   grouped matmuls, beside the rows the program's expert layers routed and
   held (the cache's running sums).

The grouped matmuls' time a call must follow the rows HELD, not the rows
routed: a two-row chunk program routes 8192 rows a layer, which would need
1.05 ms a call at the matrix unit's peak. (One expert layer ALONE, with a
share and with every row held, does not compile as a program of its own: the
compiler stages the small operands in VMEM beside the kernel's blocks and
runs out; PERF.md section 6, PR 40.)

One JSON line a part, times in milliseconds a call (mean over the traced
calls; the trace's own op events, ``benchmark/tracing.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "k-exaone-236b-a23b.batch-mixedlength"
OPS = {"global_decode": r"^%?paged_decode_attention[.\d]* =",
       "window_decode": r"^%?paged_window_decode_attention[.\d]* =",
       "global_chunk": r"^%?paged_chunk_attention[.\d]* =",
       "window_chunk": r"^%?paged_window_chunk_attention[.\d]* =",
       "gmm": r"^%?gmm[.\d]* ="}


def traced(run, calls: int, ops: dict | None = None, top: int = 0) -> dict:
    """``run()`` ``calls`` times under a trace (once before it, untraced):
    {op: [calls of it, ms a call]} for the kernels of ``ops`` (this
    script's ``OPS`` unless given) and the module's ms an execution; with
    ``top``, the program's heaviest instructions beside them (``top_ops``:
    [name, calls of it an execution, ms a call])."""
    import jax

    from benchmark import tracing

    jax.block_until_ready(run())
    with tempfile.TemporaryDirectory() as tmp:
        tracing.start(os.path.join(tmp, "trace"))
        t0 = time.monotonic()
        for _ in range(calls):
            out = run()
        jax.block_until_ready(out)
        trace = tracing.stop(os.path.join(tmp, "trace"),
                             time.monotonic() - t0)
    if not trace["devices"]:            # the CPU rehearsal: no device plane
        return {}
    out = {"program_ms": 1e3 * sum(
        d for _, _, d in trace["devices"][0]["modules"]) / calls}
    for name, pattern in (OPS if ops is None else ops).items():
        found = [d for _, _, d in tracing.ops_within(
            trace, float("-inf"), float("inf"), pattern)]
        if found:
            out[name] = [len(found) // calls,
                         round(1e3 * sum(found) / len(found), 4)]
    if top:
        by_name: dict = {}
        for text, _, d in tracing.ops_within(trace, float("-inf"),
                                             float("inf"), r""):
            by_name.setdefault(text.split(" = ")[0].lstrip("%"), []).append(d)
        heaviest = sorted(by_name.items(), key=lambda kv: -sum(kv[1]))[:top]
        out["top_ops"] = [[n, len(d) // calls, round(1e3 * sum(d) / len(d), 4)]
                          for n, d in heaviest]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny-exaone preset")
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

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        with open(os.path.join(mf.ROOT, "benchmark", "configs",
                               "rehearsal-tiny-exaone.json")) as f:
            conf = json.load(f)
        traffic = mf.load_traffic("rehearsal-closed-ring")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.paged import MOE_ROWS, _paged_decode_step

    cfg = architecture.part(conf, "program").program_config(conf)
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, BatchingSpec(**traffic["engine"]), params=params,
                    seed=args.seed & 0x7FFFFFFF)
    slots, mpp, ring = eng.num_slots, eng._mpp, eng._ring
    C, pg = eng.chunk_size, eng.page_size
    # every slot on pages of its own: its first ``ring`` from the ids the
    # window planes hold, the rest from above them
    table = np.zeros((slots, mpp), np.int32)
    for b in range(slots):
        table[b, :ring] = b * ring + np.arange(ring)
        table[b, ring:] = slots * ring + b * (mpp - ring) \
            + np.arange(mpp - ring)
    assert table.max() < eng._num_pages
    dtable = jnp.asarray(table)
    dcfg, impl = eng._cfg_decode, eng.paged_attn_impl

    step = jax.jit(lambda p, c, tbl, t, ln, lv: _paged_decode_step(
        p, {**c, "table": tbl}, t, ln, lv, dcfg, attn_impl=impl),
        donate_argnums=(1,))
    live = jnp.ones((slots,), bool)
    rng = np.random.default_rng(args.seed)
    # a token a stream: streams that all decode one token all choose the
    # same experts
    tok = jnp.asarray(rng.integers(3, conf["vocab_size"], slots).astype(
        np.int32))

    def decode_at(context: int):
        lens = jnp.full((slots,), context - 1, jnp.int32)
        # as the engine maps them: the pages the context has reached
        mapped = np.where(np.arange(mpp)[None, :] < -(-context // pg),
                          table, -1)
        tbl = jnp.asarray(mapped)

        def run():
            lg, cache = step(eng.params, eng.cache, tbl, tok, lens, live)
            cache.pop("table", None)
            eng.cache = eng._pin(cache)
            return lg
        return run

    contexts = (C, mpp * pg) if args.tiny else (512, 4096, 8192)
    long_ = contexts[-1]
    for context in contexts if "decode" in args.parts else ():
        print(json.dumps({**side, "part": "decode_step", "context": context,
                          "slots": slots, "window_pages_a_stream": -(-(
                              cfg.attn_window - 1) // pg) + 1,
                          **traced(decode_at(context), args.calls)}),
              flush=True)

    def rows_now():
        return np.asarray(jax.device_get(eng.cache[MOE_ROWS])).astype(
            np.int64)

    block = rng.integers(3, conf["vocab_size"], (2, C)).astype(np.int32)
    rows_program = eng._programs.ask("rows")
    for start in (0, long_ - C) if "chunk" in args.parts else ():
        packed = tuple(map(jnp.asarray, eng._programs.pack(
            [(block[r], table[r], start, True) for r in range(2)], 2)))

        def run():
            logits, eng.cache = rows_program(
                eng.params, eng.cache, *packed, mpp)
            return logits
        before = rows_now()
        numbers = traced(run, args.calls)
        routed, held = (rows_now() - before) // (args.calls + 1)
        print(json.dumps({**side, "part": "chunk_program", "rows": 2,
                          "start": start,
                          "expert_rows_routed_a_program": int(routed),
                          "expert_rows_held_a_program": int(held),
                          **numbers}), flush=True)

    return 0


if __name__ == "__main__":
    sys.exit(main())
