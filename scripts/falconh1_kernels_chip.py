"""ISSUE 50 on the chip, beside the benchmark and editing none of it: what the
two SSD kernels cost alone and inside the assistant cell's OWN programs at the
cell's sizes, and the proof that the comparison which decides ``correct``
sees each branch of a parallel block and the carried state.

    python3 scripts/falconh1_kernels_chip.py --seed <n> [--parts kernels,decode,chunk,blind]

``kernels``: ``ops/ssd.py`` alone at the cell's shapes: ``ssd_chunk`` over one
and two rows of 512 positions (32 heads of 128, 2 groups, state 256,
bfloat16 operands) beside its bytes at the bus's peak and its recurrence's
products at the matrix unit's (``counts.ssd_chunk_bytes`` /
``ssd_chunk_flops``); ``ssd_step`` over 48 live streams whose states lie in a
plane of 240 entries, as the kernel and as XLA's gather, step, scatter,
beside ``counts.ssd_step_bytes`` at the bus's peak (ISSUE 50: the step may
stay XLA's where that reads 80% of the bus).

``decode`` / ``chunk``: the cell's engine as the benchmark builds it (weights
from the seed, the traffic file's ``BatchingSpec``; no reference, no server),
traced: the decode step (``paged._paged_decode_step``) over all 48 slots at
contexts of 256, 800 and 1536, every slot on pages of its own: per step the
five ``ssd_step`` and the five ``paged_decode_attention`` calls, each beside
its bytes at the bus's peak, and the program's heaviest instructions; the
one-row ``[C, V]`` chunk program at starts 0 and 1024: the five ``ssd_chunk``
and ``paged_chunk_attention`` calls; beside it what the engine's traffic runs
since PR 52, the program over rows at ONE row (``ChunkPrograms``' "rows": the
head at the last valid position, under a ``cond``), with the prompt's end in
the chunk and without, and the two forms on the same tokens into pages of
their own: the row traffic reads against ``logits[C - 1]``, the planes
written. Since PR 58 the cell's engine builds no program over rows: what its
traffic runs is the chunk program that carries the slots' decode step
(``ChunkPrograms``' "mixed"), timed here with 47 slots riding at a context of
800 and with none, a chunk that ends its prompt and one that does not
(the parent's forms in the same call: ``cd .parent && python3
scripts/falconh1_kernels_chip.py ...``).

``blind``: one prompt of the comparison's own longest size through the
engine's chunk programs against the float32 reference on the chip, sound;
then against the reference with its SSD branch's output zeroed and with its
attention branch's output zeroed (``reference.logits(blind=)``: the number
the comparison reads when one side lacks the branch), and the program with
its carried state dropped between two chunks: each must read OVER the
configuration's limit, the sound one under it.

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

CELL = "falcon-h1-34b.batch-assistant"
OPS = {"ssd_chunk": r"^%?ssd_chunk[.\d]* =",
       "ssd_step": r"^%?ssd_step[.\d]* =",
       "decode_attention": r"^%?paged_decode_attention[.\d]* =",
       "chunk_attention": r"^%?paged_chunk_attention[.\d]* ="}
BUS, PEAK = 819e9, 197e12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny preset")
    ap.add_argument("--parts", default="kernels,decode,chunk")
    args = ap.parse_args(argv)

    from benchmark import architecture, correctness, device
    from benchmark import manifest as mf
    from benchmark.weights import make_params
    from scripts.exaone_kernels_chip import traced

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-falconh1.json")
        traffic = mf.load_traffic("rehearsal-closed-ssd")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.ops import ssd
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.paged import _paged_decode_step, context_bucket

    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    batching = BatchingSpec(**traffic["engine"])
    slots, C = batching.max_batch_size, batching.chunked_prefill_tokens
    rng = np.random.default_rng(args.seed)
    dt_ = cfg.activation_dtype
    h, p, g, n = cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_groups, \
        cfg.ssd_state

    if "kernels" in args.parts:
        def operands(b, s, key):
            ks = jax.random.split(jax.random.PRNGKey(key), 6)
            shape = (b, s) if s else (b,)
            return (jax.random.normal(ks[0], (*shape, h, p), dt_),
                    jax.nn.softplus(jax.random.normal(ks[1], (*shape, h))
                                    - 3.0),
                    -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0,
                                                maxval=2.7)),
                    jax.random.normal(ks[3], (*shape, g, n), dt_),
                    jax.random.normal(ks[4], (*shape, g, n), dt_),
                    jnp.ones((h,), jnp.float32))

        chunk = jax.jit(lambda *a: ssd.ssd_chunk(
            *a, impl="pallas", block=cfg.ssd_chunk))

        def step_program(impl: str):
            return jax.jit(lambda pl, *a: ssd.ssd_step(
                *a[:6], pl, *a[6:], impl=impl), donate_argnums=(0,))

        for rows in (1, 2):
            ops = operands(rows, C, 1)
            state = jnp.zeros((rows, h, n, p), jnp.float32)
            print(json.dumps({
                "part": "ssd_chunk alone", "rows": rows, "positions": C,
                "ms_at_the_bus": round(1e3 * counts.ssd_chunk_bytes(
                    conf, rows * C, rows) / BUS, 4),
                "ms_at_the_peak": round(1e3 * counts.ssd_chunk_flops(
                    conf, rows * C) / PEAK, 4),
                **traced(lambda: chunk(*ops, state), args.calls, OPS,
                         top=8)}), flush=True)
        ops = operands(slots, 0, 2)
        entries = cfg.n_layers * slots
        idx = jnp.asarray(rng.permutation(entries)[:slots].astype(np.int32))
        fresh, live = jnp.zeros((slots,), bool), jnp.ones((slots,), bool)
        for impl in ("pallas", "xla"):
            step = step_program(impl)
            box = [jnp.zeros((entries, h, n, p), jnp.float32)]

            def run(step=step, box=box):
                y, box[0] = step(box[0], *ops, idx, fresh, live)
                return y
            print(json.dumps({
                "part": f"ssd_step alone ({impl})", "streams": slots,
                "entries": entries,
                "ms_at_the_bus": round(1e3 * counts.ssd_step_bytes(
                    conf, slots) / BUS, 4),
                **traced(run, args.calls, OPS, top=8)}), flush=True)
            del box[0]

    if not {"decode", "chunk", "blind"} & set(args.parts.split(",")):
        return 0
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, batching, params=params,
                    seed=args.seed & 0x7FFFFFFF)
    mpp, pg = eng._mpp, eng.page_size
    # every slot on pages of its own, as the allocator hands them: the first
    # from the first pages' ids, the others from above
    per = (eng._num_pages - slots) // slots
    table = np.full((slots, mpp), -1, np.int32)
    for b in range(slots):
        table[b, 0] = b
        table[b, 1:1 + per] = slots + b * per + np.arange(per)
    dcfg, impl = eng._cfg_decode, eng.paged_attn_impl
    step = jax.jit(lambda pr, c, tbl, t, ln, lv: _paged_decode_step(
        pr, {**c, "table": tbl}, t, ln, lv, dcfg, attn_impl=impl),
        donate_argnums=(1,))
    live = jnp.ones((slots,), bool)
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

    contexts = (C, 2 * C) if args.tiny else (256, 800, 1536)
    for context in contexts if "decode" in args.parts else ():
        print(json.dumps({
            "part": "decode_step", "context": context, "slots": slots,
            "weights_ms_at_the_bus": round(
                1e3 * counts.decode_weight_bytes(conf, 2) / BUS, 3),
            "ssd_step_ms_at_the_bus": round(
                1e3 * counts.ssd_step_bytes(conf, slots) / BUS, 4),
            "decode_attention_ms_at_the_bus": round(
                1e3 * counts.decode_attention_bytes(
                    conf, slots * context, 2) / BUS, 4),
            **traced(decode_at(context), args.calls, OPS, top=16)}),
            flush=True)

    tokens = rng.integers(3, conf["vocab_size"], (C,)).astype(np.int32)
    block = jnp.asarray(tokens[None])
    sends = eng._plan.programs()    # what this engine's traffic can take

    def one_row(start: int):
        def run():
            logits, eng.cache = eng._paged_chunk(
                eng.params, eng.cache, block, jnp.asarray(table[0]),
                jnp.int32(start), jnp.int32(C),
                context_bucket(start, C, pg, mpp))
            return logits
        return run

    def packed(start: int, ends: bool, slot: int):
        return tuple(map(jnp.asarray, eng._programs.pack(
            [(tokens, table[slot], start, ends)], 1)))

    def at_last(start: int, ends: bool, slot: int = 0):
        def run():
            logits, eng.cache = eng._programs.ask("rows")(
                eng.params, eng.cache, *packed(start, ends, slot),
                context_bucket(start, C, pg, mpp))
            return logits
        return run

    # what the engine's traffic ran from PR 52 to PR 58: the program over
    # rows at one row, with the prompt's end in the chunk and without
    forms = [("chunk[1] all positions", one_row)]
    if "rows" in sends:
        forms += [("rows[1] last position, ends its prompt",
                   lambda start: at_last(start, True)),
                  ("rows[1] last position, ends none",
                   lambda start: at_last(start, False))]

    def carrying(start: int, ends: bool, ride: bool, context: int = 800):
        """The chunk program that carries the step (PR 58) as the engine
        dispatches it: the chunk on the LAST slot's pages, every other slot
        live at ``context`` on pages of its own (47 riding)."""
        riding = np.arange(slots) < slots - 1
        state = {**eng._dstate.arrays,
                 "tokens": tok, "live": jnp.asarray(riding),
                 "lengths": jnp.where(riding, context - 1, 0).astype(
                     jnp.int32),
                 "budgets": jnp.full((slots,), 1 << 20, jnp.int32)}
        tbl = jnp.asarray(np.where(
            (np.arange(mpp)[None, :] < -(-context // pg))
            & riding[:, None], table, -1))

        def run():      # (the state and the table are donated: copies)
            logits, _, eng.cache, _, _, _ = eng._programs.ask("mixed")(
                eng.params, eng.cache, *packed(start, ends, slots - 1),
                jnp.asarray(ride), jax.tree.map(jnp.array, state),
                jnp.array(tbl), jax.random.PRNGKey(0), "greedy")
            return logits
        return run

    if "mixed" in sends:
        forms += [(f"mixed[1] {'47 slots riding' if ride else 'none riding'}"
                   f", ends {'its prompt' if ends else 'none'}",
                   lambda start, ends=ends, ride=ride: carrying(
                       start, ends, ride))
                  for ride in (True, False) for ends in (True, False)]
    for start in (0, 2 * C) if "chunk" in args.parts else ():
        for name, form in forms:
            print(json.dumps({
                "part": name, "start": start,
                "ssd_chunk_ms_at_the_bus": round(
                    1e3 * counts.ssd_chunk_bytes(conf, C, 1) / BUS, 4),
                "ssd_chunk_ms_at_the_peak": round(
                    1e3 * counts.ssd_chunk_flops(conf, C) / PEAK, 4),
                "program_ms_at_the_peak": round(
                    1e3 * (counts.prefill_flops(conf, start + C)
                           - counts.prefill_flops(conf, start)) / PEAK, 3),
                **traced(form(start), args.calls, OPS, top=24)}), flush=True)
    if "chunk" in args.parts and "rows" in sends:
        # the two forms on the same tokens, each into a slot's own pages and
        # entry: the one row traffic reads, and what the pool was left
        every = np.asarray(one_row(0)()[C - 1])
        last = np.asarray(at_last(0, True, slot=1)()[0])
        third = min(2, slots - 1)
        none = np.asarray(at_last(0, False, slot=third)()[0])
        used = -(-C // pg)
        apart = {}
        for plane, pool in eng.cache.items():
            if pool.ndim < 2:
                continue
            # an entry a sequence at table_row[0]; rows a token in its pages
            ids = [table[b, :1] if pool.shape[1] == slots
                   else table[b, :used] for b in (0, 1, third)]
            rows = [np.asarray(pool[:, jnp.asarray(i)], np.float32)
                    for i in ids]
            apart[plane] = [float(np.abs(r - rows[0]).max())
                            for r in rows[1:]]
        print(json.dumps({
            "part": "rows[1] against chunk[1]", "positions": C,
            "logits_max_abs": float(np.abs(last - every).max()),
            "logits_largest": float(np.abs(every).max()),
            "same_greedy_token": bool(last.argmax() == every.argmax()),
            "no_end_is_zeros": bool((none == 0.0).all()),
            "pool_max_abs_ends_and_not": apart}), flush=True)

    if "blind" in args.parts:
        spec = conf["correctness"]
        plen, n_dec = spec["sequences"][0]
        if args.tiny:
            plen = 3 * C + C // 2
        toks = correctness.check_tokens(args.seed, 0, plen + n_dec,
                                        conf["vocab_size"])
        last = correctness.last_chunk_len(plen, C) + n_dec
        reference = architecture.part(conf, "reference")

        def want(blind=None):
            fn = jax.jit(lambda pr, t: reference.logits(
                pr, t, conf, last=last, blind=blind))
            with jax.default_matmul_precision("highest"):
                return fn(params, jnp.asarray(toks))

        def error(got, ref):
            err = correctness.position_errors(got, ref)
            real = last - n_dec
            return {"prefill_logit_err": float(np.median(err[:real])),
                    "decode_logit_err": float(np.median(err[real:]))}

        limits = spec["limits"]
        got, _ = correctness.engine_logits(eng, toks, plen, n_dec)
        sound = want()
        print(json.dumps({"part": "blind", "side": "sound", "limits": limits,
                          **error(got, sound)}), flush=True)
        for branch in ("ssd", "attention"):
            print(json.dumps({
                "part": "blind", "side": f"reference without {branch}",
                **error(got, want(branch))}), flush=True)
        # the program with the carried state dropped between two chunks: the
        # entry zeroed in front of the prompt's LAST chunk
        row = np.full((mpp,), -1, np.int32)
        n_pages = -(-(plen + n_dec) // pg)
        row[:n_pages] = np.arange(n_pages)
        starts = list(range(0, plen, C))
        for pos in starts:
            real = min(C, plen - pos)
            blk = np.zeros((1, C), np.int32)
            blk[0, :real] = toks[pos:pos + real]
            if pos == starts[-1]:
                eng.cache = {**eng.cache, **{
                    name: jnp.zeros_like(eng.cache[name])
                    for name in ("ssd_state", "ssd_conv")}}
            lg, eng.cache = eng._paged_chunk(
                eng.params, eng.cache, jnp.asarray(blk), jnp.asarray(row),
                jnp.int32(pos), jnp.int32(real),
                context_bucket(pos, C, pg, mpp))
        real = last - n_dec
        err = correctness.position_errors(lg[:real], sound[:real])
        print(json.dumps({
            "part": "blind", "side": "program, carried state dropped",
            "prefill_logit_err": float(np.median(err))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
