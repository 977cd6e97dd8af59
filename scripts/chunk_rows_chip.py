"""ISSUE 29 on the chip, beside the benchmark and editing none of it.

    python3 scripts/chunk_rows_chip.py check --workload <cell> --seed <n>
    python3 scripts/chunk_rows_chip.py cell --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|2>

``check`` builds the cell's engine as the benchmark does (its weights from the
seed, its ``BatchingSpec`` from the traffic file) and holds the program over
several prompts' chunks against the one-row program, which is the only one the
benchmark's ``correct`` exercises: prompts a (its next chunk starts MID-PAGE)
and b (a longer context, a short last chunk) are prefilled twice into pages of
their own; then the next chunk of each goes through the one-row program in
turn on the first copy and through ONE two-row program on the second, and a's
once more beside a DEAD row on a third (the same program: the engine
dispatches it at one static context, the whole table). The program over rows
returns ``[B, V]``, the head at each row's LAST valid position (PR 41), so a
row is held against the one-row program's ``logits[valid - 1]``. Printed: the
relative error of the logits (``benchmark.correctness.position_errors``): the
one-row program against the cell's plain float32 reference on the same weights
and tokens, median over the chunk's positions, which is what the benchmark's
``correct`` compares and is held to the cell's own limit here too; a row's one
position against the reference's (held to the limit, or to twice what the
one-row program reads at that position where that is more: one position may
sit on an expert choice that flips in bfloat16); rows against one row there
(relative, the largest absolute difference, and whether the greedy token is
the same); beside a dead row against beside b, which must be 0; and the
largest difference of the pool rows written. Exit 1 where a number is over its
limit. Rows against one row is printed and not judged: two compiled programs
need not round alike (the gathered form read 0.0 here, PR 29; the in-place
form of a per-head pool reads what either reads against the reference, from
ONE unit in the last place of the K rows the first layer writes, the rotation
fused into the projection in one program and not in the other: PERF.md
section 6, PR 36).

``cell`` is ``python3 -m benchmark.run`` with the engine's counters printed:
the window's ``prefill_chunks_dispatched / prefill_programs_dispatched``, the
programs sent through the program over rows and those in which a prompt ended
(``prefill_row_programs_dispatched``, ``prefill_programs_with_end``: PR 41), the
positions at which they ran the head (``prefill_head_positions``: PR 52) and
tokens, from the snapshots the harness takes (they ride in the run's record,
which the result line does not print), the Pallas kernels in each program
variant the warm-up reached (``LLMEngine.program_kernels``), the device's
memory peak phase by phase, and of a traced run also the tail's programs
(executions, seconds, mean) and its forty largest ops.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _log(msg: str) -> None:
    print(f"[chunk_rows] {msg}", file=sys.stderr, flush=True)


def check(workload: str, seed: int) -> int:
    from benchmark import architecture, correctness, device
    from benchmark import manifest as mf
    from benchmark.weights import make_params

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, workload)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    device.prepare_process(platform_is_tpu=True)
    dev = device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.models.decoder import plane_kind
    from kubeflow_tpu.serve.paged import context_bucket

    cfg = architecture.part(conf, "program").program_config(conf)
    params = make_params(conf, seed, cfg.param_dtype)
    limit = conf["correctness"]["limits"]["prefill_logit_err"]
    eng = LLMEngine(cfg, BatchingSpec(**traffic["engine"]), params=params,
                    seed=seed & 0x7FFFFFFF)
    if eng._plan.rows < 2:
        _log(f"{workload}: the engine built no program over rows")
        return 1
    C, pg, mpp = eng.chunk_size, eng.page_size, eng._mpp
    vocab = conf["vocab_size"]
    # a: 200 tokens behind it (mid-page), a whole chunk next; b: seven
    # chunks behind it (fewer where a slot is shorter), 300 tokens next (at
    # chunks of 512).
    b_start = min(7, mpp * pg // C - 2) * C
    plan = {"a": (C * 25 // 64, C), "b": (b_start, C * 75 // 128)}
    toks = {k: correctness.check_tokens(seed, i, s + v, vocab)
            for i, (k, (s, v)) in enumerate(plan.items())}
    per = -(-(b_start + C) // pg)
    tables, nxt = {}, 0
    for copy in ("one", "rows", "dead"):
        for k in plan:
            row = np.full((mpp,), -1, np.int32)
            row[:per] = np.arange(nxt, nxt + per, dtype=np.int32)
            tables[copy, k], nxt = row, nxt + per

    def one(k, copy, start, valid):
        block = np.zeros((1, C), np.int32)
        block[0, :valid] = toks[k][start:start + valid]
        logits, eng.cache = eng._paged_chunk(
            eng.params, eng.cache, jnp.asarray(block),
            jnp.asarray(tables[copy, k]), jnp.int32(start), jnp.int32(valid),
            context_bucket(start, C, pg, mpp))
        return logits

    def rows(keys, copy):
        # every live row's logits are wanted (a program in which no row ends
        # its prompt runs no head: PR 41); a dead row has no token
        dead = ((), np.full((mpp,), -1, np.int32), 0, False)
        packed = eng._programs.pack(
            [dead if k is None else (
                toks[k][plan[k][0]:sum(plan[k])], tables[copy, k],
                plan[k][0], True) for k in keys], 2)
        # (by name: an engine whose chunk program carries the step sends
        # several rows through that one, and builds this only when asked)
        logits, eng.cache = eng._programs.ask("rows")(
            eng.params, eng.cache, *map(jnp.asarray, packed), mpp)
        return logits

    def written(copy, k):
        """The pool rows of ``k``'s next chunk in ``copy``'s pages: the
        pages the chunk touched, of a window layer's planes the sequence's
        whole ring (its first pages); the expert rows' sums are no plane."""
        start, valid = plan[k]
        row = tables[copy, k]
        touched = row[start // pg:-(-(start + valid) // pg)]
        return {n: np.asarray(jax.device_get(plane[:, jnp.asarray(
            row[:eng._ring] if plane_kind(n) == "window" else touched)])
        ).astype(np.float32) for n, plane in eng.cache.items()
            if plane.ndim > 2}

    for copy in ("one", "rows", "dead"):
        for k, (start, _) in plan.items():
            for pos in range(0, start, C):
                one(k, copy, pos, min(C, start - pos))
    alone = {k: one(k, "one", *plan[k]) for k in plan}
    both = rows(("a", "b"), "rows")
    beside_dead = rows(("a", None), "dead")
    out = {"workload": workload, "seed": seed, "device": dev["kind"],
           "rows": eng._plan.rows, "limit": limit}
    for r, k in enumerate(plan):
        start, valid = plan[k]
        want = correctness.reference_logits(params, toks[k][:start + valid],
                                            conf, last=valid)
        last = alone[k][valid - 1:valid]
        out[f"logits_{k}_one_vs_reference_median"] = float(np.median(
            correctness.position_errors(alone[k][:valid], want)))
        for name, got in (("rows", both[r:r + 1]), ("one", last)):
            out[f"logits_{k}_{name}_vs_reference_last"] = float(
                correctness.position_errors(got, want[-1:])[0])
        out[f"logits_{k}_rows_vs_one_last"] = float(
            correctness.position_errors(both[r:r + 1], last)[0])
        out[f"logits_{k}_rows_vs_one_max_abs"] = float(
            jnp.max(jnp.abs(both[r] - last[0])))
        out[f"logits_{k}_scale"] = float(jnp.max(jnp.abs(last)))
        out[f"argmax_{k}_agree"] = bool(
            jnp.argmax(both[r]) == jnp.argmax(last[0]))
        got, want = written("rows", k), written("one", k)
        out[f"pool_{k}_rows_vs_one_max_abs"] = max(
            float(np.max(np.abs(got[n] - want[n]))) for n in got)
        out[f"pool_{k}_scale"] = max(float(np.max(np.abs(want[n])))
                                     for n in want)
    out["logits_a_dead_vs_b_max"] = float(np.max(correctness.position_errors(
        beside_dead[:1], both[:1])))
    got, want = written("dead", "a"), written("rows", "a")
    out["pool_a_dead_vs_b_max_abs"] = max(
        float(np.max(np.abs(got[n] - want[n]))) for n in got)
    ok = (all(out[f"logits_{k}_one_vs_reference_median"] < limit
              and out[f"logits_{k}_rows_vs_reference_last"] < max(
                  limit, 2 * out[f"logits_{k}_one_vs_reference_last"])
              for k in plan)
          and out["logits_a_dead_vs_b_max"] == 0.0
          and out["pool_a_dead_vs_b_max_abs"] == 0.0)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


def _tail_programs(record: dict) -> None:
    """The traced tail by program, and its largest ops."""
    import re

    from benchmark import tracing

    trace = record.get("trace")
    if not trace or not trace["devices"]:
        return
    by: dict = {}
    for name, _, dur in trace["devices"][0]["modules"]:
        n = by.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0.0])
        n[0], n[1] = n[0] + 1, n[1] + dur
    for name, (n, total) in sorted(by.items(), key=lambda kv: -kv[1][1])[:8]:
        _log(f"tail program {name}: {n} x {1e3 * total / n:.3f} ms = "
             f"{total:.4f} s")
    lam = sorted(d for name, _, d in trace["devices"][0]["modules"]
                 if name.startswith("jit__lambda") and d >= 0.002)
    if lam:
        _log("tail jit__lambda >= 2 ms: n %d, min %.2f, median %.2f, max "
             "%.2f ms" % (len(lam), 1e3 * lam[0], 1e3 * lam[len(lam) // 2],
                          1e3 * lam[-1]))
    for name, total in tracing.top_ops(trace, n=40):
        _log(f"tail op {name}: {total:.4f} s")
    spans = [s for thread in (record.get("host_spans") or [])
             for s in thread if s[0] == "engine.prefill_dispatch"]
    if spans:
        chunks = [s[3].get("chunks", 1) for s in spans]
        _log(f"tail engine.prefill_dispatch spans: {len(spans)}, chunks "
             f"{sum(chunks)}")


def run_cell(argv: list) -> int:
    from benchmark import manifest as mf
    from benchmark import run, serving

    snapshots = []
    take, read = serving.program_counters, mf.read_layer_metrics

    def recording(**parts):
        snap = take(**parts)
        if not snapshots and "engine" in parts:     # warm-up is over
            _log("program_kernels: "
                 + json.dumps(parts["engine"].program_kernels))
        snapshots.append(snap)
        return snap

    def reading(manifest, cell_name, record):
        _tail_programs(record)
        return read(manifest, cell_name, record)

    def peak(where):
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        _log(f"memory at {where}: peak {stats.get('peak_bytes_in_use', 0)} "
             f"in use {stats.get('bytes_in_use', 0)}")

    def phase(module, name):
        inner = getattr(module, name)

        def logged(*args, **kwargs):
            peak(f"{name} begins")
            out = inner(*args, **kwargs)
            peak(f"{name} ends")
            return out

        setattr(module, name, logged)

    from benchmark import correctness

    phase(correctness, "serving_numbers")
    phase(serving, "warm_first_token_sampler")
    phase(serving, "engine_snapshot")
    serving.program_counters = recording
    mf.read_layer_metrics = reading
    rc = run.main(argv)
    if len(snapshots) >= 2 and snapshots[0] and "engine" in snapshots[0]:
        before, after = snapshots[0]["engine"], snapshots[1]["engine"]
        d = {k: after[k] - before[k] for k in after
             if k.startswith("prefill_") and k.endswith(
                 ("_dispatched", "_with_end", "_head_positions"))}
        if d.get("prefill_programs_dispatched"):
            d["chunks_per_program"] = (d["prefill_chunks_dispatched"]
                                       / d["prefill_programs_dispatched"])
        if "prefill_head_positions" in d and d["prefill_chunks_dispatched"]:
            # positions a chunk at which a chunk program ran the head (PR 52)
            d["head_positions_per_chunk"] = (d["prefill_head_positions"]
                                             / d["prefill_chunks_dispatched"])
        _log(f"window counters: {json.dumps(d)}")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("check", "cell"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args, rest = ap.parse_known_args()
    if args.mode == "check":
        return check(args.workload, args.seed)
    return run_cell(["--workload", args.workload, "--seed", str(args.seed),
                     *rest])


if __name__ == "__main__":
    sys.exit(main())
