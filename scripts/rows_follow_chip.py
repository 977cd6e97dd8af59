"""ISSUE 63 on the chip, beside the benchmark and editing none of it: the
hand-over of an SSD state and conv tail from a row of a chunk program to the
row behind it, at a cell's real sizes.

    python3 scripts/rows_follow_chip.py --workload <cell> --seed <n>

The benchmark's ``correct`` drives the ONE-row program and a decode step of
its own (``benchmark/correctness.py::engine_logits``), so it never runs a row
that follows. This does: the cell's engine as the benchmark builds it (weights
from the seed, ``BatchingSpec`` from the traffic file), one prompt of four whole chunks
and sixteen tokens prefilled three times into pages of its own: chunk by chunk
through the one-row program on the first copy, and on the second as the engine
sends a prompt that is alone beside another's last chunk: through the program
that carries the step (no slot riding), rows ``[c0, dead]``, ``[c1, c2]`` and
``[c3, c4]``, the second row of each pair FOLLOWING the first. Then the next
512 tokens go through the one-row program on every copy, every position's
logits: they stand on the state, the tail and the keys a copy was left, and
the first of them sixteen tokens behind a hand-over. The third copy is the
CONTROL, a hand-over MISSED: the one-row programs again, but ``c4`` starts from
the entry as it stood BEFORE ``c3`` (state and conv tail put back by hand):
what the row behind would read had the row in front not handed its end on.
Printed: the median relative error of the probe's logits against the cell's
plain float32 reference on every copy (what ``correct`` compares: the first
two are held to the cell's own limit, and the control has to read OVER it) and
against each other; the last prompt position's logits of the two copies; and
every plane's entries and pages between the two copies, the norm of the
difference over the norm and the largest difference beside the plane's scale
(two compiled programs need not round alike, and this model's error is its
expert choices: one flipped choice moves a token's rows by tenths: printed,
not judged). Exit 1 over a limit (the control's is not judged under
``--tiny``, whose stack forgets within a chunk). ``--tiny`` rehearses it on
the CPU (the rehearsal configuration at the widths the kernels take, the
kernels interpreted)."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="nemotron-3-super-120b-a12b.batch-agentturns")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    from benchmark import architecture, correctness, device
    from benchmark import manifest as mf
    from benchmark.weights import make_params

    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-nemotronh.json")
        conf = {**conf, "num_attention_heads": 2, "num_key_value_heads": 1,
                "head_dim": 128, "mamba_num_heads": 4, "mamba_head_dim": 64,
                "program": {**conf["program"], "overrides": {
                    **conf["program"]["overrides"], "n_heads": 2,
                    "n_kv_heads": 1, "head_dim": 128, "ssd_heads": 4,
                    "ssd_head_dim": 64, "dtype": "float32",
                    "param_dtype": "float32"}}}
        spec = dict(max_batch_size=3, max_seq_len=128, page_size=8,
                    chunked_prefill_tokens=16, decode_steps=1,
                    prefill_interleave_steps=1, enable_prefix_caching=False,
                    max_concurrent_prefills=2, paged_attn_impl="pallas")
        dev = {"kind": "cpu"}
    else:
        manifest = mf.load_manifest()
        cell = mf.cell(manifest, args.workload)
        conf = mf.load_config(manifest, cell["config"])
        spec = mf.load_traffic(cell["traffic"])["engine"]
        device.prepare_process(platform_is_tpu=True)
        dev = device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.chunk_programs import WHOLE_TABLE, Sent
    from kubeflow_tpu.models.decoder import SSD_PLANES
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.paged import context_bucket

    cfg = architecture.part(conf, "program").program_config(conf)
    params = make_params(conf, args.seed, cfg.param_dtype)
    limit = conf["correctness"]["limits"]["prefill_logit_err"]
    eng = LLMEngine(cfg, BatchingSpec(**spec), params=params,
                    seed=args.seed & 0x7FFFFFFF)
    plan = eng._plan
    if not (plan.ahead and plan.rows == 2):
        print(f"[rows_follow] {args.workload}: the plan sends no row ahead: "
              f"{plan}", file=sys.stderr)
        return 1
    C, pg, mpp = eng.chunk_size, eng.page_size, eng._mpp
    short = 16
    plen = 4 * C + short
    toks = correctness.check_tokens(args.seed, 0, plen + C, conf["vocab_size"])
    per = -(-(plen + C) // pg)
    tables = {}
    for i, copy in enumerate(("one", "rows", "missed")):
        tables[copy] = np.full((mpp,), -1, np.int32)
        # as the allocator hands pages: a first page from the first pages'
        # ids (a sequence's entry), the others from above them
        tables[copy][:per] = [i, *range(eng.num_slots + i * per,
                                        eng.num_slots + (i + 1) * per - 1)]

    def one(copy, start, valid):
        block = np.zeros((1, C), np.int32)
        block[0, :valid] = toks[start:start + valid]
        logits, eng.cache = eng._paged_chunk(
            eng.params, eng.cache, jnp.asarray(block),
            jnp.asarray(tables[copy]), jnp.int32(start), jnp.int32(valid),
            context_bucket(start, C, pg, mpp))
        return logits

    def pair(copy, *rows):
        """``rows``: (start, valid) each, None a dead row; no slot rides."""
        dead = ((), np.full((mpp,), -1, np.int32), 0, False)
        packed = eng._programs.pack(
            [dead if r is None else (toks[r[0]:r[0] + r[1]], tables[copy],
                                     r[0], True) for r in rows], 2)
        return eng._programs.send(Sent("mixed", 2, WHOLE_TABLE), packed)[0]

    for pos in range(0, plen, C):
        last_one = one("one", pos, min(C, plen - pos))
    pair("rows", (0, C), None)
    pair("rows", (C, C), (2 * C, C))
    last_rows = pair("rows", (3 * C, C), (4 * C, short))
    # the control: ``c4`` from the entry as ``c2`` left it
    entry = int(tables["missed"][0])
    put = jax.jit(lambda plane, held: plane.at[:, entry].set(held),
                  donate_argnums=(0,))
    for pos in range(0, 3 * C, C):
        one("missed", pos, C)
    held = {n: eng.cache[n][:, entry] + 0 for n in SSD_PLANES}
    one("missed", 3 * C, C)
    eng.cache = eng._pin({**eng.cache, **{
        n: put(eng.cache[n], held[n]) for n in SSD_PLANES}})
    one("missed", 4 * C, short)
    probe = {copy: one(copy, plen, C) for copy in tables}
    want = correctness.reference_logits(params, toks, conf, last=C + 1)

    def median(got, ref):
        return float(np.median(correctness.position_errors(got, ref)))

    out = {"workload": args.workload, "seed": args.seed,
           "device": dev["kind"], "limit": limit, "prompt": plen,
           "probe_one_vs_reference_median": median(probe["one"], want[1:]),
           "probe_rows_vs_reference_median": median(probe["rows"], want[1:]),
           "probe_rows_vs_one_median": median(probe["rows"], probe["one"]),
           "probe_missed_vs_reference_median": median(probe["missed"],
                                                      want[1:]),
           "last_one_vs_reference": median(
               last_one[short - 1:short], want[:1]),
           "last_rows_vs_reference": median(last_rows[1:2], want[:1]),
           "last_rows_vs_one": median(last_rows[1:2],
                                      last_one[short - 1:short]),
           "argmax_last_agree": bool(jnp.argmax(last_rows[1]) == jnp.argmax(
               last_one[short - 1]))}
    for n, plane in eng.cache.items():
        if plane.ndim <= 2:
            continue
        # a sequence's entry (its first page's id), else its pages
        first = plane.shape[1] == eng.num_slots
        got, ref = (np.asarray(jax.device_get(plane[:, jnp.asarray(
            tables[c][:1] if first else tables[c][:per])])).astype(np.float32)
            for c in ("rows", "one"))
        out[f"plane_{n}_relative_norm"] = float(
            np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))
        out[f"plane_{n}_max_abs"] = float(np.max(np.abs(got - ref)))
        out[f"plane_{n}_scale"] = float(np.max(np.abs(ref)))
    ok = (out["probe_one_vs_reference_median"] < limit
          and out["probe_rows_vs_reference_median"] < limit
          # (the tiny stack forgets within a chunk: its control proves no more
          # than that the path runs)
          and (args.tiny or out["probe_missed_vs_reference_median"] > limit))
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
