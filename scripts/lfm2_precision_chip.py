"""On the chip, by hand: where does the long-answer cell's logit error come
from? Per seed, against the float32 reference at the cell's own size: the
PROGRAM (the engine's chunk prefill and decode step), the reference computed
with every matrix operand rounded to bfloat16 (the program's precision in
another implementation: what rounding alone does to this model, expert
choices that flip included), and the float8 control; for a stack with
linear-attention layers also ``program_state_bf16``: the program with its
float32 recurrent state rounded to bfloat16 after every program call (what a
bfloat16 state plane would hold between programs: the precision one step
below what such a configuration states for its state). One JSON line a side,
with the quartiles of the per-position errors beside the two medians that
``correct`` compares.

    python3 scripts/lfm2_precision_chip.py --seeds 1,2,3 [--sides ...]
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2-24b-a2b.batch-longanswer")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sides", default="program,reference_bf16,reference_fp8")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import architecture, correctness, device, reference
    from benchmark import manifest as mf
    from benchmark.weights import make_params
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, args.workload)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    device.prepare_process(platform_is_tpu=True)
    device.require_devices(cell["chips"])
    cfg = architecture.part(conf, "program").program_config(conf)
    spec, chunk = conf["correctness"], traffic["engine"][
        "chunked_prefill_tokens"]

    def bf16_round_trip(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    @contextlib.contextmanager
    def state_rounded(engine, on: bool):
        """Both programs ``engine_logits`` calls hand back a pool whose
        float32 planes (a linear layer's recurrent matrices) went through
        bfloat16."""
        from kubeflow_tpu.serve import paged

        if not on:
            yield
            return

        def rounded(result):
            logits, cache = result
            return logits, {n: bf16_round_trip(a) if a.dtype == jnp.float32
                            and n in engine.cache else a
                            for n, a in cache.items()}

        chunk, step = engine._paged_chunk, paged._paged_decode_step
        engine._paged_chunk = lambda *a, **k: rounded(chunk(*a, **k))
        paged._paged_decode_step = lambda *a, **k: rounded(step(*a, **k))
        try:
            yield
        finally:
            engine._paged_chunk, paged._paged_decode_step = chunk, step

    for seed in (int(s) for s in args.seeds.split(",")):
        params = make_params(conf, seed, cfg.param_dtype)
        want = correctness.reference_side(params, conf, spec, seed, chunk)
        for side in args.sides.split(","):
            if side in ("program", "program_state_bf16"):
                kw = {**traffic["engine"], "max_pages": 2 * traffic["engine"][
                    "max_seq_len"] // traffic["engine"]["page_size"]}
                engine = LLMEngine(cfg, BatchingSpec(**kw), params=params,
                                   seed=seed & 0x7FFFFFFF)
                with state_rounded(engine, side == "program_state_bf16"):
                    got = correctness.engine_side(engine, conf, spec, seed)
                shared = {id(x) for x in jax.tree.leaves(params)}
                for leaf in jax.tree.leaves((engine.cache, engine.params)):
                    if id(leaf) not in shared:
                        leaf.delete()
                del engine
            else:
                quant = {"reference_bf16": bf16_round_trip,
                         "reference_fp8": reference.fp8_round_trip}[side]
                got = correctness.reference_side(params, conf, spec, seed,
                                                 chunk, quant=quant)
            numbers = correctness.compare_sides(got, want, spec, chunk)
            errs = np.concatenate([correctness.position_errors(g, w)
                                   for g, w in zip(got, want)])
            q = {f"p{p}": float(np.percentile(errs, p))
                 for p in (10, 25, 50, 75, 90)}
            print(json.dumps({"seed": seed, "side": side, **numbers, **q}),
                  flush=True)
            del got
            gc.collect()
        del params, want
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
