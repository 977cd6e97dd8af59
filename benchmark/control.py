"""The control of ``correct``: the comparison shown to FAIL one precision step
below the one a configuration states, at the cell's own size, seed by seed.
Run by hand on the chip (its readings and the limits set from them are in
PERF.md) and, at a size a test run can hold, by
tests/benchmark_suite/test_benchmark_control.py. A benchmark run never runs
it.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3

Serving (bfloat16 stated): per seed the PROGRAM's two numbers, then two
controls: the program with its own lower-precision path switched on (int8
weights and an int8 KV cache, ``BatchingSpec.quantize`` /
``kv_cache_dtype``), and the reference itself put in the program's place and
computed in float8. Training (bfloat16 compute stated; the trainer has no
lower-precision path): the reference in float8 against the reference.
One JSON line per seed and side.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from benchmark import architecture, correctness, reference
from benchmark import manifest as mf


def serving_sides(conf: dict, traffic: dict, seed: int, sides,
                  emit=None) -> dict:
    """``sides`` in the order given; ``program_int8`` must come last: its
    engine does not fit beside the bfloat16 weights on a full chip, so they
    are given back to the chip once the reference has read them."""
    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    from benchmark.weights import make_params

    cfg = architecture.part(conf, "program").program_config(conf)
    spec = conf["correctness"]
    chunk = traffic["engine"]["chunked_prefill_tokens"]
    params = make_params(conf, seed, cfg.param_dtype)
    want = correctness.reference_side(params, conf, spec, seed, chunk)
    out = {}
    for side in sides:
        if side == "reference_fp8":
            got = correctness.reference_side(
                params, conf, spec, seed, chunk,
                quant=reference.fp8_round_trip)
        else:
            # A pool of a few sequences: the comparison is of precision,
            # and on a full chip the cell's own pool leaves the int8
            # engine no room to quantise its weights beside the originals.
            spec_kw = {**traffic["engine"],
                       "max_pages": 2 * traffic["engine"]["max_seq_len"]
                       // traffic["engine"]["page_size"]}
            if side == "program_int8":
                spec_kw.update(quantize="int8", kv_cache_dtype="int8")
            engine = LLMEngine(cfg, BatchingSpec(**spec_kw), params=params,
                               seed=seed & 0x7FFFFFFF)
            kept = {id(x) for x in jax.tree.leaves(engine.params)}
            if side == "program_int8":
                for leaf in jax.tree.leaves(params):
                    if id(leaf) not in kept:
                        leaf.delete()
            got = correctness.engine_side(engine, conf, spec, seed)
            shared = {id(x) for x in jax.tree.leaves(params)}
            for leaf in jax.tree.leaves((engine.cache, engine.params)):
                if id(leaf) not in shared:
                    leaf.delete()
            del engine
            gc.collect()
        out[side] = correctness.compare_sides(got, want, spec, chunk)
        del got
        if emit is not None:
            emit(side, out[side])
    del params, want
    gc.collect()
    jax.clear_caches()
    return out


def training_sides(conf: dict, traffic: dict, seed: int, devices) -> dict:
    from kubeflow_tpu.runtime.mesh import build_mesh

    from benchmark.traffic import train_batch
    from benchmark.weights import make_params, param_shapes

    program = architecture.part(conf, "program")
    cfg = program.program_config(conf)
    mesh = build_mesh(conf["mesh"], devices)
    params = make_params(
        conf, seed, cfg.param_dtype,
        shardings=program.param_shardings(
            cfg, mesh, param_shapes(conf, cfg.param_dtype)))
    batch = train_batch(seed, 0, traffic["global_batch"], traffic["seq_len"],
                        conf["vocab_size"])
    axes = tuple(a for a, n in conf["mesh"].items() if n > 1)
    kw = dict(micro=int(conf["correctness"].get("micro", 0)), mesh=mesh,
              batch_axes=axes or None)
    loss, gnorm = correctness.reference_loss_and_grad_norm(
        params, batch, conf, **kw)
    c_loss, c_gnorm = correctness.reference_loss_and_grad_norm(
        params, batch, conf, quant=reference.fp8_round_trip, **kw)
    return {"reference_fp8": {
        "loss_rel_diff": correctness.relative(c_loss, loss),
        "grad_norm_rel_diff": correctness.relative(c_gnorm, gnorm),
        "reference": [loss, gnorm], "control": [c_loss, c_gnorm]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sides", default="program,reference_fp8,program_int8")
    args = ap.parse_args(argv)
    from benchmark import device

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, args.workload)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    device.prepare_process(platform_is_tpu=True)
    dev = device.require_devices(cell["chips"])
    import jax

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()

        def emit(side, numbers, seed=seed, t=t):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side, **numbers,
                              "seconds": round(time.monotonic() - t, 1)}),
                  flush=True)

        if traffic["kind"] == "train_steps":
            for side, numbers in training_sides(
                    conf, traffic, seed,
                    jax.devices()[:dev["count"]]).items():
                emit(side, numbers)
        else:
            serving_sides(conf, traffic, seed, args.sides.split(","), emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
