"""The proof that the comparison which decides ``correct`` sees what
LongCat-Flash's block adds, on the chip at the cell's own sizes:

    python3 scripts/longcat_blind_chip.py --seed <n> [--tiny]

The comparison's own sequences through the engine's programs against the
float32 reference: the program's numbers, then the reference computed in
float8, and the reference with each part of the block got wrong
(``reference.logits(variant=)``): the expert layer left out
("no_experts"), the zero experts' term left out ("no_zero"), the expert
layer's result joined a sublayer early ("joined_early"), both rank factors
left out ("no_rank_factors"), each against the sound float32 reference: what
the comparison reads beside a program that lacks the mechanism. Every
control must read OVER one of the configuration's limits, the program under
all of them. One JSON line a reading. ``--tiny`` rehearses it on the CPU at
the tiny preset.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "longcat-flash-omni.batch-voiceturns"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny preset")
    args = ap.parse_args(argv)

    from benchmark import architecture, correctness, device, reference
    from benchmark import manifest as mf
    from benchmark.weights import make_params

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, CELL)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if args.tiny:
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-longcat.json")
        traffic = mf.load_traffic("rehearsal-closed")
    else:
        device.prepare_process(platform_is_tpu=True)
        device.require_devices(cell["chips"])

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(conf, "program").program_config(conf)
    batching = BatchingSpec(**traffic["engine"])
    C = batching.chunked_prefill_tokens
    spec = conf["correctness"]
    ref = architecture.part(conf, "reference")

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, batching, params=params, seed=args.seed & 0x7FFFFFFF)
    got = correctness.engine_side(eng, conf, spec, args.seed)
    want = correctness.reference_side(params, conf, spec, args.seed, C)
    emit(side="program", seed=args.seed, limits=spec["limits"],
         **correctness.compare_sides(got, want, spec, C))
    del got, eng

    def control(quant=None, variant="model"):
        fn = jax.jit(lambda p, t, last: ref.logits(
            p, t, conf, quant or reference.same, last=last, variant=variant),
            static_argnums=2)
        with jax.default_matmul_precision("highest"):
            return [fn(params, jnp.asarray(toks),
                       correctness.last_chunk_len(plen, C) + n_dec)
                    for toks, plen, n_dec in correctness.sample_sequences(
                        spec, args.seed, conf["vocab_size"])]

    for side, kw in (
            ("reference in float8", {"quant": reference.fp8_round_trip}),
            *((f"reference, {v}", {"variant": v})
              for v in ref.VARIANTS[1:])):
        emit(side=side, seed=args.seed,
             **correctness.compare_sides(control(**kw), want, spec, C))
    return 0


if __name__ == "__main__":
    sys.exit(main())
