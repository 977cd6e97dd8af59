"""ISSUE 61 on the chip, beside the benchmark and editing none of it: the two
SSD kernels alone at Nemotron-3-Super's shape, and the proof that the
comparison which decides ``correct`` sees every kind of layer of the stack,
the latent projection, the squared ReLU, the gated norm's groups and the
carried state.

    python3 scripts/nemotronh_kernels_chip.py --seed <n> [--parts kernels,blind]

``kernels``: ``ops/ssd.py`` alone at the cell's shapes (128 heads of 64, 8
groups, state 128, bfloat16 operands; Falcon-H1's are 32 heads of 128, 2
groups, state 256): that ``ssd_chunk`` over one and two rows of 512 positions
and ``ssd_step`` over 128 live streams whose states lie in a plane of 640
entries (two heads a lane tile, as the pool holds them) LOWER, that each AGREES with the recurrence token by token
(``ssd_scan_xla`` / ``ssd_step_xla``: the largest difference of the outputs
and of the end states, beside the largest value), and what each takes beside
its bytes at the bus's peak and its recurrence's products at the matrix
unit's (``counts.ssd_chunk_bytes`` / ``ssd_chunk_flops`` / ``ssd_step_bytes``).

``blind``: the configuration's first two sample sequences (the longest
context and one of the traffic's middle) through the engine's own chunk
programs and decode step against the float32 reference on the chip, sound;
then against the reference with ONE thing wrong (``reference.VARIANTS``:
without its Mamba layers, without its attention layer, without the routed
experts, the experts fed the hidden's first values in place of the latent,
plain ReLU for its square, one group in the gated norm), and the program with
its carried state dropped in front of the long prompt's last chunk: each must
read OVER a limit of the configuration, the sound one under both. (The
float8 control is ``python3 -m benchmark.control --workload <cell> --seeds
a,b --sides program,reference_fp8``.)

One JSON line a reading, times in milliseconds a call (mean over the traced
calls; ``scripts/exaone_kernels_chip.py::traced``). ``--tiny`` rehearses it
on the CPU at the tiny preset (no device plane: times are absent).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "nemotron-3-super-120b-a12b.batch-agentturns"
OPS = {"ssd_chunk": r"^%?ssd_chunk[.\d]* =",
       "ssd_step": r"^%?ssd_step[.\d]* ="}
BUS, PEAK = 819e9, 197e12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse on the CPU at the tiny preset")
    ap.add_argument("--parts", default="kernels,blind")
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
        conf = mf.load_json("benchmark/configs/rehearsal-tiny-nemotronh.json")
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
    from kubeflow_tpu.serve.paged import context_bucket

    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    batching = BatchingSpec(**traffic["engine"])
    slots, C = batching.max_batch_size, batching.chunked_prefill_tokens
    rng = np.random.default_rng(args.seed)
    dt_ = cfg.activation_dtype
    h, p, g, n = cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_groups, \
        cfg.ssd_state

    def apart(got, want) -> dict:
        return {"max_abs_apart": float(jnp.abs(got - want).max()),
                "largest": float(jnp.abs(want).max())}

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
        walk = jax.jit(ssd.ssd_scan_xla)
        for rows in (1, 2):
            ops = operands(rows, C, 1)
            state = jax.random.normal(jax.random.PRNGKey(9),
                                      (rows, h, n, p), jnp.float32)
            y, end = chunk(*ops, state)
            want_y, want_end = walk(*ops, state)
            print(json.dumps({
                "part": "ssd_chunk alone", "rows": rows, "positions": C,
                "heads": h, "head_dim": p, "groups": g, "state": n,
                "y": apart(y, want_y), "end_state": apart(end, want_end),
                "ms_at_the_bus": round(1e3 * counts.ssd_chunk_bytes(
                    conf, rows * C, rows) / BUS, 4),
                "ms_at_the_peak": round(1e3 * counts.ssd_chunk_flops(
                    conf, rows * C) / PEAK, 4),
                **traced(lambda: chunk(*ops, state), args.calls, OPS,
                         top=8)}), flush=True)
        ops = operands(slots, 0, 2)
        layers = cfg.layers_holding("ssd")
        entries = layers * slots
        idx = jnp.asarray(rng.permutation(entries)[:slots].astype(np.int32))
        fresh, live = jnp.zeros((slots,), bool), jnp.ones((slots,), bool)
        # the plane as the pool holds it: two heads of 64 a lane tile
        tile = ssd.heads_a_tile(h, g, p)
        states = jax.random.normal(jax.random.PRNGKey(8),
                                   (entries, h, n, p), jnp.float32)
        want_y, want_state = jax.jit(ssd.ssd_step_xla)(*ops, states[idx])
        plane0 = ssd.pack_state(states, tile)
        del states
        def step_program(impl: str):
            return jax.jit(lambda pl, *a: ssd.ssd_step(
                *a[:6], pl, *a[6:], impl=impl), donate_argnums=(0,))

        for impl in ("pallas", "xla"):
            step = step_program(impl)
            y, plane = step(jnp.array(plane0), *ops, idx, fresh, live)
            agree = {"y": apart(y, want_y), "entries": apart(
                ssd.unpack_state(plane[idx], h), want_state)}
            box = [plane]

            def run(step=step, box=box):
                y, box[0] = step(box[0], *ops, idx, fresh, live)
                return y
            print(json.dumps({
                "part": f"ssd_step alone ({impl})", "streams": slots,
                "plane": list(plane0.shape), **agree,
                "ms_at_the_bus": round(1e3 * counts.ssd_step_bytes(
                    conf, slots) / BUS, 4),
                **traced(run, args.calls, OPS, top=8)}), flush=True)
            del box[0]
        del plane0

    if "blind" not in args.parts:
        return 0
    params = make_params(conf, args.seed, cfg.param_dtype)
    eng = LLMEngine(cfg, batching, params=params,
                    seed=args.seed & 0x7FFFFFFF)
    mpp, pg = eng._mpp, eng.page_size
    spec = {**conf["correctness"],
            "sequences": conf["correctness"]["sequences"][:2]}
    reference = architecture.part(conf, "reference")
    samples = correctness.sample_sequences(spec, args.seed,
                                           conf["vocab_size"])

    def reference_program(variant: str, last: int):
        return jax.jit(lambda pr, t: reference.logits(
            pr, t, conf, last=last, variant=variant))

    def want(variant: str) -> list:
        out = []
        for toks, plen, n_dec in samples:
            fn = reference_program(
                variant, correctness.last_chunk_len(plen, C) + n_dec)
            with jax.default_matmul_precision("highest"):
                out.append(fn(params, jnp.asarray(toks)))
        return out

    def numbers(got: list, ref: list) -> dict:
        full = correctness.compare_sides(got, ref, spec, C)
        return {k: full[k] for k in ("prefill_logit_err", "decode_logit_err",
                                     "prefill_logit_err_p90")}

    limits = spec["limits"]
    got = correctness.engine_side(eng, conf, spec, args.seed)
    sound = want("model")
    print(json.dumps({"part": "blind", "side": "sound", "limits": limits,
                      **numbers(got, sound)}), flush=True)
    for variant in reference.VARIANTS[1:]:
        print(json.dumps({"part": "blind", "side": f"reference {variant}",
                          **numbers(got, want(variant))}), flush=True)
    # the program with the carried state dropped in front of the long
    # prompt's LAST chunk: every sequence entry zeroed there
    toks, plen, n_dec = samples[0]
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
    real = correctness.last_chunk_len(plen, C)
    err = correctness.position_errors(lg[:real], sound[0][:real])
    print(json.dumps({
        "part": "blind", "side": "program, carried state dropped",
        "prefill_logit_err": float(np.median(err))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
