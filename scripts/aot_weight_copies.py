"""Which weights a serving cell's programs copy before they use them:

    JAX_PLATFORMS=cpu python3 scripts/aot_weight_copies.py <cell> [decode|chunk]

Lowers the cell's serving programs for a DESCRIBED ``v5e:2x2`` (nothing
runs, no chip is needed) as the engine builds them on one chip: the
one-step ``paged_decode_multi`` over the cell's slots and pool, and
``paged_chunk_prefill`` over one row and, where the engine builds it,
over ``max_concurrent_prefills`` rows (the head at each row's last valid
position, under a conditional on "some row ends its prompt", as the engine
asks for it); ``attn_impl="pallas"``, the
parameters in the formats ``serve/weight_layout.py`` gives them
(``--default-layouts``: every leaf as the compiler lays it out, the tree
as it stood before PR 39). It prints, a program:

- every ``copy`` of a million elements or more in the compiled text, with
  its shape, the layout it copies TO and its operand. One whose operand is
  a parameter (or a layer's slice of one) is a weight laid out again at
  every dispatch (decode: hoisted out of the step loop) or at every layer
  of every program (chunk): bus traffic that computes nothing;
- the program's ``temp`` bytes (a hoisted copy is a temporary of its size);
- with ``--auto``, the layouts ``Layout.AUTO`` would choose for the
  parameters, next to the default: what the compiler "prefers". A
  preference is not a gain: take a leaf's only where the copies above
  go away in BOTH programs (PERF.md section 6, PR 39).

A cell is read from ``BENCHMARK.json`` (configuration file, traffic file's
``engine`` block), so the sizes are the benchmark's. Code that asks
``jax.default_backend()`` is answered "tpu" here, as
``benchmark/aot_sizes.py`` answers it. A compile that passes is not a chip
run. ``tests/test_chip_compile.py`` holds the four serving cells to "no
parameter-sized copy" through ``lowered_programs`` and ``weight_copies``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"
MIN_ELEMENTS = 1_000_000

# `%copy.19 = bf16[16,4096,32,128]{3,1,2,0:T(8,128)(2,1)} copy(%param.3)`,
# with or without the sigils, whatever the layout's annotations.
_COPY = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[^}]*)\})? copy\(%?(?P<operand>[\w.\-]+)\)")


def serving_cell(name: str):
    """``(cfg, batching)`` of a serving cell of ``BENCHMARK.json``: the
    program's config from the configuration file, the engine's options from
    the traffic file."""
    from benchmark import architecture
    from benchmark import manifest as mf

    from kubeflow_tpu.core.serving import BatchingSpec

    manifest = mf.load_manifest()
    cell = mf.cell(manifest, name)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    if "engine" not in traffic:
        raise SystemExit(f"{name} is not a serving cell")
    cfg = architecture.part(conf, "program").program_config(conf)
    return cfg, BatchingSpec(**traffic["engine"])


def lowered_programs(cfg, batching, dev, *, relaid: bool = True,
                     auto: bool = False, rows_logits_at: str = "last",
                     mixed: bool = False) -> dict:
    """``{program: jax.stages.Lowered}`` of an engine over ``cfg`` and
    ``batching`` on the one described chip ``dev`` (a sharding): "decode"
    (one step a dispatch), "chunk[1]" and, where the engine builds the
    program over several prompts' rows, "chunk[N]" (an engine that sends a
    prefill alone through the program over rows at one row, "last": also
    "rows[1]"; "chunk[1]" is then the ``[C, V]`` program of callers outside
    the engine's traffic). ``relaid``: the
    parameters in the engine's formats, else all in the default layout.
    ``auto``: the parameters' layouts left to the compiler, whatever
    ``relaid`` says (``compiled.input_formats`` then says what it chose).
    ``rows_logits_at``: the positions whose logits "chunk[N]" returns, the
    engine's "last" unless a comparison wants the other form.
    ``mixed``: also "mixed[N]", the chunk program that carries the slots'
    decode step (``paged_mixed_step``) at its one width (as many rows as the
    engine sends chunks together), where the engine builds it
    (``ChunkPlan.carries_step``).
    The caller has made ``jax.default_backend()`` answer "tpu"."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout

    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.chunk_programs import plan_chunks
    from kubeflow_tpu.serve.engine import serving_configs
    from kubeflow_tpu.serve.paged import (
        engine_pool_shapes, paged_chunk_prefill, paged_decode_multi,
        paged_mixed_step,
    )
    from kubeflow_tpu.serve.weight_layout import relay, weight_formats

    b = batching
    cfg_prefill, cfg_decode = serving_configs(cfg, b)
    slots, pg = b.max_batch_size, b.page_size
    chunk_tokens = b.chunked_prefill_tokens
    mpp = b.max_seq_len // pg
    pages = int(b.max_pages or slots * mpp)

    def sds(shape, dtype=jnp.int32, sharding=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: init_decoder_params(jax.random.PRNGKey(0),
                                                   cfg)))
    params = relay(params, weight_formats(
        params, cfg, one_chip_pallas=relaid and not auto))
    cache = {n: sds(shape, dt)
             for n, (shape, dt) in engine_pool_shapes(
                 cfg_decode, slots, pages, pg).items()}
    p_in = jax.tree.map(lambda _: Format(Layout.AUTO, dev), params) \
        if auto else None

    def jit(fn, n_rest):
        if not auto:
            return jax.jit(fn, donate_argnums=(1,))
        return jax.jit(fn, donate_argnums=(1,),
                       in_shardings=(p_in,) + (None,) * n_rest)

    def i32():
        return sds((slots,))

    def f32():
        return sds((slots,), jnp.float32)

    out = {"decode": jit(
        lambda p, c, tbl, t, ln, lv, tmp, tk, tpp, st, bd, key:
        paged_decode_multi(p, {**c, "table": tbl}, t, ln, lv, tmp, tk, tpp,
                           st, bd, key, cfg_decode, 1, sample_mode="greedy",
                           attn_impl="pallas"), 11).lower(
        params, cache, sds((slots, mpp)), i32(), i32(),
        sds((slots,), jnp.bool_), f32(), i32(), f32(), i32(), i32(),
        sds((2,), jnp.uint32))}
    # (key, rows, whether the program is the engine's form over rows: the
    # last position's logits, "this row ends its prompt" a row)
    by_rows = rows_logits_at == "last"
    forms = [("chunk[1]", 1, False)]
    plan = plan_chunks(cfg_prefill, cache, b, "pallas")
    # a prefill alone through the program over rows as a group of one
    if by_rows and plan.lone_at_last:
        forms.append(("rows[1]", 1, True))
    if plan.rows > 1:
        forms.append((f"chunk[{plan.rows}]", plan.rows, by_rows))
    for key, n, last in forms:
        # (a lambda, as the engine's: the lowered module's name is part of
        # the digests tests/test_chip_compile.py pins)
        out[key] = jit(
            lambda p, c, t, tr, st, vl, ends=None, at="last" if last
            else "all": paged_chunk_prefill(
                p, c, t, tr, st, vl, cfg_prefill, context_pages=mpp,
                paged_attn_impl="pallas", logits_at=at, wanted=ends),
            5 + last).lower(
            params, cache, sds((n, chunk_tokens)), sds((n, mpp)), sds((n,)),
            sds((n,)), *([sds((n,), jnp.bool_)] if last else []))
    if mixed and plan.carries_step:
        for n in (forms[-1][1],):       # its one width: the rows sent together
            out[f"mixed[{n}]"] = jit(
                lambda p, c, tbl, t, tr, s0, vl, ends, ride, tok, ln, lv,
                tmp, tk, tpp, st, bd, key: paged_mixed_step(
                    p, {**c, "table": tbl}, t, tr, s0, vl, ends, ride, tok,
                    ln, lv, tmp, tk, tpp, st, bd, key, cfg_prefill,
                    sample_mode="greedy", attn_impl="pallas"), 17).lower(
                params, cache, sds((slots, mpp)), sds((n, chunk_tokens)),
                sds((n, mpp)), sds((n,)), sds((n,)), sds((n,), jnp.bool_),
                sds((), jnp.bool_), i32(), i32(), sds((slots,), jnp.bool_),
                f32(), i32(), f32(), i32(), i32(), sds((2,), jnp.uint32))
    return out


def weight_copies(text: str, params,
                  min_elements: int = MIN_ELEMENTS) -> list[dict]:
    """The ``copy`` instructions of ``min_elements`` or more in a compiled
    program's text: ``{name, dtype, shape, to, operand, leaf}``. ``leaf``
    lists the parameters the copy is the size of: the whole leaf (the
    decode program hoists a stacked weight's copy out of its loops) or one
    layer's slice of a stacked one (the chunk program copies inside its
    layer loop, from a fusion that sliced the stack); empty for a copy the
    size of no parameter (a pool plane, an activation). Matched by shape
    and type and not by the operand's name: what feeds the copy is a
    parameter in one program, a ``dynamic-slice`` fusion in the next."""
    import jax
    import numpy as np

    sized = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name, kind = jax.tree_util.keystr(path), np.dtype(leaf.dtype).name
        shapes = {tuple(leaf.shape)}
        if len(leaf.shape) > 2:
            shapes |= {(1,) + tuple(leaf.shape[1:]), tuple(leaf.shape[1:])}
        for shape in shapes:
            sized.setdefault((kind, shape), []).append(name)
    hlo_names = {"bf16": "bfloat16", "f32": "float32", "f16": "float16",
                 "s8": "int8", "f64": "float64"}
    out = []
    for line in text.splitlines():
        m = _COPY.match(line)
        if not m:
            continue
        shape = tuple(int(d) for d in m["dims"].split(",") if d)
        if int(np.prod(shape)) < min_elements:
            continue
        out.append({
            "name": m["name"], "dtype": m["dtype"], "shape": list(shape),
            "to": m["layout"], "operand": m["operand"],
            "leaf": sized.get((hlo_names.get(m["dtype"], m["dtype"]),
                               shape), [])})
    return out


_KERNEL_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def lowered_fingerprint(lowered) -> str:
    """A lowered program's text as a short digest that does not move with
    the checkout's path or a kernel file's line numbers: every Mosaic
    kernel's serialized body (MLIR bytecode, which carries each operation's
    source location) is replaced by the digest of that module printed
    WITHOUT debug information. Two programs with the same fingerprint are
    the same operations in the same order, kernels' bodies included:
    ``tests/test_chip_compile.py`` pins the serving cells' against the
    commit before a change that must leave them alone."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def body(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22<' + hashlib.sha256(
            asm.encode()).hexdigest()[:16] + '>\\22'

    text = _KERNEL_BODY.sub(body, lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def describe(name: str, lowered) -> dict:
    """A lowered program compiled: its temporaries, its arguments, what it
    returns beyond the buffers it was donated, its copies of a million
    elements or more."""
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    return {"program": name, "temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "result_bytes": ma.output_size_in_bytes - ma.alias_size_in_bytes,
            "copies": weight_copies(compiled.as_text(),
                                    lowered.args_info[0][0])}


def auto_layouts(compiled, params) -> dict:
    """Leaf path -> the ``major_to_minor`` the compiler chose, for the
    leaves whose choice is not the default order."""
    import jax

    chosen = compiled.input_formats[0][0]
    out = {}
    for (path, leaf), fmt in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(chosen)):
        order = tuple(fmt.layout.major_to_minor)
        if order != tuple(range(len(leaf.shape))):
            out[jax.tree_util.keystr(path)] = {
                "shape": list(leaf.shape), "major_to_minor": list(order)}
    return out


def one_chip():
    """A sharding on the first chip of the described topology, with the
    persistent compile cache off (an entry written for a described chip
    cannot be read back without one)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    return SingleDeviceSharding(topo.devices[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("program", nargs="?",
                    choices=["decode", "chunk", "mixed"])
    ap.add_argument("--default-layouts", action="store_true",
                    help="every parameter in the compiler's default layout")
    ap.add_argument("--auto", action="store_true",
                    help="also print what Layout.AUTO chooses a parameter")
    args = ap.parse_args(argv)

    import jax

    jax.default_backend = lambda: "tpu"
    dev = one_chip()
    cfg, batching = serving_cell(args.cell)

    def wanted(name):
        return args.program is None or name.startswith(args.program)

    for name, low in lowered_programs(
            cfg, batching, dev, relaid=not args.default_layouts,
            mixed=True).items():
        if wanted(name):
            print(json.dumps({"cell": args.cell,
                              **describe(name, low)}), flush=True)
    if args.auto:
        for name, low in lowered_programs(cfg, batching, dev,
                                          auto=True).items():
            if wanted(name):
                print(json.dumps({
                    "cell": args.cell, "program": name,
                    "auto": auto_layouts(low.compile(), low.args_info[0][0])
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
