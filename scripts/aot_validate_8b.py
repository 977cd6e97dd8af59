"""AOT-validate the flagship recipes without hardware (VERDICT round-2
next #5 for Llama-3-8B; round-4 next #2 for Mixtral-8x7B; SURVEY.md §6
"Llama-3-8B-class pretrain, v5p-64" / BASELINE.json configs[2]
"Mixtral 8x7B MoE expert-parallel across multi-slice ICI/DCN").

Uses libtpu's topology-only AOT path (`jax.experimental.topologies`) to
lower + compile — never execute — the REAL train step (fwd+bwd+Adam,
Pallas flash attention, dots_no_batch remat) and the serving decode step
on virtual v5p/v5e meshes, then reads the compiled executable's
per-chip memory analysis against the chip HBM budget (v5p: 95 GB,
v5e: 16 GB). Multi-slice topologies come from the same path
(``num_slices=N``): devices carry distinct ``slice_index`` so GSPMD
plans DCN collectives for the ``dcn`` mesh axis, exactly as on real
multislice pods.

Run: python scripts/aot_validate_8b.py   (one JSON line per config)
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mesh_on(topology: str, axes: dict, *, num_slices: int = 1,
             topo_kwargs: dict = None):
    from jax.experimental import topologies

    from kubeflow_tpu.runtime.mesh import build_mesh

    kw = dict(topo_kwargs or {})
    if num_slices > 1:
        kw["num_slices"] = num_slices
    topo = topologies.get_topology_desc(topology, "tpu", **kw)
    # Fewer mesh slots than described chips: the first of them (one chip
    # of a v5e:2x2 is how the one-chip programs are compiled).
    n = 1
    for size in axes.values():
        n *= size
    return build_mesh(axes, topo.devices[:n])


def _mem_gb(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = 1 << 30
    return {
        "argument_gb": round(m.argument_size_in_bytes / gb, 2),
        "output_gb": round(m.output_size_in_bytes / gb, 2),
        "temp_gb": round(m.temp_size_in_bytes / gb, 2),
        "total_gb": round((m.argument_size_in_bytes + m.temp_size_in_bytes)
                          / gb, 2),
    }


def train_step_analysis(topology: str, axes: dict, *, model="llama3-8b",
                        per_chip_batch=1, pp_layers=None, num_slices=1,
                        seq_len=None, model_overrides=None, optimizer=None):
    """Compile `model`'s train step for `axes` on `topology`; return per-chip
    memory totals in GB from the compiled executable, plus the Pallas
    kernels in the lowered program (``kernels``). ``model_overrides`` /
    ``optimizer``: DecoderConfig / OptimizerConfig fields, as a JAXJob's
    config gives them."""
    import jax

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.runtime.device_report import kernel_calls
    from kubeflow_tpu.train.optim import OptimizerConfig
    from kubeflow_tpu.train.step import make_state_init, setup_train

    mesh = _mesh_on(topology, axes, num_slices=num_slices)
    over = {"remat_policy": "dots_no_batch"}
    if pp_layers:
        over["pipeline_schedule"] = "1f1b"
    if seq_len:
        over["max_seq_len"] = seq_len
    over.update(model_overrides or {})
    cfg = preset(model, **over)
    task = setup_train(
        cfg, OptimizerConfig.from_dict({"total_steps": 10,
                                        **(optimizer or {})}),
        mesh, attn_impl="pallas", init_state=False)
    state_sds = jax.eval_shape(make_state_init(cfg, task.optimizer))
    # Global batch: per_chip_batch per data shard; pipeline runs 2*pp
    # microbatches through the stages.
    batch_shards = 1
    for a in ("dcn", "data", "fsdp"):
        batch_shards *= axes.get(a, 1)
    pp = axes.get("pipeline", 1)
    global_batch = per_chip_batch * batch_shards * (2 * pp if pp > 1 else 1)
    batch_sds = jax.ShapeDtypeStruct((global_batch, cfg.max_seq_len + 1),
                                     jax.numpy.int32)
    lowered = task.step_fn.lower(state_sds, batch_sds)
    return {
        "params_b": round(cfg.num_params() / 1e9, 2),
        **_mem_gb(lowered.compile()),
        "global_batch": global_batch,
        "kernels": kernel_calls(lowered.as_text()),
    }


def paged_serve_analysis(topology: str, tp: int, *, model: str,
                         overrides: dict, slots: int, max_len: int,
                         page_size: int, num_pages: int, chunk: int,
                         decode_steps: int, attn_impl: str,
                         quantize=None, topo_kwargs=None):
    """Compile the PAGED serving programs the engine dispatches —
    ``paged_decode_multi`` (``decode_steps`` per dispatch) and one
    ``paged_chunk_prefill`` at the widest context bucket — for ``model``
    over ``tp`` chips (1 = one chip, no mesh), at a pool of ``num_pages``
    pages of ``page_size``. Returns ``{"decode": {...}, "chunk_prefill":
    {...}}``: per-chip memory in GB and the Pallas kernels in each lowered
    program. Mirrors LLMEngine's paged set-up (serve/engine.py): under a
    mesh the weights shard by the training rules and a KV-head count the
    ``model`` axis does not divide replicates the pool.
    ``quantize="int8"``: weight-only int8 (ops/quantization.py) — the AOT
    density proof that the halved params fit smaller topologies."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import (
        decoder_param_specs, init_decoder_params)
    from kubeflow_tpu.parallel.sharding import shard_params
    from kubeflow_tpu.runtime.device_report import kernel_calls
    from kubeflow_tpu.serve.paged import (
        engine_pool_shapes, paged_chunk_prefill, paged_decode_multi,
        ring_pages)
    from kubeflow_tpu.serve.weight_layout import relay, weight_formats

    mesh = _mesh_on(topology, {"model": tp}, topo_kwargs=topo_kwargs)
    cfg = preset(model, **overrides)
    # the ring a sequence keeps in window layers (engine.serving_configs)
    cfg = dataclasses.replace(cfg, window_ring_pages=ring_pages(
        cfg, chunk, page_size, max_len // page_size))
    if tp > 1:
        # LLMEngine's rule: no Mosaic norm/GLU kernels over sharded operands.
        cfg = dataclasses.replace(cfg, fused_kernels="off")

    def _abstract_params():
        p = init_decoder_params(jax.random.PRNGKey(0), cfg)
        if quantize == "int8":
            from kubeflow_tpu.ops.quantization import quantize_params_int8

            p = quantize_params_int8(p, cfg)
        return p

    params_sds = jax.eval_shape(_abstract_params)
    if tp > 1:
        psh = shard_params(params_sds, decoder_param_specs(cfg), mesh)
        kv_ps = (PartitionSpec(None, None, None, "model", None)
                 if cfg.n_kv_heads % tp == 0 else PartitionSpec())
        rep = NamedSharding(mesh, PartitionSpec())
        kv_sh = NamedSharding(mesh, kv_ps)
    else:
        kv_sh = rep = SingleDeviceSharding(mesh.devices.flat[0])
        psh = jax.tree.map(lambda _: rep, params_sds)
    params_sds = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_sds, psh)
    # The last step of the engine's load path: on one chip with the Pallas
    # kernels the per-head projections lie as the programs read them.
    params_sds = relay(params_sds, weight_formats(
        params_sds, cfg, one_chip_pallas=tp == 1 and attn_impl == "pallas"))

    def sds(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    mpp = max_len // page_size
    cache = {n: sds(shape, dt, kv_sh if n in ("k", "v") else rep)
             for n, (shape, dt) in engine_pool_shapes(
                 cfg, slots, num_pages, page_size).items()}
    i32, f32 = (lambda: sds((slots,), jnp.int32)), (
        lambda: sds((slots,), jnp.float32))

    decode = jax.jit(
        lambda p, c, tbl, t, ln, lv, tmp, tk, tpp, st, bd, key:
        paged_decode_multi(p, {**c, "table": tbl}, t, ln, lv, tmp, tk, tpp,
                           st, bd, key, cfg, decode_steps,
                           sample_mode="greedy", attn_impl=attn_impl),
        donate_argnums=(1,)).lower(
            params_sds, cache, sds((slots, mpp), jnp.int32), i32(), i32(),
            sds((slots,), jnp.bool_), f32(), i32(), f32(), i32(), i32(),
            sds((2,), jnp.uint32))
    chunked = jax.jit(
        lambda p, c, t, tr, st, vl: paged_chunk_prefill(
            p, c, t, tr, st, vl, cfg, context_pages=mpp,
            paged_attn_impl="pallas" if attn_impl == "pallas" else "gather"),
        donate_argnums=(1,)).lower(
            params_sds, cache, sds((1, chunk), jnp.int32),
            sds((1, mpp), jnp.int32), sds((1,), jnp.int32),
            sds((1,), jnp.int32))
    return {name: {**_mem_gb(low.compile()),
                   "kernels": kernel_calls(low.as_text())}
            for name, low in (("decode", decode),
                              ("chunk_prefill", chunked))}


# The serving points: bf16 weights, 16 slots of 2048 tokens held whole in
# the pool (16 pages of 128 a slot), the engine's default chunk and steps a
# dispatch, attention through XLA (a mesh takes no Mosaic kernel). Mixtral
# decodes through every expert, as the engine resolves it
# (moe_decode_impl="auto").
SERVE_BF16 = {
    "llama3-8b": {"dtype": "bfloat16", "param_dtype": "bfloat16"},
    "mixtral-8x7b": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                     "moe_impl": "dense"},
}
SERVE_POOL = dict(slots=16, max_len=2048, page_size=128, num_pages=256,
                  chunk=512, decode_steps=16, attn_impl="gather")

CONFIGS = [
    ("train", "v5p:2x2x4", {"fsdp": 8, "model": 2}, {"per_chip_batch": 1}),
    ("train", "v5p:4x4x4", {"fsdp": 16, "model": 4}, {"per_chip_batch": 1}),
    ("train", "v5p:4x4x4", {"pipeline": 4, "fsdp": 8, "model": 2},
     {"per_chip_batch": 1, "pp_layers": True}),
    # Mixtral-8x7B north star (BASELINE.json configs[2]): expert-parallel
    # training at v5p-64, the same across a 2-slice DCN multislice, and
    # bf16 serving on v5e-8 (below, after the train table).
    ("train", "v5p:4x4x4", {"expert": 8, "fsdp": 8},
     {"model": "mixtral-8x7b", "per_chip_batch": 1}),
    ("train", "v5p:2x4x4", {"dcn": 2, "expert": 8, "fsdp": 4},
     {"model": "mixtral-8x7b", "per_chip_batch": 1, "num_slices": 2}),
]


def main():
    budget = {"v5p": 95.0, "v5e": 16.0}
    for kind, topo, axes, kw in CONFIGS:
        out = train_step_analysis(topo, axes, **kw)
        out.update(kind=kind, topology=topo, axes=axes,
                   model=kw.get("model", "llama3-8b"),
                   num_slices=kw.get("num_slices", 1),
                   budget_gb=budget["v5p"],
                   fits=out["total_gb"] < budget["v5p"])
        print(json.dumps(out), flush=True)
    for model in ("llama3-8b", "mixtral-8x7b"):
        out = paged_serve_analysis("v5e:2x4x1", 8, model=model,
                                   overrides=SERVE_BF16[model], **SERVE_POOL)
        out.update(kind="serve", topology="v5e-8", axes={"model": 8},
                   model=model, budget_gb=budget["v5e"],
                   fits=all(p["total_gb"] < budget["v5e"]
                            for p in out.values()))
        print(json.dumps(out), flush=True)
    # int8 density points (VERDICT r4 #3): weight-only int8 on smaller
    # topologies than bf16 can reach.
    for topo, tp, kw in (
            ("v5e:1x1x1", 1,
             {"topo_kwargs": {"chips_per_host_bounds": [1, 1, 1]},
              "slots": 8, "num_pages": 128}),
            ("v5e:2x2x1", 4, {})):
        out = paged_serve_analysis(topo, tp, model="llama3-8b",
                                   overrides=SERVE_BF16["llama3-8b"],
                                   quantize="int8", **{**SERVE_POOL, **kw})
        out.update(kind="serve_int8", topology=topo,
                   axes={"model": tp}, model="llama3-8b",
                   budget_gb=budget["v5e"],
                   fits=all(p["total_gb"] < budget["v5e"]
                            for p in out.values()))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
