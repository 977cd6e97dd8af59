"""Sizes settled with the chip's compiler, by hand, before any chip call:

    JAX_PLATFORMS=cpu python3 -m benchmark.aot_sizes [cell ...]

Compiles each cell's programs for a DESCRIBED ``v5e:2x2`` at the real sizes
(nothing runs, no chip is needed) and prints ``memory_analysis()`` per chip:
the serving cells' decode dispatch and widest chunk prefill against their
weights and page pool, the training step under fsdp=4, and the plain
reference's programs, which must fit beside what the cell keeps resident.
Its output for the sizes chosen is in PERF.md and in each configuration's
``reduced``. Not a test (the repo has its one topology-fixture test file) and
not part of a run. It leans on ``scripts/aot_validate_8b.py``, which already
describes the topology and lowers these programs.

Code that asks ``jax.default_backend()`` sees the CPU here; this script
answers "tpu" for it, as tests/test_aot_8b.py does. A compile that passes is
not a chip run.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
TOPOLOGY = "v5e:2x2"
USABLE_GB = 15.75


def _gb(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"argument_gb": ma.argument_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9,
            "total_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                         + ma.output_size_in_bytes
                         - ma.alias_size_in_bytes) / 1e9}


def serving(conf: dict, traffic: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from scripts.aot_validate_8b import _mesh_on, paged_serve_analysis

    from benchmark import architecture, weights

    e, prog = traffic["engine"], conf["program"]
    out = paged_serve_analysis(
        TOPOLOGY, 1, model=prog["preset"], overrides=prog["overrides"],
        slots=e["max_batch_size"], max_len=e["max_seq_len"],
        page_size=e["page_size"], num_pages=e["max_pages"],
        chunk=e["chunked_prefill_tokens"], decode_steps=e["decode_steps"],
        attn_impl="pallas")
    dev = SingleDeviceSharding(_mesh_on(TOPOLOGY, {"model": 1}).devices.flat[0])
    dtype = jnp.dtype(prog["overrides"]["param_dtype"])
    p_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
        weights.param_shapes(conf, dtype))
    plen, n_dec = max(conf["correctness"]["sequences"])
    toks = jax.ShapeDtypeStruct((plen + n_dec,), jnp.int32, sharding=dev)
    logits = architecture.part(conf, "reference").logits
    param_tree = architecture.part(conf, "weights").param_tree
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: logits(
            p, t, conf, last=520)).lower(p_sds, toks).compile()
    out["reference_logits"] = {**_gb(ref), "tokens": plen + n_dec}
    init = jax.jit(lambda k: param_tree(conf, k, dtype),
                   out_shardings=dev).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)).compile()
    out["weights_init"] = _gb(init)
    return out


def training(conf: dict, traffic: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from scripts.aot_validate_8b import _mesh_on, train_step_analysis

    from kubeflow_tpu.train.optim import OptimizerConfig
    from kubeflow_tpu.train.step import setup_train

    from benchmark import architecture, correctness, weights

    prog, mesh_axes = conf["program"], conf["mesh"]
    chips = 1
    for n in mesh_axes.values():
        chips *= n
    out = {"train_step": train_step_analysis(
        TOPOLOGY, mesh_axes, model=prog["preset"],
        per_chip_batch=traffic["global_batch"] // chips,
        seq_len=traffic["seq_len"], model_overrides=prog["overrides"],
        optimizer=conf["trainer"]["optimizer"])}
    mesh = _mesh_on(TOPOLOGY, mesh_axes)
    cfg = architecture.part(conf, "program").program_config(
        conf, max_seq_len=traffic["seq_len"])
    task = setup_train(cfg, OptimizerConfig.from_dict(
        {"total_steps": 10, **conf["trainer"]["optimizer"]}), mesh,
        attn_impl="pallas", init_state=False)
    p_sh = task.state_shardings["params"]
    dtype = jnp.dtype(cfg.param_dtype)
    p_sds = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        weights.param_shapes(conf, dtype), p_sh)
    micro = conf["correctness"]["micro"]
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    axes = tuple(a for a, n in mesh_axes.items() if n > 1)
    rep = NamedSharding(mesh, PartitionSpec())
    run = correctness.loss_and_grad_norm_program(conf, gb * seq,
                                                 batch_axes=axes)
    with jax.default_matmul_precision("highest"), mesh:
        ref = jax.jit(run).lower(p_sds, jax.ShapeDtypeStruct(
            (gb // micro, micro, seq + 1), jnp.int32, sharding=rep)).compile()
    out["reference_loss_grad"] = {**_gb(ref), "micro": micro}
    param_tree = architecture.part(conf, "weights").param_tree
    init = jax.jit(lambda k: param_tree(conf, k, dtype),
                   out_shardings=p_sh).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()
    out["weights_init"] = _gb(init)
    return out


def main(argv) -> int:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import manifest as mf

    # An entry written for a described chip cannot be read back without one.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.default_backend = lambda: "tpu"
    sys.path.insert(0, mf.ROOT)
    manifest = mf.load_manifest()
    names = argv[1:] or [w["name"] for w in manifest["workloads"]]
    for name in names:
        cell = mf.cell(manifest, name)
        conf = mf.load_config(manifest, cell["config"])
        traffic = mf.load_traffic(cell["traffic"])
        fn = training if traffic["kind"] == "train_steps" else serving
        out = fn(conf, traffic)
        print(json.dumps({"cell": name, "usable_gb": USABLE_GB, **out},
                         indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
