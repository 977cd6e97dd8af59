"""Probe the chip's practical envelope: big-matmul TFLOPs (the real MXU
peak through this stack), HBM stream bandwidth, and the train step's
fwd vs fwd+bwd split for the bench model."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, reps=5):
    import jax
    jax.block_until_ready(fn(*args))          # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp

    results = {}

    # 1. Pure matmul peak, bf16 (8k^3 = 1.1 TFLOP per op)
    f = jax.jit(lambda a, b: a @ b)   # one wrapper; each shape traces once
    for n in (4096, 8192):
        a = jnp.ones((n, n), jnp.bfloat16)
        bmat = jnp.ones((n, n), jnp.bfloat16)
        dt = timeit(f, a, bmat)
        results[f"matmul{n}_tflops"] = round(2 * n**3 / dt / 1e12, 1)

    # 2. HBM stream: elementwise over 1 GB
    x = jnp.ones((512, 1024, 1024), jnp.bfloat16)   # 1 GiB
    f = jax.jit(lambda x: x * 1.5 + 2.0)
    dt = timeit(f, x)
    results["stream_gbps"] = round(2 * x.nbytes / dt / 1e9, 1)  # r+w

    # 3. Train model: fwd-only vs full step
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import decoder_loss
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.train.data import DataConfig, make_data_source
    from kubeflow_tpu.train.optim import OptimizerConfig
    from kubeflow_tpu.train.step import setup_train

    cfg = preset(
        "llama3-8b",
        n_layers=8, hidden=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        mlp_dim=8192, vocab_size=32000, max_seq_len=2048)
    devices = jax.devices()
    mesh = build_mesh({"fsdp": len(devices)}, devices)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=cfg.max_seq_len,
                          global_batch=4 * len(devices))
    source = make_data_source(data_cfg)
    task = setup_train(cfg, OptimizerConfig(total_steps=100), mesh)
    batch = jax.device_put(source.batch_at(0), task.batch_sharding)

    fwd = jax.jit(lambda p, b: decoder_loss(p, b, cfg, mesh=mesh)[0])
    dt_f = timeit(fwd, task.state["params"], batch, reps=4)
    results["fwd_only_ms"] = round(dt_f * 1e3, 1)

    grad = jax.jit(lambda p, b: jax.grad(
        lambda pp: decoder_loss(pp, b, cfg, mesh=mesh)[0])(p))
    dt_g = timeit(grad, task.state["params"], batch, reps=4)
    results["fwd_bwd_ms"] = round(dt_g * 1e3, 1)

    tokens = data_cfg.global_batch * data_cfg.seq_len
    fwd_tflop = 2 * cfg.num_params() * tokens / 1e12
    results["fwd_mxu_tflops"] = round(fwd_tflop / dt_f, 1)
    results["fwdbwd_mxu_tflops"] = round(
        (3 * fwd_tflop + fwd_tflop) / dt_g, 1)   # 6N + remat 2N = 8N

    print(json.dumps(results))


if __name__ == "__main__":
    main()
