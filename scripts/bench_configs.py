"""Measured numbers for BASELINE.json configs 3-5 (the round-1 verdict's
missing benchmark rows). One JSON line per row; `--all` writes
BENCH_CONFIGS.json at the repo root.

- ``mixtral``: Mixtral-architecture MoE (8 experts, top-2, GQA) scaled to
  one chip's HBM, trained with the default capacity-factor DISPATCH MoE
  (only selected experts compute — measured 1.81× the dense oracle's
  tok/s at identical loss; EP sharding splits the expert dim on
  multi-chip meshes — dryrun_multichip covers that compilation).
  Reports tok/s/chip and ACTIVE-params MFU.
- ``vit``: ViT-L/16 supervised training driven AS A PIPELINES DAG
  (make-config → train-on-chip → summarize), the BASELINE "ViT-L/CLIP via
  pipelines" shape; components run in-process so the train step owns the
  chip. Reports images/sec/chip and DAG wall-clock overhead.
- ``gemma-chip``: gemma-2b architecture scaled to one chip, measured
  directly (tok/s/chip on TPU).
- ``gemma-sweep``: the Katib-analog HPO sweep — 4 random-search trials of
  tiny-gemma through the LIVE control plane with real worker processes
  (orchestration wall-clock; CPU workers — a chip belongs to one process
  at a time, so parallel trials cannot share it).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train_rate(cfg, per_chip_batch, *, k_dispatch=8, disp=3, warm=2,
                mu="bfloat16", lr=None, attn_impl=None):
    """Thin wrapper over bench.measure_train_rate — ONE measurement
    methodology for every training-throughput row (same dispatch loop,
    fencing, MFU accounting AND knob defaults as the headline bench,
    via bench.TrainKnobs)."""
    from bench import HEADLINE_KNOBS, measure_train_rate

    import jax

    if attn_impl is None:
        attn_impl = HEADLINE_KNOBS.attn_impl(jax.default_backend() == "tpu")
    elif jax.default_backend() != "tpu":
        attn_impl = "xla"          # interpret-mode kernels are CI-only
    return measure_train_rate(cfg, per_chip_batch, k_dispatch=k_dispatch,
                              warm_disp=warm, disp=disp, mu_dtype=mu,
                              learning_rate=lr, attn_impl=attn_impl)


def bench_mixtral():
    """BASELINE config 3: Mixtral 8x7B architecture (8 experts, top-2),
    scaled to one chip's HBM at the same expert/hidden ratios."""
    import jax

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.runtime.topology import chip_for_device_kind

    cfg = preset(
        "mixtral-8x7b",
        n_layers=8, hidden=1024, n_heads=16, n_kv_heads=4, head_dim=64,
        mlp_dim=3584, vocab_size=32000, max_seq_len=2048,
        remat_policy="block_outs", loss_chunk_size=512,
    )
    out = _train_rate(cfg, per_chip_batch=4)
    peak = chip_for_device_kind(jax.devices()[0].device_kind).bf16_tflops
    active_mfu = (None if peak is None else
                  cfg.flops_per_token() * out["tok_s_chip"] / (peak * 1e12))
    return {
        "metric": "mixtral_moe_train_tokens_per_sec_per_chip"
                  "[mixtral-0.8b-8e-top2,seq2048]",
        "value": out["tok_s_chip"], "unit": "tokens/sec/chip",
        "detail": {**out, "active_param_mfu": (None if active_mfu is None
                                                 else round(active_mfu, 4)),
                   "num_experts": 8, "experts_per_token": 2,
                   "moe_impl": "dispatch",
                   "capacity_factor": 1.25,
                   "note": "capacity-factor dispatch MoE (default): only "
                           "selected experts compute; the dense oracle "
                           "measured 14.1k tok/s on the same config "
                           "(BASELINE.md round-3 table)"},
    }


def bench_vit():
    """BASELINE config 4: ViT-L/16 supervised training as a pipelines DAG."""
    import jax

    from kubeflow_tpu.pipelines import dsl
    from kubeflow_tpu.pipelines.compiler import compile_pipeline
    from kubeflow_tpu.pipelines.artifacts import ArtifactStore
    from kubeflow_tpu.pipelines.executor import PipelineExecutor
    from kubeflow_tpu.pipelines.metadata import MetadataStore
    import tempfile

    @dsl.component
    def make_config(steps: int, batch: int) -> dict:
        return {"steps": steps, "batch": batch}

    @dsl.component
    def train_vit(plan: dict) -> dict:
        from kubeflow_tpu.models.vision import vit_preset
        from kubeflow_tpu.runtime.mesh import build_mesh
        from kubeflow_tpu.train.optim import OptimizerConfig
        from kubeflow_tpu.train.vision_task import setup_vit_train, vit_batch

        devices = jax.devices()
        mesh = build_mesh({"data": len(devices)}, devices)
        cfg = vit_preset("vit-l16")
        task = setup_vit_train(cfg, OptimizerConfig(total_steps=10_000), mesh)
        state = task.state
        warm, timed = 2, plan["steps"]
        # Image batches are ~38 MB each; the host->device upload is not
        # what this row measures. Stage a few batches on device once (real input pipelines double-buffer the same way)
        # and cycle them in the timed loop.
        staged = [jax.device_put(vit_batch(cfg, plan["batch"], i),
                                 task.batch_shardings) for i in range(4)]
        for i in range(warm):
            state, m = task.step_fn(state, staged[i % len(staged)])
            float(m["loss"])
        t0 = time.perf_counter()
        for i in range(timed):
            state, m = task.step_fn(state, staged[(warm + i) % len(staged)])
            m["loss"].block_until_ready()
        dt = time.perf_counter() - t0
        return {"images_per_sec": plan["batch"] * timed / dt,
                "step_ms": dt / timed * 1e3, "loss": float(m["loss"])}

    @dsl.component
    def summarize(train: dict) -> float:
        return train["images_per_sec"]

    @dsl.pipeline(name="vit-l16-train")
    def vit_pipeline(steps: int = 8, batch: int = 64):
        plan = make_config(steps=steps, batch=batch)
        out = train_vit(plan=plan)
        summarize(train=out)

    td = tempfile.mkdtemp(prefix="vitbench-")
    store = MetadataStore(os.path.join(td, "mlmd.db"))
    ex = PipelineExecutor(ArtifactStore(os.path.join(td, "arts")), store)
    ir = compile_pipeline(vit_pipeline)
    t0 = time.perf_counter()
    run = ex.run(ir, parameters={"steps": 8, "batch": 64})
    wall = time.perf_counter() - t0
    store.close()
    from kubeflow_tpu.pipelines.executor import RunPhase

    assert run.phase is RunPhase.SUCCEEDED, run
    detail = run.tasks["train_vit"].outputs["output"]
    return {
        "metric": "vit_l16_train_images_per_sec_per_chip[pipelines-dag]",
        "value": round(detail["images_per_sec"] / len(jax.devices()), 1),
        "unit": "images/sec/chip",
        "detail": {"step_ms": round(detail["step_ms"], 2),
                   "dag_wall_s": round(wall, 1),
                   "loss": round(detail["loss"], 4),
                   "batch": 64, "timed_steps": 8},
    }


def bench_gemma_chip():
    """BASELINE config 5a: Gemma-2B architecture scaled to one chip
    (wide-head GQA, GeGLU, tied embeddings, 256k-vocab ratios kept via the
    chunked-CE head)."""
    from kubeflow_tpu.models.config import preset

    cfg = preset(
        "gemma-2b",
        n_layers=8, hidden=1024, n_heads=8, n_kv_heads=1, head_dim=128,
        mlp_dim=8192, vocab_size=64000, max_seq_len=2048,
        remat_policy="block_outs", loss_chunk_size=256,
    )
    out = _train_rate(cfg, per_chip_batch=4, lr=1e-4)
    return {
        "metric": "gemma_scaled_train_tokens_per_sec_per_chip"
                  "[gemma-0.4b,seq2048]",
        "value": out["tok_s_chip"], "unit": "tokens/sec/chip",
        "detail": {**out,
                   "note": "loss is init-dominated over a 40-step "
                           "throughput window (embed_scale x tied head "
                           "at this width inflates initial logits); "
                           "convergence is covered by the tiny-gemma "
                           "training tests"},
    }


def bench_gemma_sweep():
    """BASELINE config 5b: the HPO sweep itself — 4 random-search trials of
    tiny-gemma through the live control plane with real worker processes
    (orchestration wall-clock; the platform half of the Katib config)."""
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology
    from kubeflow_tpu.tune.client import build_experiment, parameter

    plane = ControlPlane(ControlPlaneConfig(
        base_dir=tempfile.mkdtemp(prefix="sweep-"),
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="cpu",
                                              dims=(2, 2))]),
        platform="cpu"))
    plane.start()
    try:
        exp = build_experiment(
            "gemma-sweep", entrypoint="llm_pretrain",
            parameters=[
                parameter("learning_rate", min=3e-4, max=3e-3,
                          log_scale=True),
                parameter("warmup_steps", min=0, max=4),
            ],
            objective_metric="loss", algorithm="random",
            algorithm_settings={"random_state": 0},
            max_trial_count=4, parallel_trial_count=2,
            metric_source="push",
            base_config={
                "model": "tiny-gemma", "steps": 12, "log_every": 4,
                "optimizer": {
                    "learning_rate": "${trialParameters.learning_rate}",
                    "warmup_steps": "${trialParameters.warmup_steps}"},
                "data": {"global_batch": 4, "seq_len": 64},
            })
        t0 = time.perf_counter()
        plane.submit(exp)
        done = plane.wait_for(exp, "Succeeded", timeout=600)
        wall = time.perf_counter() - t0
        opt = done.status.current_optimal_trial
        return {
            "metric": "katib_sweep_wall_clock_s"
                      "[tiny-gemma,4-trials,2-parallel]",
            "value": round(wall, 1), "unit": "seconds",
            "detail": {"trials_succeeded": done.status.trials_succeeded,
                       "best_objective": round(opt.objective_value, 4),
                       "best_params": opt.parameter_assignments},
        }
    finally:
        plane.stop()


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "--all"
    benches = {
        "mixtral": bench_mixtral,
        "vit": bench_vit,
        "gemma-chip": bench_gemma_chip,
        "gemma-sweep": bench_gemma_sweep,
    }
    if which != "--all":
        if which not in benches:
            sys.exit(f"unknown bench {which!r}; one of "
                     f"{sorted(benches)} or --all")
        print(json.dumps(benches[which]()))
        return
    rows = []
    for name, fn in benches.items():
        try:
            row = fn()
        except Exception as exc:   # record the failure, keep benching
            row = {"metric": name, "failed": True,
                   "err": f"{type(exc).__name__}: {exc}"}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_CONFIGS.json")
    with open(out, "w") as f:
        json.dump({"rows": rows, "round": 2,
                   "script": "scripts/bench_configs.py"}, f, indent=1)


if __name__ == "__main__":
    main()
