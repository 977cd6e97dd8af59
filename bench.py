"""Headline benchmark: JAXJob training throughput, tokens/sec/chip.

Runs the full sharded train step (fwd+bwd+Adam, donated state, bf16 compute)
on every local device and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is measured against round 1's 13,673 tok/s/chip on the same
llama3-0.6b / seq2048 / batch-4-per-chip config (the reference platform
publishes no training numbers — BASELINE.md). That figure, and the knob
values below, are pre-round: none has been measured on the machine builders
have now, and this script's model is not a benchmark configuration
(ROADMAP.md Speed item 1 replaces it).

Round-4 configuration (BASELINE.md round-4 table, pre-round):
- per-chip batch 5 with "dots_flash" remat: dots_no_batch plus the flash
  kernel's saved (o, lse) — without the names the backward replays the
  forward kernel per layer just to rebuild its VJP residuals.
- 32 train steps per device dispatch.
- the round-3 flash kernels (bf16 MXU inputs, (1024,1024) blocks), bf16
  Adam first moment, unchunked CE.
- A fused one-pass AdamW (optim.FusedAdamW) measured a TIE with the optax
  chain — XLA already fuses the chain's elementwise stages — so it stays
  available but off; the step-time decomposition lives in BASELINE.md.

Methodology: warm dispatches compile and settle the exact dispatch set,
then TWO back-to-back measured segments run and both are reported with
their spread, so a single short window cannot be mistaken for the rate.

There is no CPU path: with no TPU this script exits non-zero and prints no
result, because a CPU timing is not a device metric.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

ROUND1_TOKS_PER_SEC_CHIP = 13673.23


@dataclasses.dataclass(frozen=True)
class TrainKnobs:
    """The headline training-knob set — ONE struct shared by bench.py,
    scripts/bench_configs.py and scripts/mfu_sweep.py so the sweep rows
    and the headline number can never drift apart (they used to hardcode
    ``attn_impl="pallas" if on_tpu else "xla"`` and the remat policy
    inline, independently). Values are the measured round-4/6 winners;
    change them HERE and every measurement follows."""

    remat_policy: str = "dots_flash"
    attn_impl_tpu: str = "pallas"
    attn_impl_off_tpu: str = "xla"   # interpret-mode kernels are CI-only
    fused_kernels: str = "auto"      # ops/fused_xent.py + ops/fused_norm.py
    mu_dtype_tpu: str = "bfloat16"

    def attn_impl(self, on_tpu: bool) -> str:
        return self.attn_impl_tpu if on_tpu else self.attn_impl_off_tpu

    def mu_dtype(self, on_tpu: bool):
        return self.mu_dtype_tpu if on_tpu else None


HEADLINE_KNOBS = TrainKnobs()


def apply_perf_flags_if_tpu() -> None:
    """Latency-hiding flag set (runtime/xla_flags.py, delivered through
    LIBTPU_INIT_ARGS) ahead of backend init — skipped when the platform
    is forced to CPU (the flags are TPU-only)."""
    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        return
    from kubeflow_tpu.runtime.xla_flags import apply_xla_perf_flags

    apply_xla_perf_flags()


def measure_train_rate(cfg, per_chip_batch, *, k_dispatch, warm_disp, disp,
                       mu_dtype=None, learning_rate=None, attn_impl="xla",
                       segments=2, fused_optimizer=False):
    """The one train-throughput measurement loop every bench shares
    (bench.py headline + scripts/bench_configs.py rows): K steps per
    dispatch over an fsdp mesh, warm dispatches excluded, then ``segments``
    back-to-back measured windows of ``disp`` dispatches each (the topline
    is their mean; the per-segment rates and spread ride along). Each
    dispatch is fenced with ``block_until_ready`` on its loss. The MFU
    peak is the device's own row of runtime/topology.py CHIPS. Returns
    {tok_s_chip, step_ms, mfu, loss, segments, spread_pct}."""
    import jax

    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.runtime.topology import chip_for_device_kind
    from kubeflow_tpu.train.data import (
        DataConfig, make_data_source, stacked_batches,
    )
    from kubeflow_tpu.train.optim import OptimizerConfig
    from kubeflow_tpu.train.staging import DeviceBatchStager
    from kubeflow_tpu.train.step import setup_train

    devices = jax.devices()
    n = len(devices)
    mesh = build_mesh({"fsdp": n}, devices)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=cfg.max_seq_len,
                          global_batch=per_chip_batch * n)
    source = make_data_source(data_cfg)
    opt_kw = {}
    if learning_rate is not None:
        opt_kw["learning_rate"] = learning_rate
    task = setup_train(
        cfg, OptimizerConfig(total_steps=max(
            (warm_disp + segments * disp) * k_dispatch, 10_000),
                             mu_dtype=mu_dtype, fused=fused_optimizer,
                             **opt_kw),
        mesh, attn_impl=attn_impl)

    def fetch(di):
        # Build + upload for dispatch ``di`` — runs on the stager's
        # background thread so the host work overlaps device compute.
        batch = stacked_batches(source, di * k_dispatch, k_dispatch)
        return jax.device_put(batch, task.multi_batch_sharding)

    state = task.state
    with DeviceBatchStager(fetch, depth=2, name="bench-stager") as stager:
        def dispatch(di, state):
            state, metrics = task.multi_step_fn(state, stager.get(di))
            return state, metrics["loss"].block_until_ready()   # the fence

        for i in range(warm_disp):
            state, loss = dispatch(i, state)
        steps = disp * k_dispatch
        tokens_per_seg = data_cfg.global_batch * data_cfg.seq_len * steps
        seg_rates = []
        i0 = warm_disp
        for _ in range(max(1, segments)):
            t0 = time.perf_counter()
            for i in range(i0, i0 + disp):
                state, loss = dispatch(i, state)
            dt = time.perf_counter() - t0
            seg_rates.append(tokens_per_seg / dt / n)
            i0 += disp

    tps_chip = sum(seg_rates) / len(seg_rates)
    peak = chip_for_device_kind(devices[0].device_kind).bf16_tflops
    # No published peak (the CPU, for a caller that times tiny configs
    # there): no utilization.
    mfu = (None if peak is None
           else round(cfg.flops_per_token() * tps_chip / (peak * 1e12), 4))
    return {
        "tok_s_chip": round(tps_chip, 2),
        # tokens/step ÷ (tokens/s across all chips) = seconds/step.
        "step_ms": round(1e3 * data_cfg.global_batch * data_cfg.seq_len
                         / (tps_chip * n), 2),
        "mfu": mfu,
        "loss": round(float(loss), 4),
        "segments": [round(r, 2) for r in seg_rates],
        "spread_pct": round(100 * (max(seg_rates) - min(seg_rates))
                            / max(seg_rates), 1),
    }


def probe_chip_tflops(n: int = 8192, k1: int = 32, k2: int = 64):
    """Asymptotic bf16 matmul rate: records the practical MXU peak of
    THIS run next to the bench numbers, so a `vs_baseline` ratio can be
    read against the chip's state at measurement time.

    Slope method (BASELINE.md round-2 chip-envelope notes): time k1 and k2
    CHAINED matmuls in single dispatches and divide the extra FLOPs by the
    extra time, so the fixed per-dispatch cost cancels out."""
    import jax
    import jax.numpy as jnp

    a = jnp.ones((n, n), jnp.bfloat16)
    inv = jnp.bfloat16(1.0 / n)     # keep the chained values at ~1.0

    def chain(k):
        def f(x, a):
            def body(x, _):
                return (x @ a) * inv, None

            x, _ = jax.lax.scan(body, x, None, length=k)
            return x

        return jax.jit(f)

    times = {}
    for k in (k1, k2):
        f = chain(k)
        f(a, a).block_until_ready()              # compile
        best = float("inf")
        for _rep in range(3):        # min-of-3: host hiccups inflate, never
            t0 = time.perf_counter()  # deflate, a timing
            f(a, a).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        times[k] = best
    dt = times[k2] - times[k1]
    if dt <= 0:
        # A flaky window inverted the slope: report invalid, not a number
        # pretending to be the chip's peak.
        return None
    return round(2 * n**3 * (k2 - k1) / dt / 1e12, 1)


def _fused_resolved(cfg) -> bool:
    from kubeflow_tpu.models.layers import fused_kernels_on

    return fused_kernels_on(cfg)


def run_bench():
    apply_perf_flags_if_tpu()    # before the backend initializes

    import jax

    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.runtime.bootstrap import enable_compilation_cache

    # The compile cache goes on before the backend exists, like a worker's
    # (runtime/bootstrap.py); measured segments warm first, so the cache
    # never touches the numbers.
    enable_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        # No chip, no number: a CPU timing under the name tokens/sec/chip
        # is not a device metric.
        raise SystemExit(
            f"bench.py needs a TPU; jax found {devices[0].platform!r} "
            f"({devices[0].device_kind})")
    n = len(devices)
    probe_tflops = probe_chip_tflops()

    knobs = HEADLINE_KNOBS
    # Llama-3 architecture sized to fit one v5e chip's HBM with fp32
    # Adam state (~0.6B params): the per-chip unit of the 8B recipe.
    # Knob values are the pre-round winners (TrainKnobs docstring).
    cfg = preset(
        "llama3-8b",
        n_layers=8, hidden=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        mlp_dim=8192, vocab_size=32000, max_seq_len=2048,
        remat_policy=knobs.remat_policy,
        fused_kernels=knobs.fused_kernels,
    )
    model_tag = "llama3-0.6b"
    per_chip_batch, k_dispatch, warm_disp, disp = 5, 32, 3, 2

    out = measure_train_rate(
        cfg, per_chip_batch, k_dispatch=k_dispatch, warm_disp=warm_disp,
        disp=disp, mu_dtype=knobs.mu_dtype(True),
        attn_impl=knobs.attn_impl(True))

    return {
        "metric": f"jaxjob_train_tokens_per_sec_per_chip[{model_tag},"
                  f"seq{cfg.max_seq_len},tpux{n}]",
        "value": out["tok_s_chip"],
        "unit": "tokens/sec/chip",
        "vs_baseline": round(out["tok_s_chip"] / ROUND1_TOKS_PER_SEC_CHIP, 4),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n},
        "detail": {
            "step_time_ms": out["step_ms"],
            "mfu_vs_peak": out["mfu"],
            "steps_per_dispatch": k_dispatch,
            # The fused-kernel knob as configured AND as resolved for this
            # backend (layers.fused_kernels_on) — the A/B axis of the
            # r05→r06 trajectory.
            "fused_kernels": cfg.fused_kernels,
            "fused_resolved": _fused_resolved(cfg),
            "remat_policy": cfg.remat_policy,
            "loss": out["loss"],
            "params": cfg.num_params(),
            "segments": out["segments"],
            "spread_pct": out["spread_pct"],
            # Matmul-rate probe measured in THIS run: read vs_baseline
            # against it.
            "probe_tflops": probe_tflops,
        },
    }


if __name__ == "__main__":
    result = run_bench()
    print(json.dumps(result))
    sys.exit(0)
