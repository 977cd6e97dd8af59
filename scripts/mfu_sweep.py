"""On-chip MFU sweep: time the full train step across remat / attention /
batch / steps-per-dispatch / Adam-mu-dtype / fused-kernel grids.

Each config runs in a subprocess (isolation keeps one failed compile or
out-of-memory from killing the sweep, and one process holds the chip at a
time). Prints one
JSON line per config. Rows run through ``bench.measure_train_rate`` — the
SAME dispatch loop, fencing, two-segment spread and MFU accounting as the
headline bench (and the same ``TrainKnobs`` defaults), so a sweep row and
the headline number can never measure different things.

Usage:
    python scripts/mfu_sweep.py                                   # grid
    python scripts/mfu_sweep.py --one <remat> <attn> <batch> [k] [mu] [fused]
    python scripts/mfu_sweep.py --fused on                        # A/B half
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GRID = [
    # (remat_policy, attn_impl, per_chip_batch, k_dispatch, mu_dtype, fused)
    ("nothing_saveable", "xla", 4, 1, "none", "off"),   # round-1 baseline
    ("nothing_saveable", "xla", 4, 16, "none", "off"),  # dispatch amortization
    ("block_outs", "xla", 4, 16, "none", "off"),        # round-2 headline
    ("block_outs", "xla", 4, 16, "bfloat16", "off"),
    ("block_outs", "pallas", 4, 16, "bfloat16", "off"),
    ("dots_no_batch", "xla", 4, 16, "bfloat16", "off"),
    ("none", "pallas", 4, 16, "bfloat16", "off"),
    # The round-6 A/B: headline knobs with the fused Pallas kernels
    # (blockwise CE + RMSNorm/SwiGLU) off vs on.
    ("dots_flash", "pallas", 5, 32, "bfloat16", "off"),
    ("dots_flash", "pallas", 5, 32, "bfloat16", "on"),
]


def run_one(remat: str, attn: str, batch: int, kd: int = 1,
            mu: str = "none", fused: str = "auto", disp: int = 2,
            warm_disp: int = 2):
    from bench import apply_perf_flags_if_tpu, measure_train_rate

    apply_perf_flags_if_tpu()

    import jax

    from kubeflow_tpu.models.config import preset

    if jax.default_backend() != "tpu":
        attn = "xla"               # interpret-mode kernels are CI-only
    cfg = preset(
        "llama3-8b",
        n_layers=8, hidden=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        mlp_dim=8192, vocab_size=32000, max_seq_len=2048,
        remat_policy=remat, fused_kernels=fused,
    )
    t_c0 = time.perf_counter()
    out = measure_train_rate(
        cfg, batch, k_dispatch=kd, warm_disp=warm_disp, disp=disp,
        mu_dtype=None if mu == "none" else mu, attn_impl=attn)
    wall = time.perf_counter() - t_c0
    return {
        "remat": remat, "attn": attn, "batch": batch, "k": kd, "mu": mu,
        "fused": fused,
        **{k: out[k] for k in ("tok_s_chip", "step_ms", "mfu", "loss",
                               "segments", "spread_pct")},
        "wall_s": round(wall, 1),
    }


def main():
    if len(sys.argv) >= 5 and sys.argv[1] == "--one":
        remat, attn, batch = sys.argv[2], sys.argv[3], int(sys.argv[4])
        kd = int(sys.argv[5]) if len(sys.argv) > 5 else 1
        mu = sys.argv[6] if len(sys.argv) > 6 else "none"
        fused = sys.argv[7] if len(sys.argv) > 7 else "auto"
        print(json.dumps(run_one(remat, attn, batch, kd, mu, fused)))
        return

    grid = GRID
    if len(sys.argv) >= 3 and sys.argv[1] == "--fused":
        # Just the fused A/B half of the grid, one side: the quick re-check
        # after touching the kernels.
        grid = [row for row in GRID if row[5] == sys.argv[2]
                and row[0] == "dots_flash"]

    for remat, attn, batch, kd, mu, fused in grid:
        cmd = [sys.executable, __file__, "--one", remat, attn, str(batch),
               str(kd), mu, fused]
        t0 = time.perf_counter()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=900)
        except subprocess.TimeoutExpired:
            print(json.dumps({"remat": remat, "attn": attn, "batch": batch,
                              "k": kd, "fused": fused, "failed": True,
                              "err": "timeout 900s"}),
                  flush=True)
            continue
        wall = round(time.perf_counter() - t0, 1)
        if proc.returncode == 0 and proc.stdout.strip():
            print(proc.stdout.strip().splitlines()[-1], flush=True)
        else:
            err = (proc.stderr or "")[-300:].replace("\n", " | ")
            print(json.dumps({"remat": remat, "attn": attn, "batch": batch,
                              "k": kd, "mu": mu, "fused": fused,
                              "failed": True, "wall_s": wall, "err": err}),
                  flush=True)


if __name__ == "__main__":
    main()
