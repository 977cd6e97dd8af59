"""One process, one cell, one run.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Builds the cell from its data files (BENCHMARK.json names the configuration's
file; the traffic mix is ``benchmark/traffic/<mix>.json``), warms up the
cell's shapes, measures for ``--seconds``, compares outputs with the plain
reference, and prints ONE JSON object as the last line of its standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` when traced). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (the
profiler runs inside the measured window). ``--trace 2`` is ``--trace 0``
with a tail: once the window has closed and its numbers are taken, a few
seconds of the same traffic are traced, and the line holds both kinds of
metric side by side, ``device.busy_s`` / ``window_s`` of the tail and a
``breakdown`` whose idle gaps are named by the host's phase. That line is
checked against BENCHMARK.json before it is printed; a run that
cannot report it prints no result and exits non-zero. Everything else goes to
standard error and to ``benchmark_out/<cell>/``.

It needs the TPU the cell asks for: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

from benchmark import manifest as mf

OUT_ROOT = os.path.join(mf.ROOT, "benchmark_out")
RUNNERS = {"open_loop": "benchmark.serving", "closed_loop": "benchmark.serving",
           "train_steps": "benchmark.training"}


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def run_cell(manifest: dict, workload: str, *, seed: int, seconds: float,
             trace: int, allow_cpu: bool = False,
             t_start: float | None = None) -> dict:
    """Run one cell and return its checked last line. ``allow_cpu`` is the
    CPU rehearsal's: only a test passes it."""
    import importlib

    from benchmark import device, hostspans, tracing

    trace = int(trace)
    t_start = time.monotonic() if t_start is None else t_start
    cell = mf.cell(manifest, workload)
    conf = mf.load_config(manifest, cell["config"])
    traffic = mf.load_traffic(cell["traffic"])
    cache_dir = device.prepare_process(platform_is_tpu=not allow_cpu)
    dev = device.require_devices(cell["chips"], allow_cpu=allow_cpu)
    log(f"{workload} seed {seed} seconds {seconds} trace {trace} on "
        f"{dev['count']} x {dev['kind']} ({dev['platform']}); compile cache "
        f"{cache_dir}")
    # A run leaves nothing for the next one to find (the trainer keeps a
    # ledger beside its metrics file), and the program's own prints (the
    # trainer's metrics emitter writes to stdout) go to standard error:
    # stdout carries the result line alone.
    out_dir = os.path.join(OUT_ROOT, workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    runner = importlib.import_module(RUNNERS[traffic["kind"]])
    with contextlib.redirect_stdout(sys.stderr):
        res = runner.run(manifest, cell, conf, traffic, seed=seed,
                         seconds=seconds, trace=trace, dev=dev,
                         t_start=t_start, out_dir=out_dir, log=log)

    device_out = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"],
                  "memory_peak_bytes": res["memory_peak_bytes"]}
    values, breakdown = dict(res["values"]), None
    allow_missing: frozenset = frozenset()
    if trace:
        traced = res["traced"]
        layer = mf.read_layer_metrics(manifest, workload, res["record"])
        values = {**values, **layer} if trace == 2 else layer
        if traced is not None and traced["devices"]:
            device_out["busy_s"] = tracing.busy_s(traced)
            device_out["window_s"] = tracing.traced_window_s(traced)
            loop = hostspans.loop_thread(traced.get("host_spans"))
            breakdown = {
                "device_ops": tracing.top_ops(traced),
                # Named by what the HOST was doing where the program's
                # spans are in the trace behind the window; by the
                # program the device waited for otherwise.
                "idle_gaps": hostspans.idle_by_host_phase(traced, loop)
                if trace == 2 and loop else tracing.idle_gaps(traced)}
        if dev["platform"] != "tpu":
            # The CPU rehearsal: a trace of the CPU holds no device plane,
            # and nothing may be printed under a device metric's name.
            allow_missing = frozenset(
                set(mf.declared(manifest, workload, "per_layer"))
                - set(layer)) | {"busy_s", "window_s"}
    for name, value in sorted(values.items()):
        log(f"metric {name} = {value}")
    return mf.build_last_line(
        manifest, workload, trace, correct=res["correct"],
        attempted=res["attempted"], failed=res["failed"], values=values,
        device=device_out, breakdown=breakdown, allow_missing=allow_missing)


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), required=True)
    args = ap.parse_args(argv)
    try:
        line = run_cell(mf.load_manifest(), args.workload, seed=args.seed,
                        seconds=args.seconds, trace=args.trace,
                        t_start=t_start)
    except Exception as exc:           # boundary: report, print no result
        import traceback

        traceback.print_exc()
        log(f"NO RESULT: {type(exc).__name__}: {exc}")
        return 1
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
