"""``--trace 2`` (measure first, trace afterwards) end to end on the CPU, in
the three test-only cells of test_benchmark_rehearsal_cpu.py: one line with
both kinds of metric, a measured window that is the ``--trace 0`` window (the
same plan, byte for byte; marks that stop where the window closes), its
numbers taken before the tail starts, and the tail's trace deleted once it
is reduced. The profiler writes host annotations on the CPU too, so the
``program_span`` metrics are printed here; only ``device_trace`` ones may be
missing."""

import json
import os
import time

import pytest

from benchmark import manifest as mf
from benchmark import run as bench_run
from benchmark import serving, tracing
from benchmark.traffic import build_plan
from test_benchmark_rehearsal_cpu import (
    CELLS, DEVICE_METRICS, rehearsal_manifest,
)

SEED = 2**31 + 23


@pytest.fixture
def watched(monkeypatch, tmp_path):
    """Log lines as the harness writes them, and the instant (and the lines
    written by then) at which the tail's first act, the profiler's warm
    start, happened. The runs write under a directory of their own:
    test_benchmark_rehearsal_cpu.py runs the same cells, in another worker
    at the same time."""
    seen = {"lines": [], "warm_at": None, "lines_at_warm": None,
            "starts": 0}
    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "log",
                        lambda msg: seen["lines"].append(msg))
    warm, start = tracing.warm, tracing.start

    def warm_spy(trace_dir):
        seen["warm_at"] = time.monotonic()
        seen["lines_at_warm"] = list(seen["lines"])
        warm(trace_dir)

    def start_spy(trace_dir):
        seen["starts"] += 1
        start(trace_dir)

    monkeypatch.setattr(tracing, "warm", warm_spy)
    monkeypatch.setattr(tracing, "start", start_spy)
    return seen


def check_trace2_line(line: dict, manifest: dict, cell: str) -> dict:
    line = json.loads(json.dumps(line))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}          # no device plane, no breakdown
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    e2e = mf.declared(manifest, cell, "end_to_end")
    layer = mf.declared(manifest, cell, "per_layer")
    assert set(line["metrics"]) == set(e2e) | (set(layer) - DEVICE_METRICS)
    # The open-loop cell declares no span metric (PERF.md, Open questions).
    spans = [n for n, m in layer.items() if m["source"] == "program_span"]
    assert bool(spans) == (cell != "tiny.rehearsal-open")
    assert all(0.0 < line["metrics"][n]["value"] <= 100.0 for n in spans)
    for name, m in line["metrics"].items():
        assert m["unit"] == {**e2e, **layer}[name]["unit"]
        assert isinstance(m["value"], float)
    assert all(line["metrics"][n]["value"] > 0 for n in e2e)
    return line["metrics"]


@pytest.mark.parametrize("cell,seconds", [
    ("tiny.rehearsal-open", 2.0), ("tiny-moe.rehearsal-closed", 2.0)])
def test_serving_cell_measures_first_and_traces_afterwards(
        cell, seconds, watched):
    manifest = rehearsal_manifest()
    _, config, mix, _ = CELLS[cell]
    line = bench_run.run_cell(manifest, cell, seed=SEED, seconds=seconds,
                              trace=2, allow_cpu=True)
    metrics = check_trace2_line(line, manifest, cell)
    out = os.path.join(bench_run.OUT_ROOT, cell)
    traffic = mf.load_traffic(mix)
    vocab = mf.load_config(manifest, config)["vocab_size"]
    # The measured plan is the one --trace 0 builds: same call, same bytes.
    with open(os.path.join(out, "plan.json")) as f:
        assert f.read() == json.dumps(build_plan(
            traffic, seed=SEED, seconds=seconds, vocab=vocab, model=config))
    # The tail is another plan: other seed, indices far from the window's.
    with open(os.path.join(out, "tail_plan.json")) as f:
        tail = json.load(f)
    assert tail["seed"] != SEED and tail["warmup"] == []
    assert tail["seconds"] == pytest.approx(
        traffic["trace_start_s"] + traffic["trace_seconds"] + 1.0)
    assert min(r["i"] for r in tail["requests"]) >= serving.TAIL_INDEX_BASE
    assert tail["index_base"] == serving.TAIL_INDEX_BASE
    # The window's numbers were out before the tail's first act.
    key = "itl ms p50" if traffic["kind"] == "open_loop" \
        else "serve_tokens_per_s"
    assert any(key in ln for ln in watched["lines_at_warm"])
    assert not any(ln.startswith("tail") for ln in watched["lines_at_warm"])
    assert any(ln.startswith("tail: profiler warm start")
               for ln in watched["lines"])
    assert watched["starts"] == 2           # the warm start and the trace
    # counter metrics are the window's: the tail's requests are not in them
    with open(os.path.join(out, "loadgen.json")) as f:
        window_requests = len(json.load(f)["results"])
    with open(os.path.join(out, "tail_loadgen.json")) as f:
        assert json.load(f)["results"]
    assert line["attempted"] <= window_requests
    assert not os.path.exists(os.path.join(out, "trace"))      # reduced
    assert not os.path.exists(os.path.join(out, "trace_warm"))
    assert metrics["setup_s"]["value"] > 0


def test_training_cell_keeps_on_step_alive_behind_the_closed_window(watched):
    cell, seconds = "tiny-fsdp4.rehearsal-train", 1.0
    manifest = rehearsal_manifest()
    traffic = mf.load_traffic(CELLS[cell][2])
    line = bench_run.run_cell(manifest, cell, seed=SEED, seconds=seconds,
                              trace=2, allow_cpu=True)
    check_trace2_line(line, manifest, cell)
    out = os.path.join(bench_run.OUT_ROOT, cell)
    with open(os.path.join(out, "train.json")) as f:
        marks = json.load(f)["marks"]
    # marks stop at the window's close, which is before the tail's first act
    assert marks[-1][1] <= watched["warm_at"]
    assert marks[-1][1] - marks[0][1] >= seconds
    assert marks[-2][1] - marks[0][1] < seconds
    assert line["attempted"] == marks[-1][0] - marks[0][0]
    # ... and the trainer ran trace_steps more steps, traced
    with open(os.path.join(out, "metrics.jsonl")) as f:
        last_step = [json.loads(ln)["step"] for ln in f][-1]
    assert last_step == marks[-1][0] + traffic["trace_steps"]
    assert watched["starts"] == 2
    assert not os.path.exists(os.path.join(out, "trace"))
