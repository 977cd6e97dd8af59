"""The harness end to end on the CPU at tiny widths: the same ``run_cell``
the command calls, on test-only configuration and traffic files that
BENCHMARK.json does not list (which is also the proof that a cell is added by
adding files and entries: a traffic mix, a configuration, a reader and, for
a model whose equations the harness has not seen, an architecture directory
``benchmark/architectures/<name>/``; test_benchmark_added_files.py shows
that no file that was there is edited). The fourth cell is of another
architecture than the three real ones (``rehearsal-gemma``: tied head,
GeGLU, (1 + w) norms, embedding scale, logit soft-cap), judged against its
own plain reference. The device refusal is bypassed by a function argument,
which no flag or variable reaches. Nothing of the CPU may appear under a
device metric's name.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from benchmark.run import run_cell

REAL = mf.load_manifest()
# rehearsal cell <- the real cell whose metrics it borrows
CELLS = {
    "tiny.rehearsal-open": ("mistral-7b.chat-open", "rehearsal-tiny",
                            "rehearsal-open", 1),
    "tiny-moe.rehearsal-closed": ("mixtral-8x7b.batch-longprompt",
                                  "rehearsal-tiny-moe", "rehearsal-closed", 1),
    "tiny-fsdp4.rehearsal-train": ("mistral-7b-fsdp4.pretrain-4k",
                                   "rehearsal-tiny-fsdp4", "rehearsal-train",
                                   4),
    "tiny-gemma.rehearsal-open": ("mistral-7b.chat-open",
                                  "rehearsal-tiny-gemma", "rehearsal-open", 1),
}
DEVICE_METRICS = {m["name"] for m in REAL["per_layer"]
                  if m["source"] == "device_trace"} | {"trainer.mfu.train"}


def rehearsal_manifest() -> dict:
    """BENCHMARK.json with its cells swapped for the test-only ones: new
    entries, new files, the harness's code untouched."""
    m = copy.deepcopy(REAL)
    swap: dict = {}
    for name, (real, _, _, _) in CELLS.items():
        swap.setdefault(real, []).append(name)
    m["configs"] = [{"name": c, "source": "test-only", "reduced": [],
                     "file": f"benchmark/configs/{c}.json", "why": "test"}
                    for _, c, _, _ in CELLS.values()]
    m["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k,
                       "why": "test"} for n, (_, c, t, k) in CELLS.items()]
    for kind in ("end_to_end", "per_layer"):
        for entry in m[kind]:
            if "workloads" in entry:
                entry["workloads"] = [n for w in entry["workloads"]
                                      for n in swap[w]]
    return m


def check_line(line: dict, manifest: dict, cell: str, trace: bool) -> None:
    line = json.loads(json.dumps(line))            # it must be plain JSON
    assert set(line) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "breakdown" not in line
    want = mf.declared(manifest, cell, "per_layer" if trace else "end_to_end")
    if trace:
        assert not set(line["metrics"]) & DEVICE_METRICS, (
            "a CPU number under a device metric's name")
        assert set(line["metrics"]) == set(want) - DEVICE_METRICS
    else:
        assert set(line["metrics"]) == set(want)
    for name, m in line["metrics"].items():
        assert m["unit"] == want[name]["unit"]
        assert isinstance(m["value"], float)


@pytest.mark.parametrize("cell,trace,seconds", [
    ("tiny.rehearsal-open", True, 2.0),
    ("tiny-moe.rehearsal-closed", False, 2.0),
    ("tiny-fsdp4.rehearsal-train", False, 1.0),
    ("tiny-fsdp4.rehearsal-train", True, 1.0),
    ("tiny-gemma.rehearsal-open", False, 2.0),
])
def test_cell_runs_end_to_end_on_the_cpu(cell, trace, seconds):
    manifest = rehearsal_manifest()
    line = run_cell(manifest, cell, seed=2**31 + 17, seconds=seconds,
                    trace=trace, allow_cpu=True)
    check_line(line, manifest, cell, trace)
    if not trace:
        e2e = line["metrics"]
        assert e2e["setup_s"]["value"] > 0
        assert all(m["value"] > 0 for m in e2e.values())


def test_the_command_refuses_to_run_off_the_chip():
    """The real command, a real cell, no TPU: another exit code than 0 and
    no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         REAL["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=mf.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "NO RESULT" in p.stderr and "not a TPU" in p.stderr


def test_a_broken_timed_path_comes_out_not_correct(monkeypatch):
    """The whole run but the look for a chip, with the decode step broken
    underneath (every logit row shifted by one id where it is produced):
    requests are still answered, and ``correct`` is false."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.serve import paged

    sound = paged._paged_decode_step

    def broken(*args, **kw):
        logits, cache = sound(*args, **kw)
        return jnp.roll(logits, 1, axis=-1), cache

    monkeypatch.setattr(paged, "_paged_decode_step", broken)
    manifest = rehearsal_manifest()
    # The engine's dispatch is jitted once a process: no trace from before
    # may serve this run, and none of this run's may serve a later test.
    jax.clear_caches()
    try:
        line = run_cell(manifest, "tiny.rehearsal-open", seed=2**31 + 31,
                        seconds=1.0, trace=0, allow_cpu=True)
    finally:
        jax.clear_caches()
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
