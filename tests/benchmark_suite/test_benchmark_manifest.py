"""BENCHMARK.json against the files it names and against its own rules: for
every cell and both ``--trace`` values the names the harness will print are
exactly the names the manifest declares for that cell."""

import json
import os
import re

import pytest

from benchmark import manifest as mf

MANIFEST = mf.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _layer_metric_files():
    return sorted(f[:-3] for f in os.listdir(mf.LAYER_METRICS_DIR)
                  if f.endswith(".py") and not f.startswith("_"))


def test_top_level_keys_are_the_contracts():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer",
                             "trace_in_run"}
    assert MANIFEST["command"] == ["python3", "-m", "benchmark.run"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_printed_names_are_the_declared_names(cell, trace):
    """What the harness prints for a cell: with --trace 0 the end-to-end
    metrics declared for it, with --trace 1 the per-layer metrics whose
    reader file exists and whose manifest entry lists the cell."""
    kind = "per_layer" if trace else "end_to_end"
    want = mf.declared(MANIFEST, cell, kind)
    assert want, f"{cell} reports no {kind} metric"
    values = {n: 1.0 for n in want}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0}
    line = mf.build_last_line(MANIFEST, cell, bool(trace), correct=True,
                              attempted=1, failed=0, values=values,
                              device=device)
    assert set(line["metrics"]) == set(want)
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    if trace:
        for name in want:
            assert name in _layer_metric_files(), f"{name} has no reader"
    else:
        assert "setup_s" in want and len(want) >= 2


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_reader_declares_what_the_manifest_declares(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    mod = mf.load_layer_metric(name)
    assert mod.DECLARATION == {k: entry[k] for k in
                               ("unit", "better", "source", "layer", "moves")}
    assert callable(mod.read)
    # The suffix names the cell's traffic: a metric is declared only where
    # its source exists.
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert entry["moves"] in e2e
    for cell in entry["workloads"]:
        assert entry["moves"] in mf.declared(MANIFEST, cell, "end_to_end"), (
            f"{name} moves {entry['moves']}, which {cell} does not report")


def test_every_reader_file_is_declared():
    assert set(_layer_metric_files()) == {m["name"]
                                          for m in MANIFEST["per_layer"]}


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_names_units_and_entry_keys(kind):
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if kind == "end_to_end" else {"layer", "moves"})
    names = [m["name"] for m in MANIFEST[kind]]
    assert len(names) == len(set(names))
    for m in MANIFEST[kind]:
        assert set(m) <= allowed, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
        for w in m.get("workloads", []):
            assert w in CELLS


def test_cells_configs_and_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert len(configs) == len(MANIFEST["configs"])
    used = set()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in configs
        used.add(w["config"])
        traffic = mf.load_traffic(w["traffic"])
        assert traffic["kind"] in ("open_loop", "closed_loop", "train_steps")
        conf = mf.load_config(MANIFEST, w["config"])
        assert conf["chips"] == w["chips"]
    assert used == set(configs), "a configuration no cell uses"
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 4)
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        conf = mf.load_json(c["file"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|_size)$|^head_dim$", key), key
            assert conf["reduced"][key]["to"] == conf[key]


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for cell in CELLS:
        e2e = mf.declared(MANIFEST, cell, "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert mf.declared(MANIFEST, cell, "per_layer"), cell
