"""BENCHMARK.json as the harness reads it: cells, their declared metrics, the
files that belong to each name, and the check of the last line.

The harness holds no table of cells, configurations or metrics in code. A
cell names a configuration (whose ``file`` BENCHMARK.json gives) and a traffic
mix (``benchmark/traffic/<mix>.json``); a per-layer metric is read by
``benchmark/layer_metrics/<metric name>.py``; a configuration's file names
its architecture, and whatever depends on the model's equations (weights,
plain reference, counts, the mapping onto the program's config) is the four
files of ``benchmark/architectures/<name>/`` (benchmark/architecture.py).
Adding a cell, a metric, a configuration or an architecture is adding files
and entries; no file that is there is edited.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRAFFIC_DIR = os.path.join(HERE, "traffic")
LAYER_METRICS_DIR = os.path.join(HERE, "layer_metrics")

LAST_LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")


class ManifestError(Exception):
    """BENCHMARK.json, or a file it names, does not say what the harness
    needs."""


class MalformedResult(Exception):
    """The line the harness was about to print is not what BENCHMARK.json
    declares for this cell. Raised BEFORE anything is printed: a declared
    metric that is not printed costs the whole PR (PR 23)."""


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path if os.path.isabs(path) else os.path.join(ROOT, path)) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json; it has "
        f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str) -> dict:
    return load_json(config_entry(manifest, name)["file"])


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(TRAFFIC_DIR, name + ".json"))


def declared(manifest: dict, cell_name: str, kind: str) -> dict[str, dict]:
    """The metrics of ``kind`` ("end_to_end" | "per_layer") that
    BENCHMARK.json declares for this cell, by name: those that list it under
    ``workloads``, and those with no such key (declared for every cell)."""
    return {m["name"]: m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]}


def load_module_file(package: str, name: str, path: str):
    """The module in the file ``path``, which a name from BENCHMARK.json or
    a configuration chose (so it need not be an identifier)."""
    spec = importlib.util.spec_from_file_location(
        package + "." + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_layer_metric(name: str):
    """The reader module of one per-layer metric, found by the metric's
    name: ``read(run) -> float | None`` and ``DECLARATION``."""
    path = os.path.join(LAYER_METRICS_DIR, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"per-layer metric {name!r} has no reader {path}")
    return load_module_file("benchmark.layer_metrics", name, path)


def read_layer_metrics(manifest: dict, cell_name: str, run: dict) -> dict:
    """Every per-layer metric declared for the cell, read from ``run``. A
    reader that finds nothing to read returns None and is left out; whether
    that is allowed is the last line's check, not this function's."""
    out = {}
    for name in declared(manifest, cell_name, "per_layer"):
        value = load_layer_metric(name).read(run)
        if value is not None:
            out[name] = float(value)
    return out


def declared_for_run(manifest: dict, cell_name: str, trace: int) -> dict:
    """The metrics a run of this kind prints: ``--trace 0`` the end-to-end
    ones, ``--trace 1`` the per-layer ones, ``--trace 2`` (measure first,
    trace afterwards) both side by side."""
    kinds = {0: ("end_to_end",), 1: ("per_layer",),
             2: ("end_to_end", "per_layer")}[int(trace)]
    return {name: m for kind in kinds
            for name, m in declared(manifest, cell_name, kind).items()}


def build_last_line(manifest: dict, cell_name: str, trace: int, *,
                    correct: bool, attempted: int, failed: int,
                    values: dict[str, float], device: dict,
                    breakdown: dict | None = None,
                    allow_missing: frozenset = frozenset()) -> dict:
    """The one JSON object a run prints last, checked against the manifest:
    ``metrics`` holds exactly the metrics BENCHMARK.json declares for this
    cell and this kind of run, each a finite number with its unit.
    ``allow_missing`` exists for the CPU rehearsal alone, where a metric of
    the device has nothing to read; a chip run passes none."""
    want = declared_for_run(manifest, cell_name, trace)
    metrics = {}
    for name, entry in want.items():
        if name not in values:
            if name in allow_missing:
                continue
            raise MalformedResult(
                f"{cell_name} (--trace {int(trace)}): BENCHMARK.json declares "
                f"{name} and the run has no value for it; it has "
                f"{sorted(values)}")
        v = values[name]
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not math.isfinite(v):
            raise MalformedResult(f"{cell_name}: {name} = {v!r} is not a "
                                  "finite number")
        metrics[name] = {"value": v, "unit": entry["unit"]}
    dev_keys = DEVICE_KEYS + (TRACED_DEVICE_KEYS if trace else ())
    missing = [k for k in dev_keys if k not in device
               and k not in allow_missing]
    if missing:
        raise MalformedResult(f"{cell_name}: device lacks {missing}")
    if trace and "busy_s" in device:
        if not 0.0 < device["busy_s"] <= device["window_s"]:
            raise MalformedResult(
                f"{cell_name}: busy_s {device['busy_s']} is not above 0 and "
                f"at most window_s {device['window_s']}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": {k: device[k] for k in dev_keys if k in device}}
    if trace and breakdown is not None:
        line["breakdown"] = {k: [[str(n), float(s)] for n, s in v[:10]]
                             for k, v in breakdown.items()
                             if k in ("device_ops", "idle_gaps")}
    return line
