"""Readings that more than one per-layer metric file declares (the same
quantity in two cells moves two different end-to-end metrics, so it has two
names and two files, and one definition here)."""

from __future__ import annotations


def host_gap_share(run: dict):
    """Share of the window the device spent waiting on the host between
    decode rounds, as the engine itself counts it
    (``EngineMetrics.host_gap_histogram``; a gap is 0 when the next round was
    queued before the last one landed): the difference of the running sum
    over the window, in percent; 0.0 when no gap was sampled."""
    a, b = run.get("engine_before"), run.get("engine_after")
    if a is None or b is None:
        return None
    return 100.0 * (b["host_gap_sum_s"] - a["host_gap_sum_s"]) \
        / run["window_s"]
