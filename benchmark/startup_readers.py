"""Readings of what a run's ``setup_s`` went to, from the program's own
start-up clock (``kubeflow_tpu/obs/profiler.py``: ``engine.start.*`` /
``train.start.*`` as ``start_<phase>_sum_s`` of ``LLMEngine.counters()`` /
``Trainer.counters()``, constants once the engine is built or the trainer's
first step has synced) and from the process's compile totals beside them
(``compile_*``: ``runtime/bootstrap.py::watch_compiles``).

Each reads ONE snapshot, not a difference: ``run["counters_before"]``, taken
as the window opens, holds the whole start-up; and ``run["values"]
["setup_s"]``, the harness's own clock from process start to that instant.
None where the run has no snapshot, or the snapshot lacks a reader's keys
(a program without the clock), with ONE exception that is true of such a
program too: ``unattributed_s`` takes whatever start sums the snapshot
HAS, so without any it is ``setup_s``, all of which is then unattributed.
"""

from __future__ import annotations

START = "start_"
SUM = "_sum_s"
# XLA's compile or the cache's retrieval (on a hit JAX fires the
# backend-compile event with the retrieval inside it, so
# ``compile_retrieval_sum_s`` is a PART of the first and is not added), and
# tracing and lowering.
COMPILE_KEYS = ("compile_backend_sum_s", "compile_trace_lower_sum_s")


def snapshot(run: dict) -> dict | None:
    """The parts of the snapshot taken as the window opened, merged (a
    serving run's engine and server, a training run's trainer: their keys
    do not meet), or None where the run has none."""
    parts = run.get("counters_before")
    if not parts:
        return None
    return {k: v for part in parts.values() for k, v in part.items()}


def start_sums(snap: dict) -> dict:
    """The ``start_<phase>_sum_s`` keys a snapshot (or one part of it)
    holds."""
    return {k: v for k, v in snap.items()
            if k.startswith(START) and k.endswith(SUM)}


def _sum_of(run: dict, keys: tuple):
    """The sum of ``keys`` in the run's snapshot, or None where it has no
    snapshot or the snapshot lacks one of them."""
    snap = snapshot(run)
    if snap is None or not all(k in snap for k in keys):
        return None
    return float(sum(snap[k] for k in keys))


def program_start_s(run: dict):
    """Seconds of the program's own start: the engine's four start phases
    and its constructor's time under none, or the trainer's three."""
    sums = start_sums(snapshot(run) or {})
    return float(sum(sums.values())) if sums else None


def unattributed_s(run: dict):
    """``setup_s`` less what the program's start phases account for: the
    harness's own share (imports and backend start, the seeded weights, the
    reference check, the generator's warm-up) plus what the program does
    outside its constructors. What ``host:untraced`` is to a tail."""
    snap = snapshot(run)
    setup = (run.get("values") or {}).get("setup_s")
    if snap is None or setup is None:
        return None
    return float(setup) - float(sum(start_sums(snap).values()))


def compile_s(run: dict):
    """Seconds this process spent, up to the window, in XLA's compiles or
    the cache's retrievals and in tracing and lowering."""
    return _sum_of(run, COMPILE_KEYS)


def cache_misses(run: dict):
    """Programs compiled and written to the persistent cache up to the
    window: 0 for a start that found every program there."""
    return _sum_of(run, ("compile_cache_misses",))


def warm_s(run: dict):
    """Seconds the engine's constructor spent compiling or loading, and
    running once, the programs of its own set."""
    return _sum_of(run, ("start_warm_sum_s",))
