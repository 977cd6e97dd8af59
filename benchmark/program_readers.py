"""Readings of the program's own counters that more than one per-layer
metric file declares, and the one rule they share: a counter metric is the
difference of two total snapshots (``run["counters_before"]`` /
``run["counters_after"]``, each ``{part: LLMEngine.counters() |
ModelServer.counters() | Trainer.counters()}``) over the measured window.
(A span metric reads the traced stretch's host spans,
``run["host_spans"]``, with benchmark/hostspans.py.) Where the run has no
such snapshot (another kind of run, or a program without one) a reader
returns None; where it has one and nothing was sampled, its stated
number."""

from __future__ import annotations


def delta(run: dict, part: str, *keys: str):
    """The differences of ``keys`` of one part's snapshots over the
    window, or None where a snapshot or a key is missing."""
    a = (run.get("counters_before") or {}).get(part)
    b = (run.get("counters_after") or {}).get(part)
    if a is None or b is None or not all(k in a and k in b for k in keys):
        return None
    return [b[k] - a[k] for k in keys]


def mean_ms(run: dict, part: str, stem: str):
    """Δ``<stem>_sum_s`` / Δ``<stem>_n`` in milliseconds; 0.0 when nothing
    was counted in the window."""
    d = delta(run, part, stem + "_sum_s", stem + "_n")
    if d is None:
        return None
    total, n = d
    return total / n * 1e3 if n > 0 else 0.0


def decode_occupancy(run: dict):
    """Tokens the consumed decode rounds handed to requests over the token
    places the dispatched rounds had (steps x slots), in percent: how full
    a decode step runs. A round costs the same for one live slot as for
    all of them, so this is the share of that cost that bought a token.
    0.0 when no step was dispatched in the window."""
    d = delta(run, "engine", "decode_tokens_emitted",
              "decode_steps_dispatched")
    slots = ((run.get("counters_after") or {}).get("engine") or {}) \
        .get("slots")
    if d is None or not slots:
        return None
    tokens, steps = d
    return 100.0 * tokens / (steps * slots) if steps > 0 else 0.0
