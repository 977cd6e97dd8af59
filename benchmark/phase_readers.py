"""Readings of the host's time a token over the WINDOW, from the program's
always-on counters (``run["counters_before"]`` / ``["counters_after"]``,
the rule of benchmark/program_readers.py: the difference of two total
snapshots; None where a snapshot lacks a key, the stated 0.0 where the
divisor stood still).

The engine's scheduler adds every phase's EXCLUSIVE seconds (its own, its
children's taken out: the rule ``hostspans.innermost_segments`` cuts a
traced tail's spans by) to ``sched_<phase>_sum_s`` of
``LLMEngine.counters()``, capture or none, and the loop's time under no
phase to ``sched_other_sum_s``; ``sched_host_busy_sum_s`` is their sum less
``fetch`` (blocked on the device) less ``idle`` (waiting for work). The
tail's ``engine.sched_busy_share.*`` reads the same boundaries from three
traced seconds behind the window; these read the window itself.
"""

from __future__ import annotations

from benchmark.program_readers import delta, mean_ms


def share_of_window(run: dict, part: str, key: str):
    """100 x Δ``key`` (seconds) over the window's seconds."""
    d = delta(run, part, key)
    window = run.get("window_s")
    if d is None or not window:
        return None
    return 100.0 * d[0] / window


def per(run: dict, part: str, keys: tuple, per_key: str, scale: float = 1.0):
    """``scale`` x ΣΔ``keys`` / Δ``per_key``; 0.0 where the divisor did
    not move in the window."""
    d = delta(run, part, per_key, *keys)
    if d is None:
        return None
    return scale * sum(d[1:]) / d[0] if d[0] > 0 else 0.0


def sched_busy_share_window(run: dict):
    """Percent of the window the scheduler's thread spent on work of its
    own: neither blocked fetching from the device nor waiting for work."""
    return share_of_window(run, "engine", "sched_host_busy_sum_s")


def phase_ms_per_round(run: dict, phase: str):
    """Milliseconds of one scheduler phase (``sync_state``, ``emit``, ...)
    a decode round dispatched in the window."""
    return per(run, "engine", (f"sched_{phase}_sum_s",), "decode_rounds", 1e3)


def prefill_dispatch_ms_per_program(run: dict):
    """The host's milliseconds to send one chunk-prefill program."""
    return per(run, "engine", ("sched_prefill_dispatch_sum_s",),
               "prefill_programs_dispatched", 1e3)


def state_syncs_per_round(run: dict):
    """Per-slot scatter dispatches and page-table row uploads a decode
    round: what ``DecodeState`` sent to the device."""
    return per(run, "engine", ("state_slot_syncs", "state_row_syncs"),
               "decode_rounds")


def stream_write_share(run: dict):
    """Percent of the window the server's handler threads spent between a
    token taken off its stream and its chunk flushed, summed over threads:
    it may pass 100."""
    return share_of_window(run, "server", "stream_write_sum_s")


def stream_wake_mean_ms(run: dict):
    """From a round ready on the scheduler's side to a handler holding its
    token, mean over the tokens taken with none waiting behind them."""
    return mean_ms(run, "server", "stream_wake")
