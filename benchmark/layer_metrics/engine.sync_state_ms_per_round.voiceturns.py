"""The scheduler's milliseconds in ``engine.sync_state`` a decode round in
the voice-turns cell (``phase_readers.phase_ms_per_round``): 48 slots whose
turns end and begin all through the window, one upload and one program a
sync since PR 54. 0.0 for a window that dispatched no round; None where the
program has no such counter."""

from benchmark.phase_readers import phase_ms_per_round

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}


def read(run: dict):
    return phase_ms_per_round(run, "sync_state")
