"""Milliseconds of the scheduler's ``engine.sync_state`` phase a decode round
dispatched in the agent-context cell's window: 1e3 x
Δ``sched_sync_state_sum_s`` / Δ``decode_rounds`` of ``LLMEngine.counters()``
(benchmark/phase_readers.py::phase_ms_per_round; EXCLUSIVE seconds, summed
always, capture or none): the host's time to send the device what the
scheduler changed since the last round, one upload and one program a sync
since PR 54. 0.0 for a window that dispatched no round; None where the
program has no such counter."""

from benchmark.phase_readers import phase_ms_per_round

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}


def read(run: dict):
    return phase_ms_per_round(run, "sync_state")
