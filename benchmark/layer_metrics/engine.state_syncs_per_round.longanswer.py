"""What ``DecodeState`` sent to the device a decode round dispatched in the
longanswer cell's window: (Δ``state_slot_syncs`` + Δ``state_row_syncs``) /
Δ``decode_rounds`` of ``LLMEngine.counters()``
(benchmark/phase_readers.py::state_syncs_per_round): one scatter dispatch a
dirty slot, one upload a dirty page-table row. What
``engine.sync_state_ms_per_round.longanswer`` pays for. 0.0 for a window that
dispatched no round; None where the program has no such counter."""

from benchmark.phase_readers import state_syncs_per_round as read  # noqa: F401

DECLARATION = {"unit": "count", "better": "lower",
               "source": "program_counter", "layer": "engine scheduler",
               "moves": "serve_tokens_per_s"}
