"""Share of the window's chunk-prefill programs that carried the live slots'
decode step in the long-document cell: Δ``mixed_programs_dispatched`` over
Δ``prefill_programs_dispatched`` of ``LLMEngine.counters()``. This engine's
chunk program is two rows wide (26 rows an expert at 512 tokens) and cannot
send a row ahead (a KDA layer hands the END state of a chunk to the chunk
behind), so the step rides only a program BOTH of whose rows hold a chunk
(``serve/chunk_programs.py::ChunkPlan.rides``): the share is how often two
prefills were due together beside a live slot, and every point of it is a
decode step whose weights were not read a second time. A lone chunk keeps its
one-row program and the iteration its own step. 0.0 on a program whose chunk
program carries no step (the counter stands still) and for a window that
dispatched no chunk program; None where the program has no such counters."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "mixed_programs_dispatched",
              "prefill_programs_dispatched")
    if d is None:
        return None
    riding, programs = d
    return 100.0 * riding / programs if programs > 0 else 0.0
