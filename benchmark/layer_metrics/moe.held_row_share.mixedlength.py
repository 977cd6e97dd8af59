"""Share of the rows the expert layers routed that they COMPUTED in the
mixed-length cell: Δ``expert_rows_held`` / Δ``expert_rows_routed`` of
``LLMEngine.counters()`` over the window, every expert layer of every
program (a row is one of a token's eight choices; it is held when its expert
is one of the 16 of 128 this chip keeps). What one chip of the group of
eight computes of a layer's routed work: 12.5% in expectation on seeded
weights, and the grouped matmuls' time should follow it, not the rows
routed. None where the program has no such counters (a program from before
the share); 0.0 for a window that routed no row."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "expert_rows_held", "expert_rows_routed")
    if d is None:
        return None
    held, routed = d
    return 100.0 * held / routed if routed > 0 else 0.0
