"""Share of the window's chunk-prefill programs that ran the stack's
stateless tail (the seven gated memory units and seven cross layers behind
the full-attention layer, and the head): Δ``prefill_programs_with_end`` over
Δ``prefill_programs_dispatched`` of ``LLMEngine.counters()``. The engine
sends every chunk of this model through the program over rows, which runs
the tail at ONE position a row and only where some row ends its prompt
(``serve/paged.py::_pool_forward``): a prompt of 512-1536 tokens is one to
three chunks, so about half the programs carry an end; every other one runs
18 of the 32 layers. Lower is less work for the same prompts. 0.0 when the
window dispatched no chunk program; None where the program has no such
counters."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "prefill_programs_with_end",
              "prefill_programs_dispatched")
    if d is None:
        return None
    ended, programs = d
    return 100.0 * ended / programs if programs > 0 else 0.0
