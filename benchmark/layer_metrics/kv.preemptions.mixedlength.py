"""Recompute preemptions inside the window of the mixed-length cell (a slot
or a chunked prefill gave its pages back and its request went round again):
the difference of the engine's ``preemptions`` counter
(``LLMEngine.counters()``); 0.0 when none happened. The pool holds 32 whole
contexts of 9216 tokens in the global layer and a ring for every slot in the
window layers, so none is expected."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "count", "better": "lower",
               "source": "program_counter", "layer": "KV manager",
               "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "preemptions")
    return None if d is None else float(d[0])
