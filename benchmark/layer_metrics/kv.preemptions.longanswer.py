"""Recompute preemptions inside the window of the long-answer cell (a slot
or a chunked prefill gave its pages back and its request went round again):
the difference of the engine's ``preemptions`` counter
(``LLMEngine.counters()``); 0.0 when none happened. The pool holds 64 whole
contexts of 3200 tokens, so none is expected."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "count", "better": "lower",
               "source": "program_counter", "layer": "KV manager",
               "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "preemptions")
    return None if d is None else float(d[0])
