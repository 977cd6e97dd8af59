"""Recompute preemptions inside the window of the agent-context cell: the
difference of the engine's ``preemptions`` counter
(``kv.preemptions.mixedlength``'s reader); 0.0 when none happened. The pool
holds 16 whole contexts of 12544 tokens (98 pages each: 1568), so none is
expected."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "count", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.preemptions.mixedlength").read
