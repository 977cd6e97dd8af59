"""Recompute preemptions inside the window of the assistant cell: the
difference of the engine's ``preemptions`` counter
(``kv.preemptions.mixedlength``'s reader); 0.0 when none happened. The pool
holds 48 whole contexts of 1536 tokens (12 pages: the named sizes' longest;
the committed sizes' is 1152, 9 pages) and 48 pages to spare, and a state entry for every slot, so none is expected."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "count", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.preemptions.mixedlength").read
