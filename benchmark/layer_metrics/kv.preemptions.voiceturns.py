"""Preemptions inside the window of the voice-turns cell
(``kv.preemptions.mixedlength``'s reader); 0.0 when none happened. The pool
holds 48 whole contexts of 2176 tokens (17 pages each: 816), so none is
expected."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "count", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.preemptions.mixedlength").read
