"""Recompute preemptions inside the window of the reasoning cell: the
difference of the engine's ``preemptions`` counter (``kv.preemptions.
mixedlength``'s reader); 0.0 when none happened. The pool holds 32 whole
contexts of 8320 tokens in the full-attention layer, a ring for every slot
in the window layers and a state entry for every slot in the Mamba layers,
so none is expected."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "count", "better": "lower",
               "source": "program_counter", "layer": "KV manager",
               "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.preemptions.mixedlength").read
