"""Utilisation of the chunk-prefill programs in the agent-context cell, the
cell's share of the whole step's peak: what ``step.prefill_mfu.mixedlength``
reads (its reader, its way of counting the chunks a traced program carries),
with the operations of THIS architecture's ``counts.prefill_flops``: 2 per
multiplied parameter of the five layers for every token (the experts at the
expected rows held: half a held expert a token beside the shared one), the
indexer over the pairs a query can SEE, attention over the pairs the indexer
SELECTED (expanded form), the head ONCE a prompt. Attention over the keys
nobody selected, the selection itself, a last chunk's padding and the decode
rows a chunk program carries are work the program chose and are not counted.
None where the program has no such counters; 0.0 when the traced seconds
hold no chunk prefill."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.prefill_mfu.mixedlength").read
