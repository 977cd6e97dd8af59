"""Utilisation of the chunk-prefill programs in the agent-turns cell, the
cell's share of the whole step's peak: what ``step.prefill_mfu.mixedlength``
reads (its reader, its way of counting the chunks a traced program carries),
with the operations of THIS architecture's ``counts.prefill_flops``: 2 per
multiplied parameter of the eleven published layers held for every token (the
Mamba projections, the attention, the routers, the latent projections once a
token, the shared experts, the routed experts at the expected 5.5 held, each
1024 wide), causal attention in the one attention layer, the SSD recurrence at
5 a head, state and value, the head ONCE a prompt. A last chunk's padding, the
rows the sorted path gathers and never multiplies, the chunked form's
products and the decode rows a chunk program carries are work the program
chose and are not counted. None where the program has no such counters; 0.0
when the traced seconds hold no chunk prefill."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.prefill_mfu.mixedlength").read
