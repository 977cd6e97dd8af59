"""Utilisation of the chunk-prefill programs in the long-document cell: what
``step.prefill_mfu.mixedlength`` reads (its reader, its way of counting the
chunks a traced program carries), with the operations of THIS architecture's
``counts.prefill_flops``: 2 per multiplied parameter of the KDA and GQA
mixers, the router, the experts at the EXPECTED rows held and the shared one;
the KDA recurrence a token (7 dk dv a head); the GQA layer's causal scores;
the head once a prompt. A last chunk's padding, and whatever the chunked form
computes beyond the recurrence (the blocks' triangular solves), are work the
program chose and are not counted. None where the program has no such
counters; 0.0 when the traced seconds hold no chunk prefill."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.prefill_mfu.mixedlength").read
