"""Utilisation of the chunk-prefill programs in the assistant cell: what
``step.prefill_mfu.mixedlength`` reads (its reader, its way of counting the
chunks a traced program carries), with the operations of THIS architecture's
``counts.prefill_flops``: 2 per multiplied parameter of the five layers
(both mixers' projections and the MLP) for every token, causal attention, the
SSD recurrence at 5 a head, state and value, and the head at ONE position a
prompt. The head over every row of a chunk (a vocabulary of 261120: 1.4 of a
one-row program's 3.6 TFLOP), a last chunk's padding and the chunked form's
products are work the program chose and are not counted. None where the
program has no such counters; 0.0 when the traced seconds hold no chunk
prefill."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.prefill_mfu.mixedlength").read
