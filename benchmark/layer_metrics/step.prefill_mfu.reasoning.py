"""Utilisation of the chunk-prefill programs in the reasoning cell: what
``step.prefill_mfu.mixedlength`` reads (its reader, its way of counting the
chunks a traced program carries), with the operations of THIS architecture's
``counts.prefill_flops``: 2 per multiplied parameter of the self-decoder (the
Mamba layers, the window layers and the full one, their MLPs) for every
token, the scans' multiply-adds, the window layers' scores at the window's
length and the full layer's causal ones; the cross-decoder and the head at
ONE position a prompt (the tail the program skips is work the model does not
need). A last chunk's padding, the padded queries' second half and the tail
where the one-row program runs it at all positions are the program's choice
and are not counted. None where the program has no such counters; 0.0 when
the traced seconds hold no chunk prefill."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.prefill_mfu.mixedlength").read
