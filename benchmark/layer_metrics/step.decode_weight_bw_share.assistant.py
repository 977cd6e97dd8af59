"""How close one decode step of the assistant cell comes to the time its
weights alone take to cross the memory bus: what
``step.decode_weight_bw_share.mixedlength`` reads, its reader (a step is
five executions of ``paged_decode_attention`` inside a decode program: one
a layer), with this architecture's ``counts.decode_weight_bytes``: the five
layers, the final norm and the WHOLE head, 6.98 GB in bfloat16, dense, so the
live streams move nothing. The states (2.0 GB a 48-stream step, read and
written) and the K and V rows (0.4 GB at a mean context of 800) are left out:
the share says how far the step stands from its weights alone, and the
states are a quarter of what it moves beside them (PERF.md section 5). None
where the run has no trace; 0.0 when the traced seconds hold no decode
dispatch."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.decode_weight_bw_share.mixedlength").read
