"""How close one decode step of the long-document cell comes to the time its
weights alone take to cross the memory bus: what
``step.decode_weight_bw_share.mixedlength`` reads (its reader: a step is an
execution of the GQA layer's decode kernel, one a step at one
``full_attention`` layer held), with THIS architecture's
``counts.decode_weight_bytes``: the four mixers, routers, shared experts and
the head whole, and of the 40 held experts a layer those that SOME live
stream chose (55.5% at 32 streams, 8 of 320). The states (0.8 GB a 32-stream
step) and K/V are left out, so the share is low by construction here: the
step moves about as many bytes of state and cache as of weights (PERF.md
section 5). 0.0 when the traced seconds hold no decode dispatch."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.decode_weight_bw_share.mixedlength").read
