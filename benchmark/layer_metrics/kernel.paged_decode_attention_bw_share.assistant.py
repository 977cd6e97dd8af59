"""The decode attention kernel's share of its roofline, the memory bus, in the
assistant cell: what ``kernel.paged_decode_attention_bw_share.mixedlength``
reads, its reader, with this architecture's
``counts.decode_attention_bytes`` (2048 B a context token a layer at 4 KV
heads of 128 in bfloat16): the K and V rows ONE call (one layer's attention
branch of one decode step) has to read for the contexts its live streams hold
(Σ``context`` / Σ``k_steps`` of the tail's ``engine.decode_dispatch``
spans), over the mean device time of a ``paged_decode_attention`` call,
five a step. 48 streams of 385-1152 tokens: a call reads tens of megabytes,
so the walk's fixed cost a row weighs more here than in the long-context
cells. None where the run has no trace or no spans; 0.0 when the traced
seconds hold no round or no call."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.paged_decode_attention_bw_share.mixedlength").read
