"""Utilisation of the chunk attention kernel in the long-document cell, the
ONE GQA layer's calls: what ``kernel.paged_chunk_attention_mfu.mixedlength``
reads, its reader, with this architecture's ``counts.chunk_attention_flops``
(causal pairs, 4 x 128 operations a pair a head, 64 heads, one layer). None
where the run has no trace or the program no such counters; 0.0 when the
traced seconds hold no chunk program or no call of the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.paged_chunk_attention_mfu.mixedlength").read
