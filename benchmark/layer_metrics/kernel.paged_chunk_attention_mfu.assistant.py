"""Utilisation of the chunk attention kernel in the assistant cell, the five
layers' attention branches: what
``kernel.paged_chunk_attention_mfu.mixedlength`` reads, its reader, with
this architecture's ``counts.chunk_attention_flops`` (causal pairs, 4 x 128
operations a pair a head, 20 heads, five layers). Prompts of 384-768 tokens
are one or two chunks: the triangle of a chunk's own keys is most of the
work, and the kernel computes whole 128 x 512 tiles over it. None where the
run has no trace or the program no such counters; 0.0 when the traced
seconds hold no chunk program or no call of the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.paged_chunk_attention_mfu.mixedlength").read
