"""Utilisation of the chunk attention kernel in the reasoning cell, the eight
window layers' and the one full layer's calls: what
``kernel.paged_chunk_attention_mfu.mixedlength`` reads, its reader, with this
architecture's ``counts.chunk_attention_flops`` (a window layer's pairs at
the window's length, the full layer's causal; 4 x 64 operations a pair a
head, 40 heads). The program hands the kernel queries padded to twice the
width (``[q1 | 0]``, ``[0 | q2]``): the score product it runs is twice the
one counted, so half of what the kernel does reads as idle here by
construction (PERF.md section 7: a kernel variant waits for a trace that
asks for it). None where the run has no trace or the program no such
counters; 0.0 when the traced seconds hold no chunk program or no call of
the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.paged_chunk_attention_mfu.mixedlength").read
