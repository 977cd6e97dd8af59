"""The decode kernel's share of its roofline (the memory bus) in the ONE GQA
layer's call of the long-document cell: what
``kernel.paged_decode_attention_bw_share.mixedlength`` reads, its reader
(K and V rows of the contexts the live streams attend to, 4096 B a token,
from the tail's ``engine.decode_dispatch`` spans, over the mean device time
of a ``paged_decode_attention`` call). Contexts here are 4k-17k tokens: 1.3
GB a 32-stream step, the longest reads of any cell. None where the run has no
trace or no spans; 0.0 when the traced seconds hold no round or no call."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric(
    "kernel.paged_decode_attention_bw_share.mixedlength").read
