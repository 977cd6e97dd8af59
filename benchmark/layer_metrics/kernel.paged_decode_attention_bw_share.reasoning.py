"""The decode kernel's share of its roofline (the memory bus) in the calls
over the ONE full-attention layer's pages of the reasoning cell: what
``kernel.paged_decode_attention_bw_share.mixedlength`` reads, its reader (K
and V rows of the contexts the live streams attend to, 5120 B a token, from
the tail's ``engine.decode_dispatch`` spans, over the mean device time of a
``paged_decode_attention`` call). A step makes EIGHT such calls over the
same rows (the full layer and the seven cross layers, which keep none of
their own): 32 streams x 2k-3.5k tokens x 5120 B = 0.33-0.57 GB a call, 2.7-
4.6 GB a step. None where the run has no trace or no spans; 0.0 when the
traced seconds hold no round or no call."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric(
    "kernel.paged_decode_attention_bw_share.mixedlength").read
