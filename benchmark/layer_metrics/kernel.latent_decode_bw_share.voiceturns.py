"""The latent decode kernel's share of its roofline, the memory bus, in the
voice-turns cell: ``kernel.latent_decode_bw_share.longctx``'s reader (the
rows of the live streams' contexts, ``context`` of the
``engine.decode_dispatch`` spans over their ``k_steps``, at this
architecture's ``counts.latent_decode_bytes``: 1280 bytes a row as held; no
selection: every row of a context is read), over the chip's bandwidth, over
the mean device time of a call; a step makes eight calls, two a published
layer, at 64 heads and up to 48 streams of 0.5-2k rows. None where the run
has no trace or no spans of the program; 0.0 when the traced seconds hold no
round or no call of the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.latent_decode_bw_share.longctx").read
