"""The decode kernel's share of its roofline in the eight WINDOW layers'
calls of the reasoning cell: what ``kernel.paged_window_decode_attention_
bw_share.mixedlength`` reads, its reader (the rows a step's live streams
attend to in a window layer, ``window_context`` of the tail's
``engine.decode_dispatch`` spans: min(context, 512) a stream, x 5120 B, over
the bus, over the mean device time of a ``paged_window_decode_attention``
call). A call reads at most five pages of a ring of nine a stream whatever
the context (84 MB for 32 streams), so its time is the walk's overhead more
than the bus (PERF.md section 7: the window call at a ring of 9 pages). None
where the run has no trace or no spans; 0.0 when the traced seconds hold no
round or no call of the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric(
    "kernel.paged_window_decode_attention_bw_share.mixedlength").read
