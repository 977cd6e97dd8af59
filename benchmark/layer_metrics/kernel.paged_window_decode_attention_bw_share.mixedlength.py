"""The decode kernel's share of its roofline in the WINDOW layers' calls of
the mixed-length cell: what ``kernel.paged_decode_attention_bw_share.
mixedlength`` reads of the global layer's call, of the calls named
``paged_window_decode_attention``, with the rows a step's live streams
attend to in a window layer: ``window_context`` of the tail's
``engine.decode_dispatch`` spans (the sum over live streams of min(context,
window)) over ``k_steps``, x 4096 B, over 819 GB/s, over the mean device
time of a call (four a step: one a window layer held).

A window call reads at most two pages a stream whatever the context, so its
bytes are small (at most 0.5 MB a stream) and its time is a grid step's
overhead more than the bus: a low share here is the price of a call that
does not grow with the context, and the number to watch is the call's time
beside the global call's (PERF.md section 5). None where the run has no
trace or no spans, or the rounds do not say ``window_context`` (a program
without window layers). 0.0 when the traced seconds hold no round or no call
of the kernel."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_window_decode_attention[.\d]* ="
ROWS = "window_context"
GLOBAL_CALLS = "kernel.paged_decode_attention_bw_share.mixedlength"


def read(run: dict):
    return load_layer_metric(GLOBAL_CALLS).read(run, kernel=KERNEL,
                                                rows_of=ROWS)
