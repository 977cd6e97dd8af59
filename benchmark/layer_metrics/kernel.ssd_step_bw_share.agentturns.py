"""The kernel ``ssd_step``'s share of its roofline, the memory bus, in the
agent-turns cell: what ``kernel.ssd_step_bw_share.assistant`` reads, its
reader, with THIS architecture's ``counts.ssd_step_bytes``: a live stream's
``[128, 128, 64]`` float32 state read and written where it lies (8.4 MB) and
its convolution tail of 10240 channels in and out, for the window's mean live
streams (Δ``decode_tokens_emitted`` / Δ``decode_steps_dispatched``; 128
slots). Time: the events of ``ssd_step`` in the trace, five a step (one a
Mamba layer held), those of the steps a chunk program carries among them.
None where the run has no trace or the program no such counters; 0.0 when the
window dispatched no step or the traced seconds hold no call."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.ssd_step_bw_share.assistant").read
