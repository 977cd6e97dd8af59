"""The kernel ``ssd_chunk``'s share of its roofline in the agent-turns cell:
what ``kernel.ssd_chunk_roofline_share.assistant`` reads, its reader, with
THIS architecture's ``counts.ssd_chunk_flops`` and ``counts.ssd_chunk_bytes``:
128 heads of 64 values, 128 states, 8 groups (the state a layer a sequence the
same 4,194,304 B as Falcon-H1's, in heads half as wide: a ``[.., 128, 64]``
block fills half a lane tile). ONE call is one Mamba layer's mixer in one
chunk program, five a program; tokens and rows a call are the window's means
(Δ``prefill_tokens_dispatched`` and Δ``prefill_chunks_dispatched`` over
Δ``prefill_programs_dispatched``). None where the run has no trace or the
program no such counters; 0.0 when the window dispatched no chunk program or
the traced seconds hold no call."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kernel.ssd_chunk_roofline_share.assistant").read
