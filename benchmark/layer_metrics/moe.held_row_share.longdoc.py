"""Share of the rows the expert layers routed that they COMPUTED in the
long-document cell: Δ``expert_rows_held`` / Δ``expert_rows_routed`` of
``LLMEngine.counters()`` over the window (``moe.held_row_share.mixedlength``'s
reader): a row is one of a token's eight choices, held when its expert is one
of the 40 of 320 this chip keeps: 12.5% in expectation, which the stratified
bias holds every seed near. None where the program has no such counters; 0.0
for a window that routed no row."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("moe.held_row_share.mixedlength").read
