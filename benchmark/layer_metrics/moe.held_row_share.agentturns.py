"""Share of the rows the expert layers routed that they COMPUTED in the
agent-turns cell: ``moe.held_row_share.mixedlength``'s reader
(Δ``expert_rows_held`` / Δ``expert_rows_routed`` of
``LLMEngine.counters()``; a row is one of a token's twenty-two choices, held
when its expert is one of the 128 of 512 this chip keeps): one block of 4,
25% in expectation, level over seeds by the stratified bias. None where the
program has no such counters; 0.0 for a window that routed no row."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("moe.held_row_share.mixedlength").read
