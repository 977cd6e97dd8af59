"""How full a decode step runs in the assistant cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 48 clients on 48 slots, prompts of one or two chunks before
answers of 256-384 tokens: a slot is in prefill for a step or two of the 320
its request lives, so nearly every row of every step buys a token."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
