"""How full a decode step runs in the reasoning cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 32 clients on 32 slots, prompts of one to three chunks before
answers of 1536-2560 tokens: a slot is in prefill for a hundredth of its
request's life, so nearly every row of every step buys a token."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
