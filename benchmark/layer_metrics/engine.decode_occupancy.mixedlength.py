"""How full a decode step runs in the mixed-length cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 32 clients on 32 slots, prompts of some thousands of tokens
before answers of some hundreds: a slot is in prefill for a good part of its
request's life, and every lane in prefill is a row of the step that buys no
token."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
