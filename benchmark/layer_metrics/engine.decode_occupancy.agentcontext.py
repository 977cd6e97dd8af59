"""How full a decode step runs in the agent-context cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 16 clients on 16 slots, prompts of 8-24 chunks before answers of
128-256 tokens, ONE chunk program an iteration: a slot waits in prefill for
much of its request's life, so a step's rows are far from full."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
