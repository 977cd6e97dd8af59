"""How full a decode step runs in the long-answer cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 64 clients on 64 slots whose answers are as long as their
prompts: a slot decodes nearly all the time, so this stands near 100% and
falls with whatever keeps lanes in prefill."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
