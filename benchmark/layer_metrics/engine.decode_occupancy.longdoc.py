"""How full a decode step runs in the long-document cell: tokens emitted over
steps dispatched x slots, over the window
(benchmark/program_readers.py::decode_occupancy); 0.0 when no step was
dispatched. 32 clients on 32 slots, prompts of 4k-16k tokens (8 to 32 chunk
programs of two rows) before answers of 256-1024: a slot is in prefill for a
good part of its request's life, and every lane in prefill is a row of the
step that buys no token (and, in the KDA layers' step kernel, a row that
moves no state: a dead row reads and writes nothing)."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
