"""How full a decode step runs in the voice-turns cell:
``program_readers.decode_occupancy`` (tokens the consumed rounds handed to
requests over steps x slots). 48 closed-loop clients on 48 slots, each turn
one to three chunks before an answer of 256-512 tokens: a slot decodes for
most of its request's life, so the steps run nearly full."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
