"""How full a decode step runs: tokens emitted over steps dispatched x
slots, over the window (benchmark/program_readers.py::decode_occupancy);
0.0 when no step was dispatched."""

from benchmark.program_readers import decode_occupancy as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}
