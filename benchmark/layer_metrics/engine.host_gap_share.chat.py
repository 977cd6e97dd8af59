"""Share of the window the device waited on the host between decode rounds
(benchmark/readers.py::host_gap_share); 0.0 when no gap was sampled."""

from benchmark.readers import host_gap_share as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}
