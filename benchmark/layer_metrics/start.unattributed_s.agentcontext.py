"""Seconds of the agent-context cell's ``setup_s`` that no start phase of the
program accounts for: ``setup_s`` less the ``start_<phase>_sum_s`` keys of
the snapshot taken as the window opens (the engine's constructor;
benchmark/startup_readers.py::unattributed_s). The harness's own share of
the start (imports and backend start, the seeded weights, the float32
reference over two prompts of 12k and 2k, the warm-up) plus what the program
does outside its constructors. None where the run has no snapshot or no
``setup_s``."""

from benchmark.startup_readers import unattributed_s as read  # noqa: F401

DECLARATION = {"unit": "s", "better": "lower", "source": "program_counter",
               "layer": "start-up", "moves": "setup_s"}
