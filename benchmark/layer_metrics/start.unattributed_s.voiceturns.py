"""What no start phase of the program accounts for of ``setup_s`` in the
voice-turns cell (``startup_readers.unattributed_s``): the harness's own
stretches (10 GB of seeded weights, the float32 reference over prompts of
2168 and 504, the warm-up) plus what the program does outside its
constructors. None where the run has no snapshot or no ``setup_s``."""

from benchmark.startup_readers import unattributed_s as read  # noqa: F401

DECLARATION = {"unit": "s", "better": "lower", "source": "program_counter",
               "layer": "start-up", "moves": "setup_s"}
