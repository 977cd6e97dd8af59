"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the mixed-length cell: what ``engine.sched_busy_share_window.chat``
reads (benchmark/phase_readers.py::sched_busy_share_window: 100 x
Δ``sched_host_busy_sum_s`` / ``window_s``). 32 streams a round and one or
two chunks of 512 a pass: the host's time an iteration against a step of
some ten milliseconds and a chunk program of some tens. 0.0 for a window in
which the loop did nothing of its own; None where the program has no such
counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
