"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the reasoning cell (benchmark/phase_readers.py::
sched_busy_share_window: 100 x Δ``sched_host_busy_sum_s`` / ``window_s``). 32
streams a round of one step, 15-19 ms of device work: the host has to stay
under that an iteration for the device to set the pace (the 64-stream cell
is where it does not: PERF.md section 6). 0.0 for a window in which the loop
did nothing of its own; None where the program has no such counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
