"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the assistant cell (benchmark/phase_readers.py::
sched_busy_share_window: 100 x Δ``sched_host_busy_sum_s`` / ``window_s``). 48
streams a round of one step, 17-21 ms of device work reckoned: the host has
to stay under that an iteration for the device to set the pace, and it syncs
and emits for half as many streams again as any other closed cell. 0.0 for a
window in which the loop did nothing of its own; None where the program has
no such counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
