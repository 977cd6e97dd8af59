"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the agent-context cell (benchmark/phase_readers.py::
sched_busy_share_window: 100 x Δ``sched_host_busy_sum_s`` / ``window_s``). An
iteration is one chunk program of tens of milliseconds that carries the
slots' step: the host has a long stretch to hide in. 0.0 for a window in
which the loop did nothing of its own; None where the program has no such
counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
