"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the chat cell: 100 x Δ``sched_host_busy_sum_s`` / ``window_s``
(benchmark/phase_readers.py::sched_busy_share_window; the counter is the
loop's wall time less its fetches from the device and its waits for work,
summed always, capture or none). What ``engine.sched_busy_share.*`` read
from three traced seconds behind the window, over all 51 of it. Here a
round is one step of 15-16 ms and the scheduler's own 5-6 ms of it is one of
the three terms ``itl_p95_ms`` stands on. 0.0 for a window in which the loop
did nothing of its own; None where the program has no such counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}
