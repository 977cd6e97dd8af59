"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the long-document cell (benchmark/phase_readers.py::
sched_busy_share_window: 100 x Δ``sched_host_busy_sum_s`` / ``window_s``). 32
streams a round and one chunk program of two rows a pass: the host's time an
iteration against about 40 ms of device work. The cell is sized so that the
device and not the host sets the pace (ISSUE 43: PR 42's cell was refused for
a host-bound run 11% off its median): expect under 45%. 0.0 for a window in
which the loop did nothing of its own; None where the program has no such
counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
