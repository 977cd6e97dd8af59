"""Share of the WINDOW the engine's scheduler thread spent on work of its
own in the long-answer cell: what
``engine.sched_busy_share_window.chat`` reads
(benchmark/phase_readers.py::sched_busy_share_window: 100 x
Δ``sched_host_busy_sum_s`` / ``window_s``). With 64 streams a round hands 64
tokens a step to 64 handler threads; the tail's
``engine.sched_busy_share.longanswer`` read 45-51% of three seconds, and one
run of 26 served a tenth fewer tokens with it at 63%: this is the same share
over the window those tokens were counted in. 0.0 for a window in which the
loop did nothing of its own; None where the program has no such counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
