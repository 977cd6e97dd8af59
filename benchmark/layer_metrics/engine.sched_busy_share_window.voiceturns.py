"""Share of the window the engine's scheduler thread spent in work of its
own in the voice-turns cell (``phase_readers.sched_busy_share_window``):
with one step a dispatch over 48 streams the loop turns some hundred times a
second, each turn behind a device step of about ten milliseconds to hide
in. 0.0 for a window in which the loop did nothing of its own; None where
the program has no such counter."""

from benchmark.phase_readers import sched_busy_share_window as read  # noqa: F401

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}
