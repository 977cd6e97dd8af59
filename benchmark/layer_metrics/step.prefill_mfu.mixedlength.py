"""Utilisation of the chunk-prefill programs in the mixed-length cell: the
operations the prefilled tokens NEED on this chip (the architecture's
``counts.py``: 2 per multiplied parameter with the experts at the EXPECTED
rows held, one of a token's eight choices beside the shared expert; a window
layer's scores at the window's length, the global layer's causal; the head
once a prompt; the head over every chunk row, a last chunk's padding and the
blocks a window call computes on behind its mask are work the program chose
and are not counted) over the device time of those programs in the trace
times the chip's bf16 peak.

The ``.longanswer`` reader's way, which does not understate: the trace names
a program and not its prompts, so the needed operations of one chunk are the
window's mean (all the prompts completed in the window, over all their
chunks), and the chunks one program carries are the window's too:
Δ``prefill_chunks_dispatched`` / Δ``prefill_programs_dispatched`` of
``LLMEngine.counters()`` (a program of this cell carries up to two prompts'
chunks). None where the program has no such counters; 0.0 when the traced
seconds hold no chunk prefill or the window dispatched none."""

from benchmark import tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

# The engine jits its paged chunk prefill as a lambda; the decode dispatch
# and the small programs have names of their own. A chunk through five
# full-width layers takes tens of milliseconds, the other lambdas (cache
# copies, row updates) microseconds.
MODULE = r"^jit__lambda"
MIN_SECONDS = 0.002


def read(run: dict):
    trace, prefill = run.get("trace"), run.get("prefill")
    if trace is None or prefill is None or not trace["devices"]:
        return None
    d = delta(run, "engine", "prefill_chunks_dispatched",
              "prefill_programs_dispatched")
    if d is None:
        return None
    chunks, programs = d
    traced = [e for e in tracing.module_events(trace, MODULE)
              if e[2] >= MIN_SECONDS]
    if not traced or programs <= 0:
        return 0.0
    seconds = sum(e[2] for e in traced)
    need = len(traced) * (chunks / programs) \
        * prefill["mean_useful_flops_per_chunk"]
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops"])
