"""Utilisation of the chunk-prefill programs: the operations the prefilled
tokens NEED (the architecture's ``counts.py``: 2 per multiplied parameter
with the top-k experts only, plus causal attention; padding of a prompt's
last chunk and the dispatch's capacity slack are not needed and not counted)
over the device time of those programs in the trace times the chip's bf16
peak.

The trace names a program and not its prompt, so the needed operations of one
chunk are the window's mean: all the prompts completed in the window, over
all their chunks. 0.0 when the traced seconds hold no chunk prefill."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

# The engine jits its paged chunk prefill as a lambda; the decode dispatch
# and the small programs have names of their own. A chunk through three or
# more full-width layers takes tens of milliseconds, the other lambdas
# (cache copies, row updates) microseconds.
MODULE = r"^jit__lambda"
MIN_SECONDS = 0.002


def read(run: dict):
    trace, prefill = run.get("trace"), run.get("prefill")
    if trace is None or prefill is None or not trace["devices"]:
        return None
    chunks = [e for e in tracing.module_events(trace, MODULE)
              if e[2] >= MIN_SECONDS]
    if not chunks:
        return 0.0
    seconds = sum(e[2] for e in chunks)
    need = len(chunks) * prefill["mean_useful_flops_per_chunk"]
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops"])
