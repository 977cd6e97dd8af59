"""Utilisation of the chunk-prefill programs in the long-context cell: the
operations the prefilled tokens NEED (the architecture's ``counts.py``: each
token's keys and values expanded from its latent row once, the expanded
attention's 20.5 kFLOP a context token, top-k experts and the shared one, the
head once a prompt; a chunk's re-expansion or absorbed attention over the
cached context, the head over every chunk row and a last chunk's padding are
work the program chose and are not counted) over the device time of those
programs in the trace times the chip's bf16 peak.

The trace names a program and not its prompt, so the needed operations of one
chunk are the window's mean: all the prompts completed in the window, over
all their chunks. 0.0 when the traced seconds hold no chunk prefill."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

# The engine jits its paged chunk prefill as a lambda; the decode dispatch
# and the small programs have names of their own. A chunk through seven
# full-width layers takes tens of milliseconds, the other lambdas (cache
# copies, row updates) microseconds.
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
