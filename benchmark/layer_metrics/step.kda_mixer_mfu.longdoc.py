"""Utilisation of a KDA layer's WHOLE mixer in the chunk programs of the
long-document cell: the operations ONE KDA layer's mixer needs for the tokens
a chunk program carries (the architecture's ``counts.kda_mixer_flops``: 2 per
multiplied parameter of its projections, low-rank pairs and beta, and the
recurrence, 7 dk dv a head a token) over the stretch of the program that
layer's mixer runs in, times the chip's bf16 peak.

The stretch is found by the two kernels' names, which the trace prints, and
by nothing inside it: within one chunk program, from the end of the last
grouped-matmul call (``gmm``: the expert kernels of the layer BEFORE) in
front of a ``kda_chunk`` call to the start of the first one behind it (the
layer's OWN experts). So it holds everything the KDA layer does outside its
expert kernels: the norm, the projections, the convolutions, gates and L2
norms, the chunked form's state-free operands (``ops/kda.py::block_operands``:
the sub-blocks' decay-weighted products, the triangular solves, the float32
transposes), the scan kernel ``kda_chunk`` itself, the output norm, gate and
projection, and beside them what XLA runs there of the expert layers around
it (the layer before's combine, this one's router, sort and shared expert).
That is the point: whichever side of the kernel's boundary a later change
puts the operands on, the stretch and the needed operations stay what they
are, which ``kernel.kda_chunk_roofline_share.longdoc`` (the kernel's own
calls alone, about a twentieth of the stretch) cannot say. It understates the
mixer's own utilisation by the expert layers' part of the stretch and cannot
overstate it: every counted operation runs inside the stretch.

The trace names a call and not its prompts, so the tokens a program carries
are the window's mean: Δ``prefill_tokens_dispatched`` over
Δ``prefill_programs_dispatched`` of ``LLMEngine.counters()`` (real tokens: a
last chunk's padding is not counted). A ``kda_chunk`` call with no expert
kernel on one of its sides inside its program (a program the trace cut) is
left out.

None where the run has no trace or the program no such counters. 0.0 when
the window dispatched no chunk program or the traced seconds hold no such
stretch."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

SCAN = r"^%?kda_chunk[.\d]* ="
EXPERTS = r"^%?gmm[.\d]* ="
MODULE = r"^jit__lambda"
MIN_SECONDS = 0.002


def stretches(trace: dict) -> list:
    """(start, end) of every stretch between two expert kernels of one chunk
    program that holds a ``kda_chunk`` call."""
    found = set()
    for _, start, dur in tracing.module_events(trace, MODULE):
        if dur < MIN_SECONDS:
            continue
        experts = tracing.ops_within(trace, start, start + dur, EXPERTS)
        for _, at, _ in tracing.ops_within(trace, start, start + dur, SCAN):
            before = [s + d for _, s, d in experts if s + d <= at]
            behind = [s for _, s, _ in experts if s >= at]
            if before and behind:
                found.add((max(before), min(behind)))
    return sorted(found)


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    d = delta(run, "engine", "prefill_tokens_dispatched",
              "prefill_programs_dispatched")
    if d is None:
        return None
    tokens, programs = d
    counts = architecture.part(run["config"], "counts")
    spans = stretches(trace)
    if programs <= 0 or not spans:
        return 0.0
    need = len(spans) * counts.kda_mixer_flops(run["config"],
                                               tokens / programs)
    return 100.0 * need / (sum(e - s for s, e in spans)
                           * run["peaks"]["bf16_flops"])
