"""The kernel ``kda_chunk``'s share of its roofline in the long-document
cell: the least time the chip could take for ONE call (one KDA layer of one
chunk program), the larger of its operations over the bf16 peak and its bytes
over the bus's published bandwidth, over the mean device time of a call.

Operations and bytes are the architecture's ``counts.kda_chunk_flops`` (the
recurrence, 7 dk dv a head a token) and ``counts.kda_chunk_bytes`` (a token a
head the float32 rows of the blocks' operands and the output, a row a head
the state in and out) for the tokens and the rows a call carries. The trace
names a call and not its prompts, so both are the window's means:
Δ``prefill_tokens_dispatched`` and Δ``prefill_chunks_dispatched`` over
Δ``prefill_programs_dispatched`` of ``LLMEngine.counters()`` (real tokens: a
last chunk's padding is not counted). At about 40 operations a byte against
the chip's 240 the bus is the nearer roof; the reader takes whichever the
counts say. Time: the events of ``kda_chunk`` in the trace, found by the name
the instruction itself has. What the kernel does NOT cover (the projections,
convolutions, gates and the blocks' triangular solves, which are XLA's and
read no state) is ``step.prefill_mfu.longdoc``'s to show.

None where the run has no trace or the program no such counters. 0.0 when
the window dispatched no chunk program or the traced seconds hold no call."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?kda_chunk[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    d = delta(run, "engine", "prefill_tokens_dispatched",
              "prefill_chunks_dispatched", "prefill_programs_dispatched")
    counts = architecture.part(run["config"], "counts")
    if d is None:
        return None
    tokens, chunks, programs = d
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    if programs <= 0 or not calls:
        return 0.0
    conf, peaks = run["config"], run["peaks"]
    floor_s = max(
        counts.kda_chunk_flops(conf, tokens / programs)
        / peaks["bf16_flops"],
        counts.kda_chunk_bytes(conf, tokens / programs, chunks / programs)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / (sum(calls) / len(calls))
