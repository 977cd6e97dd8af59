"""The kernel ``ssd_chunk``'s share of its roofline in the assistant cell: the
least time the chip could take for ONE call (one layer's SSD mixer in one
chunk program), the larger of its operations over the bf16 peak and its bytes
over the bus's published bandwidth, over the mean device time of a call.

Operations and bytes are the architecture's ``counts.ssd_chunk_flops`` (the
RECURRENCE a token needs, 5 a head, state and value: the chunked form's ``C
B^T`` and masked products are how the kernel runs it on the matrix unit and
are not counted) and ``counts.ssd_chunk_bytes`` (a token its heads' ``x dt``
in and ``y`` out in float32, its groups' ``B`` and ``C`` and a log-decay a
head; a row the ``[32, 256, 128]`` float32 state in and out) for the tokens
and the rows a call carries. The trace names a call and not its prompts, so
both are the window's means: Δ``prefill_tokens_dispatched`` and
Δ``prefill_chunks_dispatched`` over Δ``prefill_programs_dispatched`` of
``LLMEngine.counters()`` (real tokens: a last chunk's padding is not
counted), as ``kernel.ssm_scan_roofline_share.reasoning`` takes them. At a
short chunk the state's 8.4 MB in and out are most of the bytes and the bus
is the nearer roof; the reader takes whichever the counts say. Time: the
events of ``ssd_chunk`` in the trace, found by the name the instruction
itself has.

None where the run has no trace or the program no such counters. 0.0 when
the window dispatched no chunk program or the traced seconds hold no call."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?ssd_chunk[.\d]* ="


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    d = delta(run, "engine", "prefill_tokens_dispatched",
              "prefill_chunks_dispatched", "prefill_programs_dispatched")
    if d is None:
        return None
    tokens, chunks, programs = d
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    if programs <= 0 or not calls:
        return 0.0
    conf, peaks = run["config"], run["peaks"]
    counts = architecture.part(conf, "counts")
    floor_s = max(
        counts.ssd_chunk_flops(conf, tokens / programs)
        / peaks["bf16_flops"],
        counts.ssd_chunk_bytes(conf, tokens / programs, chunks / programs,
                               run["weight_bytes_per_param"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / (sum(calls) / len(calls))
