"""The kernel ``ssm_scan``'s share of its roofline in the reasoning cell: the
least time the chip could take for ONE call (one Mamba layer of one chunk
program) over the mean device time of a call.

THE ROOF IS THE MEMORY BUS, the nearest roof the chip PUBLISHES: the
architecture's ``counts.ssm_scan_bytes`` (a token its 5120 channels of ``x``
and ``Delta`` in and of ``y`` out in float32 and its ``B`` and ``C``; a row
the ``[16, 5120]`` state in and out) over 819 GB/s. The kernel has no matrix
product, so the bf16 peak is no roof of its, and what BINDS it is the
vector and transcendental units, for which no peak is published: 5120 x 16
exponentials and seven multiply-adds each a token a layer. So this share is
expected well under 100% and says how far the exponentials stand from the
bus; beside it, by hand, ``counts.ssm_scan_elements`` over a call's time is
the exponentials a second (``scripts/phi4flash_kernels_chip.py`` prints
both; PERF.md section 5).

The trace names a call and not its prompts, so tokens and rows are the
window's means: Δ``prefill_tokens_dispatched`` and
Δ``prefill_chunks_dispatched`` over Δ``prefill_programs_dispatched`` of
``LLMEngine.counters()`` (real tokens: a last chunk's padding is not
counted). Time: the events of ``ssm_scan`` in the trace, found by the name
the instruction itself has.

None where the run has no trace or the program no such counters. 0.0 when
the window dispatched no chunk program or the traced seconds hold no call."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?ssm_scan[.\d]* ="


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
    counts = architecture.part(run["config"], "counts")
    floor_s = counts.ssm_scan_bytes(
        run["config"], tokens / programs, chunks / programs) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (sum(calls) / len(calls))
