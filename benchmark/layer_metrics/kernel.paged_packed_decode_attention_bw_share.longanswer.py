"""The packed-row decode kernel's share of its roofline, which is the memory
bus: the bytes ONE call (one attention layer of one decode step) has to
read, over the chip's published bandwidth, over the device time of a call.
Bytes and time are means over the SAME traced seconds.

Bytes: the K row and the V row of the context the live slots attend to (the
architecture's ``counts.packed_decode_bytes``: 2 KB a context token, against
16 kFLOP the equations need and 131 kFLOP the kernel spends multiplying whole
rows: 2.5 ns on the bus against 0.7 ns on the matrix unit). The rows of a
step are what the scheduler's ``engine.decode_dispatch`` spans in the trace
say of their rounds: ``context`` (rows the round's steps attend to, over its
live slots) over ``k_steps``. Time: the kernel's events in the trace, a call
an attention layer a step, found by the name the instruction itself has (the
trace gives an op's whole HLO text, and the ops that take the kernel's
result name it too, as their operand). The queries, the output and the pages
the kernel fetches and skips are not counted: a floor, which cannot pass
100% while the time covers the reads.

None where the run has no trace or no spans of the program, or the rounds do
not say their context. 0.0 when the traced seconds hold no round or no call
of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_packed_decode_attention[.\d]* ="
ROUND = "engine.decode_dispatch"


def read(run: dict):
    trace, spans = run.get("trace"), run.get("host_spans")
    if trace is None or not trace["devices"] or spans is None:
        return None
    rounds = [attrs for name, _, _, attrs in
              hostspans.thread_with(spans, hostspans.ENGINE_THREAD) or []
              if name == ROUND]
    if any("context" not in r for r in rounds):
        return None
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    steps = sum(int(r["k_steps"]) for r in rounds)
    if steps <= 0 or not calls:
        return 0.0
    rows = sum(int(r["context"]) for r in rounds) / steps
    need = architecture.part(run["config"], "counts").packed_decode_bytes(
        run["config"], rows, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] \
        / (sum(calls) / len(calls))
