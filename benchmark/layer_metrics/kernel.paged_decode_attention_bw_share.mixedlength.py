"""The decode kernel's share of its roofline in the GLOBAL layer's call of
the mixed-length cell, which is the memory bus: the bytes ONE call (the
global layer of one decode step) has to read, over the chip's published
bandwidth, over the device time of a call. Bytes and time are means over the
SAME traced seconds.

Bytes: the K rows and the V rows of the contexts the live streams attend to
(the architecture's ``counts.decode_attention_bytes``: 4096 B a context
token at 8 KV heads of 128 in bf16). The rows of a step are what the
scheduler's ``engine.decode_dispatch`` spans in the trace say of their
rounds: ``context`` (rows the round's steps attend to, over its live slots)
over ``k_steps``. Time: the events of ``paged_decode_attention`` in the
trace, a call a global layer a step, found by the name the instruction
itself has (a window layer's call is ``paged_window_decode_attention`` and
is not matched). The queries, the output and the pages the kernel fetches
and skips are not counted: a floor, which cannot pass 100% while the time
covers the reads.

None where the run has no trace or no spans of the program, or the rounds do
not say their context. 0.0 when the traced seconds hold no round or no call
of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_decode_attention[.\d]* ="
ROUND = "engine.decode_dispatch"
ROWS = "context"


def read(run: dict, kernel: str = KERNEL, rows_of: str = ROWS):
    trace, spans = run.get("trace"), run.get("host_spans")
    if trace is None or not trace["devices"] or spans is None:
        return None
    rounds = [attrs for name, _, _, attrs in
              hostspans.thread_with(spans, hostspans.ENGINE_THREAD) or []
              if name == ROUND]
    if any(rows_of not in r for r in rounds):
        return None
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), kernel)]
    steps = sum(int(r["k_steps"]) for r in rounds)
    if steps <= 0 or not calls:
        return 0.0
    rows = sum(int(r[rows_of]) for r in rounds) / steps
    need = architecture.part(run["config"], "counts").decode_attention_bytes(
        run["config"], rows, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] \
        / (sum(calls) / len(calls))
