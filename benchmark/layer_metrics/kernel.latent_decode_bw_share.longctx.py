"""The latent decode kernel's share of its roofline, which is the memory
bus: the bytes ONE call (one layer of one decode step) has to read, over the
chip's published bandwidth, over the device time of a call. Bytes and time
are means over the SAME traced seconds.

Bytes: the latent and rotary rows of the context the live slots attend to
(the architecture's ``counts.latent_decode_bytes``; per context token 1152
bytes against 43.5 kFLOP, so 1.4 ns on the bus against 0.2 ns on the
matrix unit). The rows of a step are what the scheduler's
``engine.decode_dispatch`` spans in the trace say of their rounds:
``context`` (rows the round's steps attend to, over its live slots) over
``k_steps``. The always-on counter ``decode_context_tokens`` sums the same
number, but over the measured window, whose steps are not the traced ones.
Time: the kernel's events in the trace, a call a layer a step, found by the
name the instruction itself has (the trace gives an op's whole HLO text, and
the slice that takes the kernel's result names the kernel too, as its
operand: 0.1 us each, which halved the mean before the review). The
queries, the output and the pages the kernel fetches and skips are not
counted: a floor, which cannot pass 100% while the time covers the reads.

None where the run has no trace or no spans of the program, or the rounds
do not say their context (a program from before the latent pool). 0.0 when
the traced seconds hold no round or no call of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_latent_decode_attention[.\d]* ="
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
    need = architecture.part(run["config"], "counts").latent_decode_bytes(
        run["config"], rows, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] \
        / (sum(calls) / len(calls))
