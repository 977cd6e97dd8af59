"""The latent decode kernel's share of its roofline, the memory bus, in the
agent-context cell: the bytes ONE call (one layer of one decode step) NEEDS,
over the chip's published bandwidth, over the device time of a call; bytes
and time are means over the SAME traced seconds
(``kernel.latent_decode_bw_share.longctx``'s way).

Bytes: the architecture's ``counts.latent_decode_bytes`` over the rows the
live streams' queries SELECTED (1280 bytes a row as held): ``selected`` of
the ``engine.decode_dispatch`` spans in the trace (``min(2048, t + 1)`` for a
stream at position ``t``) over their ``k_steps``. The kernel walks every
live page under the selection's mask today (a stream at 12k reads six times
its selected rows) and is charged none of the rest. It cannot pass 100% while
the time covers the reads.

None where the run has no trace or no spans of the program, or the rounds do
not say what was selected (a program without an indexer). 0.0 when the
traced seconds hold no round or no call of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_latent_decode_attention[.\d]* ="
ROUND = "engine.decode_dispatch"


def read(run: dict):
    trace, spans = run.get("trace"), run.get("host_spans")
    if trace is None or not trace["devices"] or spans is None:
        return None
    rounds = [a for name, _, _, a in
              hostspans.thread_with(spans, hostspans.ENGINE_THREAD) or []
              if name == ROUND]
    if any("selected" not in a for a in rounds):
        return None
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    steps = sum(int(a["k_steps"]) for a in rounds)
    if steps <= 0 or not calls:
        return 0.0
    rows = sum(int(a["selected"]) for a in rounds) / steps
    need = architecture.part(run["config"], "counts").latent_decode_bytes(
        run["config"], rows, run["weight_bytes_per_param"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] \
        / (sum(calls) / len(calls))
