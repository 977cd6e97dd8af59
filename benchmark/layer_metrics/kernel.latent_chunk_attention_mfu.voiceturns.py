"""Utilisation of the kernel ``paged_latent_chunk_attention`` in the
voice-turns cell: the operations attention NEEDS for the chunks of the
traced seconds, over the device time of the kernel's calls in the same
seconds times the chip's bf16 peak.

Needed: the architecture's ``counts.latent_chunk_attention_flops`` (absorbed,
as the kernel runs it: per pair and head a score over 576 values and a value
sum over 512) over the pairs the chunks' queries can SEE (no selection: a
query at position ``t`` attends to ``t + 1`` keys), ``context`` of the
``engine.prefill_dispatch`` spans in the trace, once an attention held (two
a published layer). The blocks a call computes behind its causal mask and a
last chunk's padding are work the kernel chose and are not counted. It
cannot pass 100% while the time covers the work.

None where the run has no trace or no spans of the program, or the spans do
not say their context (a program from before this reader). 0.0 when the
traced seconds hold no call of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_latent_chunk_attention[.\d]* ="
CHUNKS = "engine.prefill_dispatch"


def read(run: dict):
    trace, spans = run.get("trace"), run.get("host_spans")
    if trace is None or not trace["devices"] or spans is None:
        return None
    chunks = [a for name, _, _, a in
              hostspans.thread_with(spans, hostspans.ENGINE_THREAD) or []
              if name == CHUNKS]
    if any("context" not in a for a in chunks):
        return None
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    if not calls:
        return 0.0
    conf = run["config"]
    need = 2 * conf["num_layers"] * architecture.part(
        conf, "counts").latent_chunk_attention_flops(
            conf, sum(int(a["context"]) for a in chunks))
    return 100.0 * need / (sum(calls) * run["peaks"]["bf16_flops"])
