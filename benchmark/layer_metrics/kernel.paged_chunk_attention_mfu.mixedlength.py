"""Utilisation of the chunk attention kernel in the mixed-length cell, over
its calls of both kinds (``paged_chunk_attention``: the global layer's;
``paged_window_chunk_attention``: the window layers'): the operations the
prefilled prompts' attention NEEDS over the calls' device time times the
chip's bf16 peak.

Needed: the architecture's ``counts.chunk_attention_flops``, per (query, key,
head) a score and a value sum over a head's values, a global layer's pairs
causal, a window layer's at the window's length WHATEVER the kernel computes
(a window call computes on every 512-key block its tile's window touches and
masks the rest: work the kernel chose). The trace names a call and not its
prompt, so the needed operations of one chunk are the window's mean (all the
prompts completed in the window, over all their chunks), the chunks a traced
program carries are the window's too (Δ``prefill_chunks_dispatched`` /
Δ``prefill_programs_dispatched`` of ``LLMEngine.counters()``), and the chunk
programs are counted as ``step.prefill_mfu.mixedlength`` counts them. Time:
the sum of the kernel's events of both names in the trace, found by the name
the instruction itself has.

None where the run has no trace or the program no such counters. 0.0 when
the traced seconds hold no chunk program or no call of the kernel."""

from benchmark import architecture, tracing
from benchmark.program_readers import delta
from benchmark.traffic import n_chunks

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_(window_)?chunk_attention[.\d]* ="
MODULE = r"^jit__lambda"
MIN_SECONDS = 0.002


def read(run: dict):
    trace, prefill = run.get("trace"), run.get("prefill")
    loadgen = run.get("loadgen")
    if trace is None or prefill is None or loadgen is None \
            or not trace["devices"]:
        return None
    d = delta(run, "engine", "prefill_chunks_dispatched",
              "prefill_programs_dispatched")
    if d is None:
        return None
    chunks, programs = d
    traced = [e for e in tracing.module_events(trace, MODULE)
              if e[2] >= MIN_SECONDS]
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    lens = loadgen.get("prompt_lens_in_window")
    if not traced or not calls or programs <= 0 or not lens:
        return 0.0
    counts = architecture.part(run["config"], "counts")
    a_chunk = sum(counts.chunk_attention_flops(run["config"], n)
                  for n in lens) \
        / sum(n_chunks(n, prefill["chunk"]) for n in lens)
    need = len(traced) * (chunks / programs) * a_chunk
    return 100.0 * need / (sum(calls) * run["peaks"]["bf16_flops"])
