"""The kernel ``paged_index_scores``'s share of its roofline in the agent-context
cell: the least time the chip could take for the indexer's scores of the
traced seconds, over the device time of the kernel's calls in the same
seconds.

The least time, a call: the larger of its operations over the bf16 peak and
its bytes over the bus's published bandwidth (the architecture's
``counts.index_scores_flops``: 2 x 32 x 128 a (query, key) pair the query can
SEE; ``counts.index_scores_bytes``: the 256-byte index keys of the context a
call scores, read once). A chunk's call scores 512 queries against its
context's keys and stands on the matrix unit; a decode step's call scores one
query a stream and stands on the bus; the floors of the two kinds are added.
What the calls scored is what the scheduler's spans in the trace say of their
programs: ``context`` of ``engine.prefill_dispatch`` (pairs the chunks'
queries can see) and of ``engine.decode_dispatch`` (keys the live streams'
queries can see: a pair a key), each once a layer held. Time: the kernel's
events in the trace, found by the name the instruction itself has. It cannot
pass 100% while the time covers the work.

None where the run has no trace or no spans of the program, or its dispatch
spans do not say what was selected (a program without an indexer). 0.0 when
the traced seconds hold no call of the kernel."""

from benchmark import architecture, hostspans, tracing

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "serve_tokens_per_s"}

KERNEL = r"^%?paged_index_scores[.\d]* ="
CHUNKS, ROUND = "engine.prefill_dispatch", "engine.decode_dispatch"


def read(run: dict):
    trace, spans = run.get("trace"), run.get("host_spans")
    if trace is None or not trace["devices"] or spans is None:
        return None
    thread = hostspans.thread_with(spans, hostspans.ENGINE_THREAD) or []
    chunks = [a for name, _, _, a in thread if name == CHUNKS]
    rounds = [a for name, _, _, a in thread if name == ROUND]
    if any("selected" not in a for a in chunks + rounds):
        return None
    calls = [dur for _, _, dur in tracing.ops_within(
        trace, float("-inf"), float("inf"), KERNEL)]
    if not calls:
        return 0.0
    conf, peaks = run["config"], run["peaks"]
    counts = architecture.part(conf, "counts")
    value = run["weight_bytes_per_param"]

    def floor_s(pairs, keys):
        return max(counts.index_scores_flops(conf, pairs)
                   / peaks["bf16_flops"],
                   counts.index_scores_bytes(conf, keys, value)
                   / peaks["hbm_bytes_per_s"])

    # a chunk's 512 queries share its context's keys: pairs / 512 at most
    chunk_pairs = sum(int(a["context"]) for a in chunks)
    step_keys = sum(int(a["context"]) for a in rounds)
    need = conf["num_hidden_layers"] * (
        floor_s(chunk_pairs, chunk_pairs / run["prefill"]["chunk"])
        + floor_s(step_keys, step_keys))
    return 100.0 * need / sum(calls)
