"""Share of the page pool's bytes that hold the KDA layers' state a SEQUENCE
(the recurrent matrices in float32 and the convolutions' tails, one entry a
slot) and not rows a token: ``kv_sequence_pool_bytes`` over ``kv_pool_bytes``
of ``LLMEngine.counters()``, both constants of the engine as built. 13.0 MB a
sequence x 32 beside 2.28 GB of K and V of the one GQA layer: 15%, whatever
the contexts' length (four full-attention layers would hold 9.1 GB for the
same 32 contexts). None where the program has no such counter (a program from
before the sequence planes)."""

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}


def read(run: dict):
    engine = (run.get("counters_after") or {}).get("engine") or {}
    if "kv_sequence_pool_bytes" not in engine \
            or not engine.get("kv_pool_bytes"):
        return None
    return 100.0 * engine["kv_sequence_pool_bytes"] / engine["kv_pool_bytes"]
