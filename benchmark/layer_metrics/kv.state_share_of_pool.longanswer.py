"""Share of the page pool's bytes that hold the conv layers' state (the
tails a page ends in) and not K and V: ``state_pool_bytes`` over
``kv_pool_bytes`` of ``LLMEngine.counters()``, both constants of the engine
as built. 56 KB of 568 KB a page at the published widths: what keeping the
state a page, so that it is reached, shared and preempted through the page
table, costs beside a state a slot (which would be 64 x 56 KB in all). None
where the program has no such counter (a program from before the state
planes)."""

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}


def read(run: dict):
    engine = (run.get("counters_after") or {}).get("engine") or {}
    if "state_pool_bytes" not in engine or not engine.get("kv_pool_bytes"):
        return None
    return 100.0 * engine["state_pool_bytes"] / engine["kv_pool_bytes"]
