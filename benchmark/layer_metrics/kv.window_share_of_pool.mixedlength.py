"""Share of the page pool's bytes that the window layers' planes hold in the
mixed-length cell: ``kv_window_pool_bytes`` over ``kv_pool_bytes`` of
``LLMEngine.counters()``, both constants of the engine as built. Four window
layers keep a ring of 6 pages a sequence (192 pages for 32 slots) beside one
global layer's 2304 pages: 0.40 GB of 1.61; held alike by every layer the
same 32 contexts would take 6.0 GB, four fifths of it in the window layers.
None where the program has no such counter (a program from before the window
planes)."""

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}


def read(run: dict):
    engine = (run.get("counters_after") or {}).get("engine") or {}
    if "kv_window_pool_bytes" not in engine \
            or not engine.get("kv_pool_bytes"):
        return None
    return 100.0 * engine["kv_window_pool_bytes"] / engine["kv_pool_bytes"]
