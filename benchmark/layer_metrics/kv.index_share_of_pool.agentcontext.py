"""Share of the page pool's bytes that hold the indexer's key a token (the
``idx`` planes: 256 bytes a token a layer) beside the latent rows (1280):
``index_pool_bytes`` over ``kv_pool_bytes`` of ``LLMEngine.counters()``, both
constants of the engine as built: 16.7% at the published widths. FP8 index
keys (the published code's; PERF.md section 7) would read 9.3%. None where
the program has no such counter."""

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}


def read(run: dict):
    after = (run.get("counters_after") or {}).get("engine") or {}
    if "index_pool_bytes" not in after or not after.get("kv_pool_bytes"):
        return None
    return 100.0 * after["index_pool_bytes"] / after["kv_pool_bytes"]
