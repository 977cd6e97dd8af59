"""Share of the page pool's bytes that the eight window layers' planes hold
in the reasoning cell: ``kv.window_share_of_pool.mixedlength``'s reader
(``kv_window_pool_bytes`` over ``kv_pool_bytes`` of ``LLMEngine.counters()``,
both constants of the engine as built). A ring of 9 pages a sequence (288
pages for 32 slots) in eight layers, 1.51 GB, beside ONE layer's 2080 pages,
1.36 GB: half the pool; held alike by every attention layer the same 32
contexts would take 43.6 GB. None where the program has no such counter."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.window_share_of_pool.mixedlength").read
