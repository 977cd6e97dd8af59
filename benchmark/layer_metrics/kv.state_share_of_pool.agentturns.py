"""Share of the page pool's bytes that hold the Mamba layers' SSD state a
SEQUENCE (``[128, 128, 64]`` float32 and the convolution's tail, one entry a
slot a layer) and not rows a token: ``kv.state_share_of_pool.longdoc``'s
reader (``kv_sequence_pool_bytes`` over ``kv_pool_bytes`` of
``LLMEngine.counters()``, both constants of the engine as built). 21.3 MB a
sequence x 128 = 2.72 GB of a pool of 3.11 GB: 87.6%, whatever the contexts'
length: ten layers of eleven hold nothing a token. None where the program has
no such counter."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "KV manager", "moves": "serve_tokens_per_s"}

read = load_layer_metric("kv.state_share_of_pool.longdoc").read
