"""Mean time from a request's admission to its first token, over the first
tokens inside the window: prefill chunks, each of which waits for the
decode round in flight, and the first-token sampler. Queue wait ends where
this starts (``engine.queue_wait_mean_ms.chat``); the two together are the
engine's share of the client's TTFT. The difference of
``LLMEngine.counters()``' ``prefill_phase_sum_s`` over that of
``prefill_phase_n``; 0.0 when no first token fell in the window."""

from benchmark.program_readers import mean_ms

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}


def read(run: dict):
    return mean_ms(run, "engine", "prefill_phase")
