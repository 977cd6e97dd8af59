"""What the HTTP server adds to a streamed completion's first byte, mean
over the completions whose first chunk went out inside the window: (handler
entry -> ``engine.submit`` returned) + (the engine's first-token stamp ->
the first chunk written to the socket), the difference of
``ModelServer.counters()``' running sum over the difference of its count.
32 streaming threads and the scheduler share one interpreter lock; this is
where that shows. 0.0 when no first chunk went out in the window."""

from benchmark.program_readers import mean_ms

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_counter",
               "layer": "router / server", "moves": "itl_p95_ms"}


def read(run: dict):
    return mean_ms(run, "server", "first_byte_overhead")
