"""Time to first token as the client sees it: 95th percentile over all
requests of (first streamed token - the instant the request was DUE). What a
chat user feels first, and NOT an end-to-end metric of this benchmark: with
some 140 requests in a window and waits that span a whole decode round, its
runs spread by 15% (PERF.md, Findings), three times what a bound may cover.
It stands here so that every traced run records it. It and the gap between
tokens both follow the length of a decode round, which is why it names that
metric; 0.0 for a run in which no request completed."""

from benchmark.stats import percentile_or

DECLARATION = {"unit": "ms", "better": "lower", "source": "host_clock",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}


def read(run: dict):
    gen = run.get("loadgen")
    if gen is None:
        return None
    return percentile_or(gen["ttft_ms"], 95, 0.0)
