"""How late the load generator sent: 95th percentile over all requests of
(instant sent - instant due). A starved generator offers less load than the
cell says, and a fast TTFT then says nothing; 0.0 for a run with no request."""

from benchmark.stats import percentile_or

DECLARATION = {"unit": "ms", "better": "lower", "source": "host_clock",
               "layer": "load generator", "moves": "itl_p95_ms"}


def read(run: dict):
    gen = run.get("loadgen")
    if gen is None:
        return None
    return percentile_or(gen["late_ms"], 95, 0.0)
