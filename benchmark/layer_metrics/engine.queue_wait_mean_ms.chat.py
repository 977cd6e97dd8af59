"""Mean wait between a request's arrival at the engine and its admission,
over the admissions inside the window: the difference of the running sum over
the difference of the running count of ``EngineMetrics``' queue-delay
histogram; 0.0 when nothing was admitted in the window."""

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_counter",
               "layer": "engine scheduler", "moves": "itl_p95_ms"}


def read(run: dict):
    a, b = run.get("engine_before"), run.get("engine_after")
    if a is None or b is None:
        return None
    n = b["queue_delay_n"] - a["queue_delay_n"]
    if n <= 0:
        return 0.0
    return (b["queue_delay_sum_s"] - a["queue_delay_sum_s"]) / n * 1e3
