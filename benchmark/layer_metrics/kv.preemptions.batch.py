"""Recompute preemptions inside the window (a slot or a chunked prefill gave
its pages back and its request went round again): the difference of
``EngineMetrics.preemptions``; 0.0 when none happened."""

DECLARATION = {"unit": "count", "better": "lower",
               "source": "program_counter", "layer": "KV manager",
               "moves": "serve_tokens_per_s"}


def read(run: dict):
    a, b = run.get("engine_before"), run.get("engine_after")
    if a is None or b is None:
        return None
    return float(b["preemptions"] - a["preemptions"])
