"""Share of the traced window in which a device ran a collective
(all-gather, reduce-scatter, all-reduce, ...) and nothing else: communication
the schedule did not hide, mean over the devices (benchmark/tracing.py);
0.0 when the trace holds no collective op."""

from benchmark import tracing

DECLARATION = {"unit": "%", "better": "lower", "source": "device_trace",
               "layer": "train step", "moves": "train_tokens_per_s_chip"}


def read(run: dict):
    trace = run.get("trace")
    if trace is None or not trace["devices"]:
        return None
    return 100.0 * tracing.exposed_collective_s(trace) \
        / tracing.traced_window_s(trace)
