"""Share of the traced stretch the engine's scheduler thread spent on work
of its own: the stretch less ``engine.fetch`` (blocked on the device) and
``engine.idle`` (nothing to do), on the thread that holds
``engine.decode_dispatch`` (benchmark/hostspans.py::busy_share). 0.0 for a
trace in which the scheduler left no span. Declared in the batch cell
alone: there a dispatch is 26 ms and a dispatch call returns at once; in
the chat cell a dispatch call can sit 0.3-1.2 s behind the round in flight,
which a span cannot tell from work (PERF.md, Open questions)."""

from benchmark import hostspans

DECLARATION = {"unit": "%", "better": "lower", "source": "program_span",
               "layer": "engine scheduler", "moves": "serve_tokens_per_s"}


def read(run: dict):
    spans = run.get("host_spans")
    if spans is None:
        return None
    return hostspans.busy_share(
        hostspans.thread_with(spans, hostspans.ENGINE_THREAD),
        hostspans.ENGINE_BLOCKED)
