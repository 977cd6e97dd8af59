"""Share of the traced stretch the engine's scheduler thread spent on work
of its own in the long-answer cell: what ``engine.sched_busy_share.batch``
reads (benchmark/hostspans.py::busy_share: the stretch less ``engine.fetch``
and ``engine.idle``, on the thread that holds ``engine.decode_dispatch``).
With 64 streams a round hands 64 tokens a step to 64 handler threads, so the
host's share is larger here than in the 16-slot cells. 0.0 for a trace in
which the scheduler left no span."""

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
