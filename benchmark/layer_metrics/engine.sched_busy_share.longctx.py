"""Share of the traced stretch the engine's scheduler thread spent on work
of its own in the long-context cell: what ``engine.sched_busy_share.batch``
reads (benchmark/hostspans.py::busy_share: the stretch less ``engine.fetch``
and ``engine.idle``, on the thread that holds ``engine.decode_dispatch``),
declared here because this cell has that cell's shape: a closed loop, one
step a dispatch, and a dispatch call that returns at once. 0.0 for a trace
in which the scheduler left no span."""

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
