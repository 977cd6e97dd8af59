"""Share of the traced stretch the trainer's loop thread spent on work of
its own: the stretch less ``train.sync`` (blocked on the device at a log
point) and ``train.stage_wait`` (waiting for input), on the thread that
holds ``train.dispatch`` (benchmark/hostspans.py::busy_share). 0.0 for a
trace in which the loop left no span."""

from benchmark import hostspans

DECLARATION = {"unit": "%", "better": "lower", "source": "program_span",
               "layer": "trainer loop", "moves": "train_tokens_per_s_chip"}


def read(run: dict):
    spans = run.get("host_spans")
    if spans is None:
        return None
    return hostspans.busy_share(
        hostspans.thread_with(spans, hostspans.TRAINER_THREAD),
        hostspans.TRAINER_BLOCKED)
