"""Share of the window the trainer's loop waited for its next batch
(``stager.get``): the difference of ``Trainer.counters()``'
``stage_wait_sum_s`` over the window between the first and the last sync
point, in percent; 0.0 when the loop never waited."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "trainer loop", "moves": "train_tokens_per_s_chip"}


def read(run: dict):
    d = delta(run, "trainer", "stage_wait_sum_s")
    if d is None:
        return None
    return 100.0 * d[0] / run["window_s"]
