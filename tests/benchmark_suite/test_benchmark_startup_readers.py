"""The readers of what a run's ``setup_s`` went to
(benchmark/startup_readers.py, ISSUE 53): the arithmetic each describes on
the snapshot taken as the window opens, None where the run has no snapshot
or the snapshot lacks the keys, and the ONE reading that is true of a
program without the start-up clock too, which is why it alone is declared
in the PR that brings the clock (``start.unattributed_s.*``: a metric
declared for a cell the benchmark already has is read on the PARENT commit
as well, and ``build_last_line`` raises for a declared metric without a
value: test_benchmark_phase_readers.py says it at length).

Beside them the eight readers of the state sync a round that ISSUE 37
defined and tested there and no PR had declared, although every parent since
PR 38 has their keys.
"""

import pytest

from benchmark import manifest as mf
from benchmark import startup_readers as sr

MANIFEST = mf.load_manifest()
CELLS = {
    "chat": ("mistral-7b.chat-open", "itl_p95_ms"),
    "batch": ("mixtral-8x7b.batch-longprompt", "serve_tokens_per_s"),
    "train": ("mistral-7b-fsdp4.pretrain-4k", "train_tokens_per_s_chip"),
    "longctx": ("glm-4.7-flash.batch-longcontext", "serve_tokens_per_s"),
    "longanswer": ("lfm2-24b-a2b.batch-longanswer", "serve_tokens_per_s"),
    "mixedlength": ("k-exaone-236b-a23b.batch-mixedlength",
                    "serve_tokens_per_s"),
    "longdoc": ("solar-open2-250b.batch-longdoc", "serve_tokens_per_s"),
    "reasoning": ("phi-4-mini-flash.batch-reasoning", "serve_tokens_per_s"),
    "assistant": ("falcon-h1-34b.batch-assistant", "serve_tokens_per_s"),
}
UNATTRIBUTED = [f"start.unattributed_s.{cell}" for cell in CELLS]
SYNC_MS = [f"engine.sync_state_ms_per_round.{cell}"
           for cell in ("chat", "batch", "longctx", "longanswer",
                        "assistant")]
SYNCS = [f"engine.state_syncs_per_round.{cell}"
         for cell in ("batch", "longanswer", "assistant")]

# The snapshot of a built engine beside its server, as the window opens:
# 5.6 s of constructor, and a process that compiled 0.5 s, retrieved for
# 6.0 s (inside its 6.5 s of backend-compile events) and traced and lowered
# for 4.25 s.
ENGINE = {"decode_rounds": 12, "start_place_sum_s": 1.5,
          "start_pool_sum_s": 0.25, "start_relay_sum_s": 0.75,
          "start_warm_sum_s": 3.0, "start_other_sum_s": 0.125,
          "compile_backend_sum_s": 6.5, "compile_backend_n": 41,
          "compile_retrieval_sum_s": 6.0, "compile_trace_lower_sum_s": 4.25,
          "compile_cache_hits": 38, "compile_cache_misses": 3}
SERVER = {"stream_chunks_n": 40, "first_byte_overhead_sum_s": 0.5}
TRAINER = {"stage_wait_sum_s": 0.5, "start_build_sum_s": 3.0,
           "start_resume_sum_s": 0.0, "start_first_step_sum_s": 9.5,
           "compile_backend_sum_s": 8.0, "compile_backend_n": 30,
           "compile_retrieval_sum_s": 0.0,
           "compile_trace_lower_sum_s": 2.0, "compile_cache_hits": 0,
           "compile_cache_misses": 30}
# What a program WITHOUT the clock holds (the parent of the PR that brings
# it): counters, and none of the start or compile keys.
PARENT_ENGINE = {"decode_rounds": 12, "sched_sync_state_sum_s": 0.25,
                 "state_slot_syncs": 3, "state_row_syncs": 4}
PARENT_TRAINER = {"stage_wait_sum_s": 0.5}


def serving_run(engine=ENGINE, server=SERVER, setup_s=22.0):
    return {"kind": "closed_loop", "window_s": 51.0,
            "counters_before": {"engine": dict(engine),
                                "server": dict(server)},
            "counters_after": {"engine": dict(engine),
                               "server": dict(server)},
            "values": {"setup_s": setup_s, "serve_tokens_per_s": 1.0}}


def training_run(trainer=TRAINER, setup_s=63.0):
    return {"kind": "train_steps", "window_s": 51.0,
            "counters_before": {"trainer": dict(trainer)},
            "counters_after": {"trainer": dict(trainer)},
            "values": {"setup_s": setup_s, "train_tokens_per_s_chip": 1.0}}


# reader -> (a serving run's number, a training run's number)
READERS = {
    "program_start_s": (sr.program_start_s, 5.625, 12.5),
    "unattributed_s": (sr.unattributed_s, 22.0 - 5.625, 63.0 - 12.5),
    "compile_s": (sr.compile_s, 10.75, 10.0),      # retrieval is IN backend
    "cache_misses": (sr.cache_misses, 3.0, 30.0),
    "warm_s": (sr.warm_s, 3.0, None),              # a trainer warms nothing
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_snapshot_taken_as_the_window_opens(name):
    read, serving, training = READERS[name]
    assert read(serving_run()) == pytest.approx(serving)
    value = read(training_run())
    assert value is None if training is None \
        else value == pytest.approx(training)
    assert isinstance(read(serving_run()), float)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_program_without_the_clock(name):
    """The snapshot is there and lacks the keys: all of ``setup_s`` is
    unattributed, which is true; every other reader has nothing to read."""
    read = READERS[name][0]
    for run, setup_s in ((serving_run(PARENT_ENGINE), 22.0),
                         (training_run(PARENT_TRAINER), 63.0)):
        value = read(run)
        if name == "unattributed_s":
            assert value == setup_s and isinstance(value, float)
        else:
            assert value is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_nothing_where_the_run_has_no_snapshot(name):
    read = READERS[name][0]
    assert read({"window_s": 1.0}) is None
    assert read({**serving_run(), "counters_before": None}) is None
    assert read({"window_s": 1.0, "values": {"setup_s": 5.0}}) is None


def test_unattributed_needs_the_harness_clock_and_takes_the_sums_it_finds():
    run = serving_run()
    assert sr.unattributed_s({**run, "values": {}}) is None
    assert sr.unattributed_s({k: v for k, v in run.items()
                              if k != "values"}) is None
    # it reads the snapshot BEFORE the window: the other one is not its
    assert sr.unattributed_s({**run, "counters_after": None}) \
        == pytest.approx(22.0 - 5.625)
    # a later phase's sum would be taken with no edit here; a ``start_*``
    # key that is no sum of seconds (a count) is not
    more = {**ENGINE, "start_fetch_sum_s": 1.0, "start_programs_n": 9}
    assert sr.unattributed_s(serving_run(more)) == pytest.approx(
        22.0 - 6.625)
    assert sr.program_start_s(serving_run(more)) == pytest.approx(6.625)


def test_a_retrieval_is_not_counted_beside_the_backend_seconds_it_is_in():
    run = serving_run({**ENGINE, "compile_retrieval_sum_s": 0.0})
    assert sr.compile_s(run) == sr.compile_s(serving_run())
    lacking = {k: v for k, v in ENGINE.items()
               if k != "compile_trace_lower_sum_s"}
    assert sr.compile_s(serving_run(lacking)) is None


@pytest.mark.parametrize("name", UNATTRIBUTED)
def test_the_nine_declared_read_on_the_parent_commits_program(name):
    """What makes them safe to declare in the PR that brings the clock."""
    cell, _ = CELLS[name.rpartition(".")[2]]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    mod = mf.load_layer_metric(name)
    assert mod.DECLARATION == {
        "unit": "s", "better": "lower", "source": "program_counter",
        "layer": "start-up", "moves": "setup_s"}
    assert {k: entry[k] for k in mod.DECLARATION} == mod.DECLARATION
    assert entry["workloads"] == [cell]
    train = name.endswith(".train")
    parent = training_run(PARENT_TRAINER) if train \
        else serving_run(PARENT_ENGINE)
    assert mod.read(parent) == (63.0 if train else 22.0)
    ours = training_run() if train else serving_run()
    assert mod.read(ours) < ours["values"]["setup_s"]
    assert mod.read({"window_s": 1.0}) is None


@pytest.mark.parametrize("name", SYNC_MS + SYNCS)
def test_the_eight_readers_of_the_state_sync_a_round(name):
    stem, _, short = name.rpartition(".")
    cell, moves = CELLS[short]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    mod = mf.load_layer_metric(name)
    unit = "ms" if stem == "engine.sync_state_ms_per_round" else "count"
    assert mod.DECLARATION == {
        "unit": unit, "better": "lower", "source": "program_counter",
        "layer": "engine scheduler", "moves": moves}
    assert {k: entry[k] for k in mod.DECLARATION} == mod.DECLARATION
    assert entry["workloads"] == [cell]
    assert moves in mf.declared(MANIFEST, cell, "end_to_end")
    # on the keys every parent since PR 38 has: 2000 rounds in the window
    after = {"decode_rounds": 2012, "sched_sync_state_sum_s": 1.75,
             "state_slot_syncs": 803, "state_row_syncs": 804}
    run = {"window_s": 51.0,
           "counters_before": {"engine": dict(PARENT_ENGINE)},
           "counters_after": {"engine": after}}
    assert mod.read(run) == pytest.approx(0.75 if unit == "ms" else 0.8)
    still = {**run, "counters_after": run["counters_before"]}
    assert mod.read(still) == 0.0
    assert mod.read({"window_s": 1.0}) is None
    assert mod.read({**run, "counters_before": {"engine": {}}}) is None


def test_each_entry_is_listed_once_for_its_one_cell():
    """Not WHERE in the list, and not that they are the only ones: a later
    PR appends the by-phase readers behind them."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert all(names.count(n) == 1 for n in UNATTRIBUTED + SYNC_MS + SYNCS)
    for short, (cell, _) in CELLS.items():
        assert f"start.unattributed_s.{short}" \
            in mf.declared(MANIFEST, cell, "per_layer")
    # per-layer metrics under the one end-to-end metric every cell reports
    assert set(UNATTRIBUTED) <= {m["name"] for m in MANIFEST["per_layer"]
                                 if m["moves"] == "setup_s"}
