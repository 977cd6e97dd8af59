"""The readers of the program's own counters and spans
(benchmark/program_readers.py and the metric files built on it) are total:
a window in which nothing was sampled gives the stated number (``NEW`` and
``quiet_run`` here feed test_benchmark_layer_metrics_total.py, which asks
that of every declared metric), a sampled one the arithmetic the metric's
file describes, and a run that has no such snapshot or no such spans
(another kind of run, a program without them) gives None and does not
raise."""

import pytest

from benchmark import manifest as mf

MANIFEST = mf.load_manifest()

ENGINE0 = {"slots": 32, "queue_delay_sum_s": 0.0, "queue_delay_n": 0,
           "prefill_phase_sum_s": 0.0, "prefill_phase_n": 0,
           "decode_steps_dispatched": 0, "decode_tokens_emitted": 0}
SERVER0 = {"first_byte_overhead_sum_s": 0.0, "first_byte_overhead_n": 0}
TRAINER0 = {"stage_wait_sum_s": 0.0}

# name -> (number for a window without samples, a sampled run's number)
NEW = {
    "server.first_byte_overhead_mean_ms.chat": (0.0, 1.5),
    "engine.prefill_phase_mean_ms.chat": (0.0, 900.0),
    "engine.decode_occupancy.chat": (0.0, 50.0),
    "engine.decode_occupancy.batch": (0.0, 50.0),
    "engine.sched_busy_share.batch": (0.0, 10.0),
    "trainer.input_wait_share.train": (0.0, 0.5),
    "trainer.host_busy_share.train": (0.0, 2.0),
}


def quiet_run(name: str) -> dict:
    """The program has the counters and the control, and nothing moved:
    no request, no step, and the loop's thread left no span in the trace."""
    if name.endswith(".train"):
        return {"kind": "train_steps", "window_s": 40.0,
                "counters_before": {"trainer": dict(TRAINER0)},
                "counters_after": {"trainer": dict(TRAINER0)},
                "host_spans": [[["kftpu.trace_anchor", 0.0, 1e-6, {}]]]}
    parts = {"engine": dict(ENGINE0), "server": dict(SERVER0)}
    return {"kind": "open_loop", "window_s": 40.0,
            "counters_before": parts,
            "counters_after": {k: dict(v) for k, v in parts.items()},
            "host_spans": [[["kftpu.trace_anchor", 0.0, 1e-6, {}]]]}


def sampled_run(name: str) -> dict:
    run = quiet_run(name)
    if name.endswith(".train"):
        run["counters_after"]["trainer"].update(
            stage_wait_sum_s=0.2)
        # one step of 1 s: 0.97 s blocked in the sync, 0.01 s waiting for
        # input, 0.02 s the loop's own
        run["host_spans"].append([
            ["train", 0.0, 1.0, {"step_num": 7}],
            ["train.stage_wait", 0.0, 0.01, {"step": 7}],
            ["train.dispatch", 0.01, 0.005, {"step": 7}],
            ["train.sync", 0.02, 0.97, {"step": 7}],
            ["train.log", 0.99, 0.01, {"step": 7}]])
        return run
    run["counters_after"]["engine"].update(
        prefill_phase_sum_s=9.0, prefill_phase_n=10,
        decode_steps_dispatched=100, decode_tokens_emitted=1600)
    run["counters_after"]["server"].update(
        first_byte_overhead_sum_s=0.015, first_byte_overhead_n=10)
    # 1 s of the scheduler: 0.7 s in the round's fetch, 0.1 s in the first
    # token's fetch inside sample_first, 0.1 s idle, 0.1 s its own
    run["host_spans"].append([
        ["engine.admit", 0.0, 0.15, {}],
        ["engine.sample_first", 0.02, 0.12, {"n": 1}],
        ["engine.fetch", 0.03, 0.1, {"first": 1}],
        ["engine.decode_dispatch", 0.16, 0.01, {"round": 3}],
        ["engine.fetch", 0.18, 0.7, {"round": 2}],
        ["engine.emit", 0.88, 0.02, {"round": 2}],
        ["engine.idle", 0.9, 0.1, {}]])
    return run


def test_every_new_metric_is_declared_and_has_its_reader():
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert set(NEW) <= set(declared)
    # new entries stand at the end of the list, in one block
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(names[-len(NEW):]) == set(NEW)
    for name in NEW:
        assert declared[name]["source"] in ("program_counter",
                                            "program_span")
        assert mf.load_layer_metric(name).DECLARATION["source"] \
            == declared[name]["source"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_sampled_window(name):
    value = mf.load_layer_metric(name).read(sampled_run(name))
    assert value == pytest.approx(NEW[name][1])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_returns_nothing_where_the_program_has_no_source(name):
    """Another kind of run; a program without ``counters()`` (the harness
    then hands None); one without the control (a trace with no anchor has
    no plain form); a snapshot that lacks the key."""
    read = mf.load_layer_metric(name).read
    assert read({"window_s": 1.0}) is None
    run = quiet_run(name)
    run.update(counters_before=None, counters_after=None, host_spans=None)
    assert read(run) is None
    run = quiet_run(name)
    run["host_spans"] = None
    for part in run["counters_after"].values():
        part.clear()
    assert read(run) is None


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_a_trace_2_line_holds_both_kinds_side_by_side(cell):
    e2e = mf.declared(MANIFEST, cell, "end_to_end")
    layer = mf.declared(MANIFEST, cell, "per_layer")
    want = mf.declared_for_run(MANIFEST, cell, 2)
    assert set(want) == set(e2e) | set(layer) and not set(e2e) & set(layer)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0}
    line = mf.build_last_line(
        MANIFEST, cell, 2, correct=True, attempted=1, failed=0,
        values={n: 1.0 for n in want}, device=device,
        breakdown={"device_ops": [["a", 1.0]],
                   "idle_gaps": [["host:engine.admit", 0.5]]})
    assert set(line["metrics"]) == set(want)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["breakdown"]["idle_gaps"] == [["host:engine.admit", 0.5]]
    for missing in want:
        with pytest.raises(mf.MalformedResult):
            mf.build_last_line(
                MANIFEST, cell, 2, correct=True, attempted=1, failed=0,
                values={n: 1.0 for n in want if n != missing}, device=device)
    # the other two kinds of run print what they printed
    assert set(mf.declared_for_run(MANIFEST, cell, 0)) == set(e2e)
    assert set(mf.declared_for_run(MANIFEST, cell, 1)) == set(layer)
