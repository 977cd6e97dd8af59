"""PR 23's failure, pinned: a per-layer metric is a total function of a run
(a window with no sample of its source gives its stated number, never a
missing key), and the last line cannot be printed without a metric the
manifest declares."""

import pytest

from benchmark import manifest as mf
from test_benchmark_program_readers import NEW, quiet_run

MANIFEST = mf.load_manifest()
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
ZERO = {"queue_delay_sum_s": 0.0, "queue_delay_n": 0, "host_gap_sum_s": 0.0,
        "host_gap_n": 0, "preemptions": 0, "shed": 0, "completed": 0,
        "decode_rounds": 0}
# A trace with a device in it and nothing the readers look for: no decode
# dispatch, no chunk prefill, no collective.
QUIET_TRACE = {"window_s": 1.0, "other_planes": [], "devices": [{
    "name": "/device:TPU:0", "lines": {},
    "modules": [["jit_other(1)", 0.0, 0.5]],
    "ops": [["fusion.1", 0.0, 0.5]]}]}
CONFIG = mf.load_config(MANIFEST, MANIFEST["configs"][0]["name"])


def empty_serving_run():
    """A served window in which nothing was sampled: no request was sent,
    the engine's counters did not move, the trace holds no program of
    interest; the program's own counters stood still and its scheduler left
    no span."""
    return {**quiet_run("any.chat"),
            "kind": "open_loop", "window_s": 40.0, "config": CONFIG,
            "engine_before": dict(ZERO), "engine_after": dict(ZERO),
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": []},
            "trace": QUIET_TRACE, "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 1e12}}


def empty_training_run():
    return {**quiet_run("any.train"),
            "kind": "train_steps", "window_s": 40.0, "config": CONFIG,
            "trace": QUIET_TRACE, "peaks": PEAKS,
            "train": {"tokens_per_s_chip": 5000.0, "seq_len": 4096,
                      "steps": 10, "median_step_s": 1.0}}


STATED = {
    "loadgen.late_p95_ms.chat": 0.0,
    "client.ttft_p95_ms.chat": 0.0,
    "engine.queue_wait_mean_ms.chat": 0.0,
    "engine.host_gap_share.chat": 0.0,
    "engine.host_gap_share.batch": 0.0,
    "kv.preemptions.batch": 0.0,
    "step.decode_weight_bw_share.chat": 0.0,
    "step.prefill_mfu.batch": 0.0,
    "step.collective_exposed_share.train": 0.0,
    # the readers of the program's own counters and spans
    # (test_benchmark_program_readers.py has their sampled cases)
    **{name: stated for name, (stated, _) in NEW.items()},
}


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_reader_is_total_on_a_window_without_samples(name):
    run = (empty_training_run() if name.endswith(".train")
           else empty_serving_run())
    value = mf.load_layer_metric(name).read(run)
    assert isinstance(value, float), f"{name} returned {value!r}"
    if name in STATED:
        assert value == STATED[name]
    else:                       # the MFU has no zero-sample case: > 0
        assert value > 0.0


@pytest.mark.parametrize("name", [m["name"] for m in MANIFEST["per_layer"]])
def test_reader_of_another_kind_of_run_returns_nothing(name):
    """Where the source does not exist at all, a reader returns None and
    does not raise: the harness then leaves it out, and the last line's
    check decides whether that is allowed."""
    assert mf.load_layer_metric(name).read({"window_s": 1.0}) is None


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_last_line_raises_before_printing_without_a_declared_metric(
        cell, trace):
    want = mf.declared(MANIFEST, cell, "per_layer" if trace else "end_to_end")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0}
    for missing in want:
        values = {n: 1.0 for n in want if n != missing}
        with pytest.raises(mf.MalformedResult, match=missing.replace(
                ".", r"\.")):
            mf.build_last_line(MANIFEST, cell, trace, correct=True,
                               attempted=1, failed=0, values=values,
                               device=device)


def test_last_line_refuses_what_is_not_a_finite_number_and_a_bad_device():
    cell = MANIFEST["workloads"][0]["name"]
    want = mf.declared(MANIFEST, cell, "per_layer")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0}
    for bad in (float("nan"), float("inf"), None, "1.0", True):
        values = {n: 1.0 for n in want}
        values[next(iter(want))] = bad
        with pytest.raises(mf.MalformedResult):
            mf.build_last_line(MANIFEST, cell, True, correct=True,
                               attempted=1, failed=0, values=values,
                               device=device)
    values = {n: 1.0 for n in want}
    for dev in ({**device, "busy_s": 0.0}, {**device, "busy_s": 2.0},
                {k: v for k, v in device.items() if k != "window_s"}):
        with pytest.raises(mf.MalformedResult):
            mf.build_last_line(MANIFEST, cell, True, correct=True,
                               attempted=1, failed=0, values=values,
                               device=dev)


def test_last_line_holds_the_contracts_keys_and_no_other():
    cell = MANIFEST["workloads"][0]["name"]
    want = mf.declared(MANIFEST, cell, "per_layer")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1, "busy_s": 0.5, "window_s": 1.0,
              "peaks": "dropped"}
    line = mf.build_last_line(
        MANIFEST, cell, True, correct=True, attempted=3, failed=0,
        values={**{n: 1.0 for n in want}, "undeclared": 2.0}, device=device,
        breakdown={"device_ops": [["a", 1.0]] * 12, "idle_gaps": [],
                   "other": []})
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert set(line["metrics"]) == set(want)
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) == 10
