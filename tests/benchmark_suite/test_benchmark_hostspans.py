"""The reductions of the program's host spans (benchmark/hostspans.py) on
hand-built plain forms, and on a small recording of a real trace kept
beside this file."""

import json
import os

import pytest

from benchmark import hostspans, tracing

HERE = os.path.dirname(os.path.abspath(__file__))

# One scheduler iteration and a half, hand-built: admit with two children,
# the state scatter, the dispatch, a fetch, an emit, and a stretch at 0.5 s
# that no span covers.
SCHED = [
    ["engine.reap", 0.00, 0.01, {}],
    ["engine.admit", 0.01, 0.19, {}],
    ["engine.prefill_dispatch", 0.02, 0.03, {"slot": 1, "pos": 0}],
    ["engine.sample_first", 0.06, 0.12, {"n": 1}],
    ["engine.fetch", 0.07, 0.10, {"first": 1}],
    ["engine.sync_state", 0.20, 0.05, {}],
    ["engine.decode_dispatch", 0.25, 0.01, {"round": 1, "k_steps": 8}],
    ["engine.fetch", 0.26, 0.20, {"round": 0}],
    ["engine.emit", 0.46, 0.02, {"round": 0}],
    ["engine.idle", 0.60, 0.40, {}],
]
OTHER = [["kftpu.trace_anchor", 0.0, 1e-6, {"wall_ns": 5, "mono_ns": 7}],
         ["server.stream", 0.1, 0.2, {}]]


def trace_with_busy(*busy):
    return {"window_s": 1.0, "devices": [{
        "name": "/device:TPU:0", "lines": {}, "modules": [],
        "ops": [["op", s, e - s] for s, e in busy]}]}


def test_the_loop_thread_is_found_by_its_spans_never_by_a_name():
    assert hostspans.thread_with([OTHER, SCHED],
                                 hostspans.ENGINE_THREAD) is SCHED
    assert hostspans.loop_thread([OTHER, SCHED]) is SCHED
    assert hostspans.thread_with([OTHER], hostspans.ENGINE_THREAD) is None
    assert hostspans.loop_thread(None) is None
    trainer = [["train.dispatch", 0.0, 0.1, {"step": 1}]]
    assert hostspans.loop_thread([OTHER, trainer]) is trainer
    assert hostspans.anchor([SCHED, OTHER]) == {
        "wall_ns": 5, "mono_ns": 7, "trace_s": 0.0}
    assert hostspans.anchor([SCHED]) is None


def seconds_by_phase(thread) -> dict:
    total: dict = {}
    for s0, s1, name in hostspans.innermost_segments(thread):
        total[name] = total.get(name, 0.0) + (s1 - s0)
    return total


def test_innermost_segments_take_a_childs_piece_out_of_its_parent():
    segs = hostspans.innermost_segments(SCHED)
    # disjoint, in order, and together exactly the union of the spans
    assert all(a[1] <= b[0] + 1e-12 for a, b in zip(segs, segs[1:]))
    assert sum(e - s for s, e, _ in segs) == pytest.approx(
        tracing.measure((s, s + d) for _, s, d, _ in SCHED))
    by_name = seconds_by_phase(SCHED)
    assert by_name["engine.admit"] == pytest.approx(0.19 - 0.03 - 0.12)
    assert by_name["engine.sample_first"] == pytest.approx(0.02)
    assert by_name["engine.fetch"] == pytest.approx(0.30)
    assert by_name["engine.idle"] == pytest.approx(0.40)
    assert "host:untraced" not in by_name
    at = {round(s, 3): n for s, _, n in segs}
    assert at[0.07] == "engine.fetch" and at[0.17] == "engine.sample_first"
    assert at[0.18] == "engine.admit"


def test_busy_share_is_the_window_less_what_blocks():
    # window 0.0 .. 1.0; blocked: the two fetches (0.30) and idle (0.40);
    # the uncovered 0.48 .. 0.60 counts as the host's own
    assert hostspans.busy_share(SCHED, hostspans.ENGINE_BLOCKED) \
        == pytest.approx(30.0)
    assert hostspans.busy_share([["engine.idle", 0.0, 2.0, {}]],
                                hostspans.ENGINE_BLOCKED) == 0.0
    assert hostspans.busy_share([["engine.admit", 0.0, 2.0, {}]],
                                hostspans.ENGINE_BLOCKED) == 100.0
    assert hostspans.busy_share(None, hostspans.ENGINE_BLOCKED) == 0.0
    assert hostspans.busy_share([], hostspans.ENGINE_BLOCKED) == 0.0


def test_idle_is_split_over_the_innermost_host_span_and_the_rest_untraced():
    # busy but for 0.10-0.22 (admit's children and the scatter), 0.50-0.56
    # (no span at all) and 0.70-0.75 (the loop idle)
    trace = trace_with_busy((0.0, 0.10), (0.22, 0.50), (0.56, 0.70),
                            (0.75, 1.0))
    got = dict(hostspans.idle_by_host_phase(trace, SCHED))
    assert got == pytest.approx({
        "host:engine.fetch": 0.07,             # 0.10 .. 0.17, inside sampler
        "host:engine.sample_first": 0.01,      # 0.17 .. 0.18
        "host:engine.admit": 0.02,             # 0.18 .. 0.20
        "host:engine.sync_state": 0.02,        # 0.20 .. 0.22
        "host:untraced": 0.06,                 # 0.50 .. 0.56
        "host:engine.idle": 0.05})
    assert sum(got.values()) == pytest.approx(
        tracing.measure(hostspans.device_idle(trace)))
    ranked = hostspans.idle_by_host_phase(trace, SCHED, n=2)
    assert [n for n, _ in ranked] == ["host:engine.fetch", "host:untraced"]
    # no thread: everything is untraced; no device: nothing to split
    assert hostspans.idle_by_host_phase(trace, None) == [
        ["host:untraced", pytest.approx(0.23)]]
    assert hostspans.idle_by_host_phase(
        {"window_s": 1.0, "devices": []}, SCHED) == []


def test_the_program_span_filter_keeps_spans_and_drops_the_runtimes_events():
    keep = ["engine.admit", "train.stage_wait", "kftpu.trace_anchor",
            "server.first_byte"]
    drop = ["PjitFunction(<lambda>)", "PjRtCpuExecutable::Execute",
            "copy.24", "slice_bitcast_fusion.1", "dot_general.1", "train",
            "XlaLinearize", "tpu::System::Execute=>Done", ""]
    assert all(hostspans.PROGRAM_SPAN.match(n) for n in keep)
    assert not any(hostspans.PROGRAM_SPAN.match(n) for n in drop)


# -- a recording of a real trace ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_host_spans.json")) as f:
        return json.load(f)["trace"]


def test_recorded_trace_names_its_idle_by_the_schedulers_phase(recorded):
    """The first 1.2 s of a chat-cell trace from the chip: two chunk
    prefills, then the scheduler scatters thirteen slots' state one by one
    (``engine.sync_state``, 14 ms, the device idle but for a microsecond's
    scatter every 0.7 ms) before it dispatches a 32-step decode round and
    blocks in its fetch."""
    assert hostspans.anchor(recorded["host_spans"])["mono_ns"] > 0
    loop = hostspans.loop_thread(recorded["host_spans"])
    assert loop is hostspans.thread_with(recorded["host_spans"],
                                         hostspans.ENGINE_THREAD)
    assert not [s for s in loop if s[0] == hostspans.ANCHOR]
    idle = hostspans.device_idle(recorded)
    assert tracing.measure(idle) == pytest.approx(0.0176808, abs=1e-6)
    got = dict(hostspans.idle_by_host_phase(recorded, loop))
    assert sum(got.values()) == pytest.approx(tracing.measure(idle))
    assert got["host:engine.sync_state"] == pytest.approx(0.0141219,
                                                          abs=1e-6)
    # the profiler saw the device from 0.0 s and the scheduler's first
    # whole span only from 0.057 s: what lies before is untraced
    assert got["host:untraced"] == pytest.approx(0.0034494, abs=1e-6)
    assert got["host:engine.sync_state"] / sum(got.values()) > 0.79
    phases = seconds_by_phase(loop)
    assert phases["engine.fetch"] == pytest.approx(1.2173501, abs=1e-6)
    assert hostspans.busy_share(loop, hostspans.ENGINE_BLOCKED) \
        == pytest.approx(2.8694, abs=1e-3)
    dispatch = [s for s in loop if s[0] == "engine.decode_dispatch"]
    fetch = [s for s in loop if s[0] == "engine.fetch"]
    assert [s[3]["round"] for s in dispatch] == [37, 38]
    assert [s[3]["round"] for s in fetch] == [36, 37]   # one round behind
    assert dispatch[0][3] == {"round": 37, "k_steps": 32, "live": 26}
