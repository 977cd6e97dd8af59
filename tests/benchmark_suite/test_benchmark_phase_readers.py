"""The readers of the host's time a token over the window
(benchmark/phase_readers.py, ISSUE 37): the arithmetic each describes on a
sampled pair of snapshots, the stated 0.0 where the divisor stood still, None
where a snapshot lacks a key; and the two metrics declared on them.

A reader declared for a cell the benchmark already had is read on the PARENT
commit too (the driver lays a PR's benchmark files over the parent's checkout
for the traced runs), and ``build_last_line`` raises for a declared metric
without a value: so a metric declared here has to find every key it takes in
the parent's ``counters()``. ``PARENT_ENGINE_KEYS`` is that dict's keys at
PR 36; the definitions that need a later key are in the module, tested here,
printed by ``scripts/round_pacing_chip.py``, and declared by a later PR."""

import pytest

from benchmark import manifest as mf
from benchmark import phase_readers as pr

MANIFEST = mf.load_manifest()
DECLARED = {"engine.sched_busy_share_window.chat": "itl_p95_ms",
            "engine.sched_busy_share_window.longanswer":
                "serve_tokens_per_s"}
PARENT_ENGINE_KEYS = {
    "slots", "queue_delay_sum_s", "queue_delay_n", "host_gap_sum_s",
    "host_gap_n", "preemptions", "requests_shed", "requests_completed",
    "tokens_generated", "decode_rounds", "first_token_fetches",
    "prefill_phase_sum_s", "prefill_phase_n", "decode_steps_dispatched",
    "decode_tokens_emitted", "decode_context_tokens", "decode_rounds_at_cap",
    "sched_host_busy_sum_s", "prefill_programs_dispatched",
    "prefill_chunks_dispatched", "prefill_tokens_dispatched",
    "prefill_passes", "prefill_chunks_deferred", "kv_bytes_per_token",
    "kv_pool_bytes", "state_pool_bytes", "state_tail_writes"}
PARENT_SERVER_KEYS = {"first_byte_overhead_sum_s", "first_byte_overhead_n"}

ENGINE_A = {"decode_rounds": 1000, "prefill_programs_dispatched": 100,
            "sched_host_busy_sum_s": 10.0, "sched_sync_state_sum_s": 1.0,
            "sched_emit_sum_s": 2.0, "sched_prefill_dispatch_sum_s": 0.5,
            "state_slot_syncs": 300, "state_row_syncs": 200}
ENGINE_B = {"decode_rounds": 3000, "prefill_programs_dispatched": 500,
            "sched_host_busy_sum_s": 22.75, "sched_sync_state_sum_s": 2.5,
            "sched_emit_sum_s": 9.0, "sched_prefill_dispatch_sum_s": 1.1,
            "state_slot_syncs": 1100, "state_row_syncs": 1000}
SERVER_A = {"stream_chunks_n": 10, "stream_write_sum_s": 1.0,
            "stream_wake_sum_s": 0.01, "stream_wake_n": 10,
            "stream_behind_n": 0}
SERVER_B = {"stream_chunks_n": 210010, "stream_write_sum_s": 62.2,
            "stream_wake_sum_s": 100.01, "stream_wake_n": 200010,
            "stream_behind_n": 10000}


def run_of(engine_a, engine_b, server_a, server_b, window_s=51.0):
    return {"window_s": window_s,
            "counters_before": {"engine": engine_a, "server": server_a},
            "counters_after": {"engine": engine_b, "server": server_b}}


SAMPLED = run_of(ENGINE_A, ENGINE_B, SERVER_A, SERVER_B)
STOOD_STILL = run_of(ENGINE_A, ENGINE_A, SERVER_A, SERVER_A)

# reader -> (a sampled window's number, a window in which nothing moved)
READERS = {
    "sched_busy_share_window": (
        pr.sched_busy_share_window, 25.0, 0.0),          # 12.75 s of 51
    "sync_state_ms_per_round": (
        lambda run: pr.phase_ms_per_round(run, "sync_state"), 0.75, 0.0),
    "emit_ms_per_round": (
        lambda run: pr.phase_ms_per_round(run, "emit"), 3.5, 0.0),
    "prefill_dispatch_ms_per_program": (
        pr.prefill_dispatch_ms_per_program, 1.5, 0.0),   # 0.6 s over 400
    "state_syncs_per_round": (
        pr.state_syncs_per_round, 0.8, 0.0),             # 1600 over 2000
    "stream_write_share": (
        pr.stream_write_share, 120.0, 0.0),              # 61.2 s of 51
    "stream_wake_mean_ms": (
        pr.stream_wake_mean_ms, 0.5, 0.0),               # 100 s over 200000
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_sampled_window_and_on_one_that_stood_still(name):
    read, sampled, still = READERS[name]
    assert read(SAMPLED) == pytest.approx(sampled)
    value = read(STOOD_STILL)
    assert isinstance(value, float) and value == still


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_nothing_where_a_snapshot_lacks_a_key(name):
    read = READERS[name][0]
    assert read({"window_s": 51.0}) is None
    assert read({**SAMPLED, "counters_before": None}) is None
    part = "server" if name.startswith("stream") else "engine"
    for side in ("counters_before", "counters_after"):
        lacking = {**SAMPLED, side: {**SAMPLED[side], part: {}}}
        assert read(lacking) is None
    # the program as it stood before these counters: a part with only the
    # parent's keys is a snapshot that lacks the key, not an error
    parent = run_of(dict.fromkeys(PARENT_ENGINE_KEYS, 1),
                    dict.fromkeys(PARENT_ENGINE_KEYS, 2),
                    dict.fromkeys(PARENT_SERVER_KEYS, 1),
                    dict.fromkeys(PARENT_SERVER_KEYS, 2))
    value = read(parent)
    if name == "sched_busy_share_window":
        assert value == pytest.approx(100.0 / 51.0)
    else:
        assert value is None


def test_a_share_without_a_window_is_nothing_and_a_sum_of_keys_adds_them():
    assert pr.share_of_window({**SAMPLED, "window_s": 0.0}, "engine",
                              "sched_host_busy_sum_s") is None
    assert pr.per(SAMPLED, "engine", ("state_slot_syncs",),
                  "decode_rounds") == pytest.approx(0.4)
    assert pr.per(SAMPLED, "server", ("stream_behind_n",),
                  "stream_chunks_n") == pytest.approx(10000 / 210000)


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_the_declared_metrics_read_on_the_parent_commits_program(name):
    """What makes them safe to declare for a cell the parent runs."""
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    mod = mf.load_layer_metric(name)
    assert mod.DECLARATION == {
        "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "engine scheduler", "moves": DECLARED[name]}
    assert entry["moves"] == DECLARED[name] and len(entry["workloads"]) == 1
    parent = run_of({k: 1.0 for k in PARENT_ENGINE_KEYS},
                    {k: 3.55 for k in PARENT_ENGINE_KEYS},
                    dict.fromkeys(PARENT_SERVER_KEYS, 0),
                    dict.fromkeys(PARENT_SERVER_KEYS, 0))
    assert mod.read(parent) == pytest.approx(5.0)
    assert mod.read(SAMPLED) == pytest.approx(25.0)
    assert mod.read({"window_s": 1.0}) is None


def test_each_entry_is_listed_once_for_its_one_cell():
    """Not WHERE in the list: a later PR appends behind them."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert all(names.count(n) == 1 for n in DECLARED)
    for cell in ("mistral-7b.chat-open", "lfm2-24b-a2b.batch-longanswer"):
        mine = [n for n in mf.declared(MANIFEST, cell, "per_layer")
                if n in DECLARED]
        assert len(mine) == 1
