"""The scheduler's time by phase, always on (ISSUE 37): every phase of the
engine's loop goes through one helper (``obs/profiler.py::PhaseClock``) that
adds the phase's EXCLUSIVE seconds to a running sum and opens the
``hot_span`` of the same name between the same two boundaries while a
capture is active. ``LLMEngine.counters()`` carries the sums, what the state
syncs sent, and ``ModelServer.counters()`` a token's way from its round to
the socket.
"""

import json
import threading
import time
import urllib.request

import jax
import pytest

from benchmark import hostspans
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.obs import profiler
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.server import STREAM_FOLD_CHUNKS, ModelServer
from test_obs_profiler import load_spans

PHASES = [name.rpartition(".")[2] for name in profiler.ENGINE_PHASES]
SUM_KEYS = [f"sched_{p}_sum_s" for p in PHASES + ["other"]]


def loop_seconds(c: dict) -> float:
    return sum(c[k] for k in SUM_KEYS)


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


@pytest.fixture(autouse=True)
def control_is_off():
    assert not profiler.active()
    yield
    profiler.stop()


# -- the clock by itself ----------------------------------------------------------

class Ticks:
    """A clock a test moves by hand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def test_a_phase_keeps_its_own_seconds_and_its_children_keep_theirs():
    t = Ticks()
    clock = profiler.PhaseClock(("a.outer", "a.inner", "a.leaf"), clock=t)
    assert clock.begin() and not clock.begin()
    t.now += 1.0                                    # under no phase
    with clock.phase("a.outer"):
        t.now += 2.0
        with clock.phase("a.inner"):
            t.now += 4.0
            with clock.phase("a.leaf"):
                t.now += 8.0
            t.now += 16.0
        t.now += 32.0
        with clock.phase("a.leaf"):
            t.now += 64.0
    t.now += 128.0
    clock.end()
    assert clock.snapshot() == {"a.outer": 34.0, "a.inner": 20.0,
                                "a.leaf": 72.0, "other": 129.0}
    assert sum(clock.sums) == t.now - 100.0         # every second, once
    assert clock.total("a.leaf") == 72.0


def test_outside_the_loop_a_phase_counts_and_the_time_between_does_not():
    t = Ticks()
    clock = profiler.PhaseClock(("a.x", "a.y"), clock=t)
    t.now += 5.0                                    # nobody's
    with clock.phase("a.x"):
        t.now += 1.0
        with clock.phase("a.y"):
            t.now += 2.0
        t.now += 3.0
    t.now += 7.0                                    # nobody's
    with clock.phase("a.x"):
        t.now += 1.0
    assert clock.snapshot() == {"a.x": 5.0, "a.y": 2.0, "other": 0.0}
    assert clock.begin()
    t.now += 2.0
    assert clock.tick() == t.now
    clock.end()
    assert clock.snapshot()["other"] == 2.0


def test_a_phase_may_nest_in_itself_and_an_error_passes_through():
    t = Ticks()
    clock = profiler.PhaseClock(("a.x",), clock=t)
    clock.begin()
    with pytest.raises(ValueError):
        with clock.phase("a.x"):
            t.now += 1.0
            with clock.phase("a.x"):
                t.now += 2.0
                raise ValueError("passes through")
    t.now += 4.0
    clock.end()
    assert clock.snapshot() == {"a.x": 3.0, "other": 4.0}


def test_off_capture_a_phase_allocates_no_span_and_hands_back_the_noop():
    clock = profiler.PhaseClock(profiler.ENGINE_PHASES)
    with clock.phase(profiler.ENGINE_EMIT,
                     profiler.active() and {"round": 3}) as span:
        assert span is profiler.NO_SPAN
        span.set_metadata(tokens=1)                 # nothing to write to
    assert clock.phase(profiler.ENGINE_EMIT) \
        is clock.phase(profiler.ENGINE_EMIT, None)  # one object a phase


# -- the engine's loop ------------------------------------------------------------

def make_engine(**kw):
    cfg = preset("tiny", vocab_size=512)
    spec = dict(max_batch_size=4, max_seq_len=128, chunked_prefill_tokens=32,
                paged=True, page_size=16, decode_steps=4,
                prefill_interleave_steps=2)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec),
                     params=init_decoder_params(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.stop()


def test_every_key_from_construction_on():
    eng = make_engine()
    c = eng.counters()
    assert len(PHASES) == 11
    for key in SUM_KEYS + ["sched_host_busy_sum_s"]:
        assert c[key] == 0.0, key
    for key in ("sched_iterations", "state_slot_syncs", "state_row_syncs",
                "state_sync_rounds", "state_sync_dispatches"):
        assert c[key] == 0, key
    assert not hasattr(eng, "_blocked") and not hasattr(eng, "_blocked_s")
    assert set(eng.sched_phase_seconds()) == set(PHASES) | {"other"}


def test_the_sums_add_up_to_the_loops_wall_time_driven_by_hand(engine):
    """A few hundred iterations with prefills, decode rounds and empty
    passes, each ``step`` timed from outside: the phase sums and
    ``sched_other_sum_s`` are that time, and ``sched_host_busy_sum_s`` is
    it less ``fetch`` less ``idle``."""
    before = engine.counters()
    reqs = [engine.submit(list(range(1, 40 + 3 * i)),
                          SamplingParams(max_new_tokens=30))
            for i in range(10)]
    outside, iterations = 0.0, 0
    while iterations < 300 or not all(r.done.is_set() for r in reqs):
        t0 = time.perf_counter()
        engine.step()
        outside += time.perf_counter() - t0
        iterations += 1
    d = delta(before, engine.counters())
    assert d["sched_iterations"] == iterations >= 300
    assert d["decode_rounds"] > 20 and d["prefill_programs_dispatched"] > 5
    inside = loop_seconds(d)
    # the test's own two clock reads and the call lie outside the timeline
    assert inside <= outside
    assert outside - inside < 20e-6 * iterations + 0.01 * outside
    assert d["sched_idle_sum_s"] == 0.0        # ``_loop``'s, not ``step``'s
    assert d["sched_host_busy_sum_s"] == pytest.approx(
        inside - d["sched_fetch_sum_s"] - d["sched_idle_sum_s"], abs=1e-9)
    for key in ("sched_reap_sum_s", "sched_admit_sum_s",
                "sched_prefill_dispatch_sum_s", "sched_sample_first_sum_s",
                "sched_ensure_pages_sum_s", "sched_sync_state_sum_s",
                "sched_decode_dispatch_sum_s", "sched_fetch_sum_s",
                "sched_emit_sum_s", "sched_other_sum_s"):
        assert d[key] > 0.0, key


def test_the_sums_add_up_to_the_loop_threads_lifetime(engine):
    """``_loop`` holds one timeline across iterations and idle waits: from
    its first instant to its last every second is in one sum."""
    before = engine.counters()
    t0 = time.perf_counter()
    engine.start()
    try:
        for n in (3, 2):
            reqs = [engine.submit(list(range(1, 50 + i)),
                                  SamplingParams(max_new_tokens=12))
                    for i in range(n)]
            for r in reqs:
                r.result(60)
            time.sleep(0.12)                    # a few idle waits
    finally:
        assert engine.stop()
    outside = time.perf_counter() - t0
    d = delta(before, engine.counters())
    inside = loop_seconds(d)
    assert d["sched_idle_sum_s"] > 0.15
    # the thread's start, the join and what ``stop`` does after it lie
    # outside
    assert inside <= outside and outside - inside < 0.25
    assert d["sched_host_busy_sum_s"] == pytest.approx(
        inside - d["sched_fetch_sum_s"] - d["sched_idle_sum_s"], abs=1e-9)
    assert d["sched_host_busy_sum_s"] < inside - 0.15


def test_a_childs_seconds_are_taken_out_of_its_parent(engine, monkeypatch):
    """``admit`` ⊃ ``prefill_dispatch``; ``sample_first`` ⊃ ``fetch`` and
    ``emit``: a sleep inside the child shows in the child's sum alone."""
    nap = 0.05
    dispatch = engine._dispatch_chunks
    emit = engine._emit_round
    get = jax.device_get

    def slow_dispatch(group):
        # inside ``admit`` and OUTSIDE ``prefill_dispatch``: admit's own
        time.sleep(nap)
        return dispatch(group)

    def slow_emit(*a):
        time.sleep(nap)
        return emit(*a)

    def slow_get(x):
        time.sleep(nap)
        return get(x)

    def run_one(prompt, budget):
        """A short request beside a live stream, up to its first token."""
        live = engine.submit(list(range(1, 30)),
                             SamplingParams(max_new_tokens=budget))
        while live.first_token_time is None:
            engine.step()
        before, outside = engine.counters(), 0.0
        late = engine.submit(prompt, SamplingParams(max_new_tokens=2))
        while late.first_token_time is None:
            t0 = time.perf_counter()
            engine.step()
            outside += time.perf_counter() - t0
        d = delta(before, engine.counters())
        while not (live.done.is_set() and late.done.is_set()):
            engine.step()
        return d, outside

    run_one(list(range(2, 31)), 40)             # every shape compiled
    monkeypatch.setattr(engine, "_dispatch_chunks", slow_dispatch)
    monkeypatch.setattr(engine, "_emit_round", slow_emit)
    monkeypatch.setattr(jax, "device_get", slow_get)
    d, outside = run_one(list(range(3, 32)), 40)
    monkeypatch.undo()
    programs = d["prefill_programs_dispatched"]
    assert programs >= 1 and d["first_token_fetches"] == 1
    # the naps land where they were taken: the live stream's round in flight
    # is fetched and emitted, then the first token fetched ...
    assert d["sched_admit_sum_s"] >= programs * nap
    assert d["sched_fetch_sum_s"] >= 2 * nap
    assert d["sched_emit_sum_s"] >= nap
    # ... and not a second time in what encloses them: the round in flight
    # was fetched and emitted inside ``sample_first`` (ISSUE 34), whose own
    # seconds hold neither nap, as ``prefill_dispatch`` holds none of
    # ``admit``'s; every second is in one sum
    assert d["sched_sample_first_sum_s"] < nap
    assert d["sched_prefill_dispatch_sum_s"] < nap * programs
    assert loop_seconds(d) <= outside
    assert outside - loop_seconds(d) < 0.01 * outside + 1e-3


def test_under_a_capture_the_sums_are_the_spans_innermost_segments(
        engine, tmp_path):
    """One boundary, two sinks: over a captured stretch each phase's sum is
    what ``innermost_segments`` cuts out of the recorded spans for it.

    The two sinks read the clock one after the other at a boundary (the
    phase clock's ``tick``, then the span's own stamp), so a worker the
    machine takes off its core between the two reads puts the whole stall
    into one sink: under six busy workers that is a millisecond now and then
    (the driver's run of PR 46: one phase off by more than 5% / 1 ms; alone
    and beside its file the test passed every time). A stall is one
    capture's; a sum that does not follow its spans is every capture's: the
    stretch is captured a second time where the first is off, and has to
    agree then."""
    def captured(at: str):
        profiler.start(at)
        before = engine.counters()
        reqs = [engine.submit(list(range(1, 60 + 5 * i)),
                              SamplingParams(max_new_tokens=25))
                for i in range(6)]
        while not all(r.done.is_set() for r in reqs):
            engine.step()
        after = engine.counters()
        profiler.stop()
        sched = hostspans.thread_with(load_spans(at), hostspans.ENGINE_THREAD)
        by_name: dict = {}
        for t0, t1, name in hostspans.innermost_segments(
                [s for s in sched if s[0] != profiler.ANCHOR]):
            by_name[name] = by_name.get(name, 0.0) + (t1 - t0)
        return delta(before, after), sched, by_name

    for attempt in ("t", "again"):
        moved, sched, by_name = captured(str(tmp_path / attempt))
        off = {}
        for name in profiler.ENGINE_PHASES:
            if name in (profiler.ENGINE_IDLE, profiler.ENGINE_KVTIER_TICK):
                continue            # ``_loop``'s; no host tier on this engine
            summed = moved[f"sched_{name.rpartition('.')[2]}_sum_s"]
            spanned = by_name[name]
            assert summed > 0.0
            if abs(summed - spanned) > max(0.05 * spanned, 1e-3):
                off[name] = (summed, spanned)
        if not off:
            break
    assert not off, off
    # what the sync sent and what the round handed on, on their spans
    syncs = [s[3] for s in sched if s[0] == profiler.ENGINE_SYNC_STATE]
    assert sum(a["slots"] for a in syncs) == moved["state_slot_syncs"]
    assert sum(a["rows"] for a in syncs) == moved["state_row_syncs"]
    assert sum(1 for a in syncs if a["slots"] or a["rows"]) \
        == moved["state_sync_rounds"] == moved["state_sync_dispatches"]
    emits = [s[3] for s in sched if s[0] == profiler.ENGINE_EMIT]
    assert sum(a["tokens"] for a in emits) == moved["decode_tokens_emitted"]
    assert all(0 <= a["streams"] <= 4 and a["streams"] <= a["tokens"]
               and "round" in a for a in emits)


def test_state_keys_are_decode_states_own_counts(engine):
    reqs = [engine.submit([5 + i] * 20, SamplingParams(max_new_tokens=6))
            for i in range(3)]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
    c = engine.counters()
    stats = engine._dstate.stats
    assert c["state_slot_syncs"] == stats["slot_syncs"] > 0
    assert c["state_row_syncs"] == stats["table_row_syncs"] > 0
    assert 0 < c["state_sync_rounds"] <= c["decode_rounds"]
    # a steady round syncs nothing, so fewer rounds synced than ran
    assert c["state_sync_rounds"] < c["decode_rounds"]
    # and a round that syncs sends one program, whatever it holds
    assert c["state_sync_dispatches"] == stats["sync_dispatches"] \
        == c["state_sync_rounds"]
    assert c["state_sync_dispatches"] \
        < c["state_slot_syncs"] + c["state_row_syncs"]


def test_the_speculative_paths_fetches_are_fetch_phases():
    # a draft model proposes every round: both fetches of the path run (the
    # drafts', the verification's)
    eng = make_engine(speculative={
        "mode": "draft_model", "k": 3,
        "draft": {"preset": "tiny", "overrides": {"vocab_size": 512}}})
    try:
        before = eng.counters()
        req = eng.submit([7, 8, 9] * 8, SamplingParams(
            max_new_tokens=16, temperature=0.0))
        while not req.done.is_set():
            eng.step()
        d = delta(before, eng.counters())
        assert eng.metrics.snapshot()["spec_rounds"] > 0
        assert d["sched_fetch_sum_s"] > 0.0 and d["sched_sync_state_sum_s"] > 0
        assert d["sched_host_busy_sum_s"] == pytest.approx(
            loop_seconds(d) - d["sched_fetch_sum_s"], abs=1e-9)
    finally:
        eng.stop()


def test_metrics_exports_the_phase_sums_as_one_family(engine):
    text = ModelServer("m", engine).metrics_text()
    c = engine.counters()
    for phase in PHASES + ["other"]:
        line = next(ln for ln in text.splitlines() if ln.startswith(
            f'kftpu_engine_sched_phase_seconds_total{{model="m",'
            f'phase="{phase}"}}'))
        assert float(line.split()[-1]) == pytest.approx(
            c[f"sched_{phase}_sum_s"], rel=1e-6, abs=1e-9)
    assert "# TYPE kftpu_engine_sched_phase_seconds_total counter" in text


# -- the server: a token's way from the round to the socket -------------------------

STREAM_KEYS = ("stream_chunks_n", "stream_write_sum_s", "stream_wake_sum_s",
               "stream_wake_n", "stream_behind_n")


def stream_lines(url: str, body: dict, *, pause_after_first: float = 0.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    lines = []
    with urllib.request.urlopen(req, timeout=60) as resp:
        for raw in resp:
            if raw.startswith(b"data: "):
                lines.append(raw[6:].strip())
                if len(lines) == 1 and pause_after_first:
                    time.sleep(pause_after_first)
    return lines


def test_server_counts_a_tokens_way_and_a_slow_consumer_falls_behind(
        monkeypatch):
    eng = make_engine(max_batch_size=2, decode_steps=1,
                      prefill_interleave_steps=1)
    server = ModelServer("m", eng)
    zero = server.counters()
    assert {k: zero[k] for k in STREAM_KEYS} == dict.fromkeys(STREAM_KEYS, 0)
    server.start()
    try:
        n = 2 * STREAM_FOLD_CHUNKS + 5
        body = {"model": "m", "prompt": "hello", "max_tokens": n,
                "stream": True}
        lines = stream_lines(server.url + "/v1/completions", body)
        assert lines[-1] == b"[DONE]" and len(lines) == n + 1
        one = server.counters()
        assert one["stream_chunks_n"] == n      # the stream's end folds too
        assert one["stream_wake_n"] + one["stream_behind_n"] == n
        assert one["stream_wake_n"] > 0
        assert 0.0 < one["stream_wake_sum_s"] < 10.0
        assert 0.0 < one["stream_write_sum_s"] < 10.0
        assert one["first_byte_overhead_n"] == 1

        # A handler that takes longer over a chunk than the engine over a
        # round: tokens pile up behind the one it holds. Those it takes
        # while a later one waits are counted, not sampled.
        handler = server.httpd.RequestHandlerClass
        chunk = handler._chunk

        def slow_chunk(self, data):
            time.sleep(0.01)
            return chunk(self, data)

        monkeypatch.setattr(handler, "_chunk", slow_chunk)
        lines = stream_lines(server.url + "/v1/completions", body)
        assert len(lines) == n + 1
        two = server.counters()
        d = delta(one, two)
        assert d["stream_chunks_n"] == n
        assert d["stream_behind_n"] > n // 2
        assert d["stream_wake_n"] == n - d["stream_behind_n"]
        assert d["stream_write_sum_s"] >= 0.01 * n
        # unsampled: the mean over the sampled ones is not dragged along by
        # the seconds the others waited in the queue
        if d["stream_wake_n"]:
            assert d["stream_wake_sum_s"] / d["stream_wake_n"] < 0.01 * n / 4
    finally:
        server.stop()


def test_a_snapshot_sees_a_stream_in_flight_every_fold():
    eng = make_engine(max_batch_size=2, decode_steps=1,
                      prefill_interleave_steps=1)
    server = ModelServer("m", eng)
    server.start()
    seen = []
    try:
        n = 3 * STREAM_FOLD_CHUNKS
        body = {"model": "m", "prompt": "hello", "max_tokens": n,
                "stream": True}
        done = threading.Event()

        def watch():
            while not done.is_set():
                seen.append(server.counters()["stream_chunks_n"])
                time.sleep(0.002)

        t = threading.Thread(target=watch)
        t.start()
        try:
            stream_lines(server.url + "/v1/completions", body)
        finally:
            done.set()
            t.join(10)
        assert not t.is_alive()
        assert server.counters()["stream_chunks_n"] == n
        # only whole folds and the end's remainder ever show
        assert set(seen) <= {0, STREAM_FOLD_CHUNKS, 2 * STREAM_FOLD_CHUNKS, n}
    finally:
        server.stop()
