"""The one trace control (obs/profiler.py), and what the hot loops leave in
a capture taken through it: the engine's and the trainer's phases as host
spans in the profiler's own trace, nested as the README lists them, and the
always-on counters beside them.

The captures are of the CPU backend: the profiler writes host annotations
there too, into the plane ``/host:CPU``, one line per thread.
"""

import glob
import json
import os
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
from kubeflow_tpu.runtime.mesh import build_mesh
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.server import PROFILE_MAX_SECONDS, ModelServer
from kubeflow_tpu.train.trainer import Trainer, TrainerConfig


def load_spans(trace_dir: str):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    assert files, f"no trace under {trace_dir}"
    return hostspans.from_profile(ProfileData.from_file(files[-1]), 0)


def named(thread, name):
    return [s for s in thread if s[0] == name]


def inside(child, parent) -> bool:
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2] + 1e-9


@pytest.fixture(autouse=True)
def control_is_off():
    assert not profiler.active()
    yield
    profiler.stop()


# -- the control ----------------------------------------------------------------

def test_off_hot_span_is_the_one_shared_noop():
    a = profiler.hot_span("engine.admit")
    b = profiler.hot_span("engine.fetch", round=3)
    c = profiler.hot_step("train", 7)
    assert a is b is c is profiler.NO_SPAN
    with a as entered:
        assert entered is profiler.NO_SPAN
    with pytest.raises(ValueError):        # it swallows nothing
        with profiler.hot_span("engine.emit"):
            raise ValueError("passes through")


def test_stop_without_start_is_a_noop():
    assert profiler.stop() == ""
    assert not profiler.active()


def test_start_stop_active_and_double_start(tmp_path):
    d = str(tmp_path / "t")
    profiler.start(d)
    try:
        assert profiler.active()
        assert profiler.hot_span("engine.admit") is not profiler.NO_SPAN
        with pytest.raises(RuntimeError, match="already active"):
            profiler.start(str(tmp_path / "other"))
        assert profiler.active()           # the first capture goes on
    finally:
        assert profiler.stop() == d
    assert not profiler.active()
    assert profiler.stop() == ""
    assert glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    assert not os.path.exists(tmp_path / "other")


def test_one_anchor_per_trace_and_spans_on_the_calling_thread(tmp_path):
    d = str(tmp_path / "t")
    profiler.start(d)

    def work():
        with profiler.hot_span("engine.decode_dispatch", round=5, k_steps=8,
                               live=3):
            with profiler.hot_span("engine.sync_state"):
                pass

    t = threading.Thread(target=work)
    t.start()
    t.join(10)
    with profiler.hot_step("train", 11):
        pass
    profiler.stop()
    threads = load_spans(d)
    anchors = [s for th in threads for s in named(th, profiler.ANCHOR)]
    assert len(anchors) == 1
    assert anchors[0][3]["wall_ns"] > 1e18 and anchors[0][3]["mono_ns"] > 0
    assert hostspans.anchor(threads)["trace_s"] == anchors[0][1]
    worker = hostspans.thread_with(threads, ("engine.decode_dispatch",))
    assert not named(worker, profiler.ANCHOR)      # another thread's line
    (outer,), (inner,) = (named(worker, "engine.decode_dispatch"),
                          named(worker, "engine.sync_state"))
    assert outer[3] == {"round": 5, "k_steps": 8, "live": 3}
    assert inside(inner, outer)
    main = hostspans.thread_with(threads, (profiler.ANCHOR,))
    assert named(main, "train")[0][3]["step_num"] == 11


def test_a_trace_without_the_control_has_no_plain_form(tmp_path):
    """The readers tell a program without spans from a quiet one by the
    anchor: no anchor, nothing to read."""
    d = str(tmp_path / "t")
    jax.profiler.start_trace(d)
    jax.block_until_ready(jax.numpy.ones(8) + 1)
    jax.profiler.stop_trace()
    assert load_spans(d) is None


# -- the engine -----------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = preset("tiny", vocab_size=512)
    eng = LLMEngine(cfg, BatchingSpec(
        max_batch_size=4, max_seq_len=128, chunked_prefill_tokens=32,
        paged=True, page_size=16, decode_steps=4,
        prefill_interleave_steps=2),
        params=init_decoder_params(jax.random.PRNGKey(0), cfg))
    yield eng
    eng.stop()


# What must be IN ``counters()``: a later key is added without a word here
# (the scheduler's phase sums and the state syncs' counts have their own
# tests, tests/test_serve_phase_sums.py).
ENGINE_KEYS = {
    "slots", "queue_delay_sum_s", "queue_delay_n", "host_gap_sum_s",
    "host_gap_n", "preemptions", "requests_shed", "requests_completed",
    "tokens_generated", "decode_rounds", "first_token_fetches",
    "prefill_phase_sum_s", "prefill_phase_n", "decode_steps_dispatched",
    "decode_tokens_emitted", "decode_context_tokens", "decode_rounds_at_cap",
    "sched_host_busy_sum_s", "prefill_programs_dispatched", "prefill_chunks_dispatched",
    "prefill_tokens_dispatched", "prefill_passes", "prefill_chunks_deferred",
    "kv_bytes_per_token", "kv_pool_bytes", "state_pool_bytes",
    "state_tail_writes", "weights_relaid_bytes"}
ENGINE_CONSTANTS = {"slots", "kv_bytes_per_token", "kv_pool_bytes",
                    "state_pool_bytes", "weights_relaid_bytes",
                    "kv_window_pool_bytes", "kv_global_pool_bytes",
                    "kv_window_pages_a_sequence", "kv_sequence_pool_bytes",
                    "kv_token_pool_bytes",
                    # what the constructor took (tests/test_obs_startup.py)
                    "start_place_sum_s", "start_pool_sum_s",
                    "start_relay_sum_s", "start_warm_sum_s",
                    "start_other_sum_s"}
# the PROCESS's compiles so far: neither the engine's nor at rest
PROCESS_KEYS = {"compile_backend_sum_s", "compile_backend_n",
                "compile_retrieval_sum_s", "compile_trace_lower_sum_s",
                "compile_cache_hits", "compile_cache_misses"}


def test_engine_counters_exist_at_construction_and_only_grow(engine):
    before = engine.counters()
    assert set(before) >= ENGINE_KEYS      # before any request
    assert all(v == 0 for k, v in before.items()
               if k not in ENGINE_CONSTANTS | PROCESS_KEYS)
    assert before["slots"] == 4
    assert before["kv_pool_bytes"] > before["kv_bytes_per_token"] > 0
    reqs = [engine.submit(list(range(1, 40 + i)),
                          SamplingParams(max_new_tokens=9))
            for i in range(3)]
    snaps = [before]
    while not all(r.done.is_set() for r in reqs):
        engine.step()
        snaps.append(engine.counters())
    for a, b in zip(snaps, snaps[1:]):
        assert set(b) == set(before)       # no key comes or goes with traffic
        assert all(b[k] >= a[k] for k in b)
    after = snaps[-1]
    assert after["prefill_phase_n"] == 3 and after["prefill_phase_sum_s"] > 0
    # a prompt token is dispatched once, or found in the prefix index (the
    # third prompt can be); chunks share programs
    assert 39 + 40 < after["prefill_tokens_dispatched"] <= 39 + 40 + 41
    assert 0 < after["prefill_programs_dispatched"] \
        <= after["prefill_chunks_dispatched"]
    # a first token comes from the prefill; the decode rounds emit the rest
    assert after["decode_tokens_emitted"] == 3 * 8
    # a round dispatches 1, 2 (a prefill in flight) or 4 steps
    assert after["decode_rounds"] <= after["decode_steps_dispatched"] \
        <= 4 * after["decode_rounds"]
    assert after["decode_tokens_emitted"] \
        <= after["decode_steps_dispatched"] * after["slots"]
    # every dispatched step attends to at least a prompt's rows
    assert after["decode_context_tokens"] \
        >= 39 * after["decode_steps_dispatched"]
    assert all(after[k] == before[k] for k in ENGINE_CONSTANTS)


def test_engine_writes_its_phases_into_the_capture(engine, tmp_path):
    d = str(tmp_path / "t")
    engine.start()
    try:
        engine.submit([3] * 20, SamplingParams(max_new_tokens=4)).result(60)
        profiler.start(d)
        reqs = [engine.submit(list(range(1, 50 + i)),
                              SamplingParams(max_new_tokens=10))
                for i in range(5)]
        for r in reqs:
            r.result(60)
        time.sleep(0.2)                    # let the loop fall idle once
        profiler.stop()
    finally:
        engine.stop()
    threads = load_spans(d)
    sched = hostspans.thread_with(threads, hostspans.ENGINE_THREAD)
    names = {s[0] for s in sched}
    assert names >= {
        profiler.ENGINE_REAP, profiler.ENGINE_ADMIT,
        profiler.ENGINE_PREFILL_DISPATCH, profiler.ENGINE_SAMPLE_FIRST,
        profiler.ENGINE_KVTIER_TICK, profiler.ENGINE_ENSURE_PAGES,
        profiler.ENGINE_SYNC_STATE, profiler.ENGINE_DECODE_DISPATCH,
        profiler.ENGINE_FETCH, profiler.ENGINE_EMIT, profiler.ENGINE_IDLE}
    assert not named(sched, profiler.ANCHOR)       # the main thread's
    admits = named(sched, profiler.ENGINE_ADMIT)
    for child in (named(sched, profiler.ENGINE_PREFILL_DISPATCH)
                  + named(sched, profiler.ENGINE_SAMPLE_FIRST)):
        assert any(inside(child, a) for a in admits), child
    # the top-level phases of one iteration do not overlap
    firsts = [s for s in named(sched, profiler.ENGINE_FETCH)
              if "first" in s[3]]
    samplers = named(sched, profiler.ENGINE_SAMPLE_FIRST)
    assert firsts and all(any(inside(f, sf) and f[3]["first"] == sf[3]["n"]
                              for sf in samplers) for f in firsts)
    # The round in flight goes out BEFORE the wait for the first tokens
    # (ISSUE 34): its fetch and emit lie inside the sampler's span, ahead
    # of that span's own fetch.
    early = [s for s in sched
             if s[0] in (profiler.ENGINE_FETCH, profiler.ENGINE_EMIT)
             and s not in firsts and any(inside(s, sf) for sf in samplers)]
    assert early and all(any(inside(e, sf) and inside(f, sf) and e[1] < f[1]
                             for sf in samplers for f in firsts)
                         for e in early)
    # the top-level phases of one iteration do not overlap
    top = sorted((s for s in sched if s not in firsts and s not in early
                  and s[0] not in (profiler.ENGINE_PREFILL_DISPATCH,
                                   profiler.ENGINE_SAMPLE_FIRST)),
                 key=lambda s: s[1])
    for a, b in zip(top, top[1:]):
        assert a[1] + a[2] <= b[1] + 1e-9, (a, b)
    dispatched = {s[3]["round"]: s for s in
                  named(sched, profiler.ENGINE_DECODE_DISPATCH)}
    fetched = [s for s in named(sched, profiler.ENGINE_FETCH)
               if "round" in s[3]]
    assert fetched and all(f[3]["round"] in dispatched or
                           f[3]["round"] == min(dispatched) - 1
                           for f in fetched)
    for f in fetched:                      # a fetch follows its dispatch
        if f[3]["round"] in dispatched:
            assert dispatched[f[3]["round"]][1] < f[1]
    one = next(iter(dispatched.values()))[3]
    assert one["k_steps"] in (1, 2, 4) and 1 <= one["live"] <= 4
    # the rows a round attends to: at least a prompt's a live slot a step
    assert all(s[3]["context"] >= 49 * s[3]["live"] * s[3]["k_steps"]
               for s in dispatched.values())
    assert 0.0 < hostspans.busy_share(sched, hostspans.ENGINE_BLOCKED) <= 100


# -- the server -----------------------------------------------------------------

def _post(url: str, body: dict, timeout: float = 60.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def test_server_counts_first_byte_overhead_and_captures_a_live_replica(
        tmp_path):
    cfg = preset("tiny", vocab_size=512)
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=128,
                                      decode_steps=4),
                    params=init_decoder_params(jax.random.PRNGKey(0), cfg))
    d = str(tmp_path / "cap")
    server = ModelServer("m", eng, profile_dir=d)
    assert server.counters().items() >= {"first_byte_overhead_sum_s": 0.0,
                                         "first_byte_overhead_n": 0}.items()
    server.start()
    try:
        # the capture goes where the SERVER was told, whatever a client
        # names, and lasts at most the cap
        elsewhere = str(tmp_path / "elsewhere")
        assert json.loads(_post(
            server.url + "/debug/profile/start",
            {"dir": elsewhere, "seconds": 3600})) == {
                "active": True, "dir": d,
                "seconds": PROFILE_MAX_SECONDS}
        assert not os.path.exists(elsewhere)
        with urllib.request.urlopen(server.url + "/debug/profile") as r:
            assert json.loads(r.read()) == {"active": True}
        with pytest.raises(urllib.error.HTTPError) as second:
            _post(server.url + "/debug/profile/start", {})
        assert second.value.code == 409
        body = {"model": "m", "prompt": "hello", "max_tokens": 5,
                "stream": True}
        for _ in range(2):
            assert b"[DONE]" in _post(server.url + "/v1/completions", body)
        # a completion that does not stream has no first byte of its own
        _post(server.url + "/v1/completions", {**body, "stream": False})
        assert json.loads(_post(server.url + "/debug/profile/stop", {})) \
            == {"active": False, "dir": d}
        assert not profiler.active()
        after = server.counters()
        assert after["first_byte_overhead_n"] == 2
        assert 0.0 < after["first_byte_overhead_sum_s"] < 10.0
        text = server.metrics_text()
        assert 'kftpu_serving_first_byte_overhead_seconds_count{model="m"} 2' \
            in text
        sched = hostspans.thread_with(load_spans(d), hostspans.ENGINE_THREAD)
        assert named(sched, profiler.ENGINE_DECODE_DISPATCH)
        # a client that never sends /stop: the server ends the capture
        assert json.loads(_post(server.url + "/debug/profile/start",
                                {"seconds": 0.3}))["seconds"] == 0.3
        deadline = time.monotonic() + 30.0
        while profiler.active() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not profiler.active()
        assert json.loads(_post(server.url + "/debug/profile/stop", {})) \
            == {"active": False, "dir": ""}
    finally:
        server.stop()


# -- the trainer ----------------------------------------------------------------

def make_trainer(tmp_path, **kw):
    cfg = TrainerConfig(
        model="tiny", model_overrides={"n_layers": 1, "hidden": 32},
        optimizer={"learning_rate": 1e-3, "total_steps": 100},
        data={"global_batch": 8, "seq_len": 16, "vocab_size": 64},
        steps=8, log_every=2, watchdog_enabled=False, **kw)
    return Trainer(cfg, build_mesh({"fsdp": 8}),
                   metrics_path=str(tmp_path / "m.jsonl"))


def check_trainer_capture(trace_dir, steps):
    threads = load_spans(trace_dir)
    loop = hostspans.thread_with(threads, hostspans.TRAINER_THREAD)
    iterations = named(loop, profiler.TRAIN_STEP)
    assert [s[3]["step_num"] for s in iterations] == steps
    for name in (profiler.TRAIN_STAGE_WAIT, profiler.TRAIN_DISPATCH):
        spans = named(loop, name)
        assert [s[3]["step"] for s in spans] == steps
        assert all(inside(s, it) for s, it in zip(spans, iterations))
    logged = [s for s in steps if (s + 1) % 2 == 0]
    for name in (profiler.TRAIN_SYNC, profiler.TRAIN_LOG):
        assert [s[3]["step"] for s in named(loop, name)] == logged
    assert 0.0 < hostspans.busy_share(loop, hostspans.TRAINER_BLOCKED) <= 100


def test_trainer_profile_start_step_goes_through_the_control(tmp_path):
    tr = make_trainer(tmp_path, profile_start_step=3, profile_num_steps=2)
    assert tr.counters()["stage_wait_sum_s"] == 0.0
    seen = []
    tr.run(on_step=lambda step, m: seen.append(
        (step, profiler.active(), tr.counters())))
    assert [a for _, a, _ in seen] == [False] * 3 + [True] * 2 + [False] * 3
    assert not profiler.active()
    for (_, _, a), (_, _, b) in zip(seen, seen[1:]):
        assert set(a) == set(b) and all(b[k] >= a[k] for k in a)
    assert seen[-1][2]["stage_wait_sum_s"] > 0
    check_trainer_capture(str(tmp_path / "trace"), [3, 4])


def test_trainer_captures_a_running_job_from_the_next_step(tmp_path):
    tr = make_trainer(tmp_path)
    d = str(tmp_path / "asked")
    seen = []

    def on_step(step, metrics):
        if step == 2:                      # asked while the job runs
            tr.request_profile(num_steps=3, trace_dir=d)
        seen.append(profiler.active())

    tr.run(on_step=on_step)
    assert seen == [False] * 2 + [True] * 3 + [False] * 3
    check_trainer_capture(d, [2, 3, 4])


def test_trainer_stops_its_capture_when_the_loop_dies(tmp_path):
    tr = make_trainer(tmp_path, profile_start_step=1, profile_num_steps=50)

    def on_step(step, metrics):
        if step == 3:
            raise KeyboardInterrupt()

    with pytest.raises(KeyboardInterrupt):
        tr.run(on_step=on_step)
    assert not profiler.active()
