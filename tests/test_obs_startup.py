"""The start-up clock inside the program (ISSUE 53): the constructor of
``LLMEngine`` and the start of a ``Trainer`` go through a ``PhaseClock`` of
their own (``engine.start.*`` / ``train.start.*``: always-on sums in
``counters()``, spans under a capture that is active while they run), each
program the engine's constructor runs once is one ``engine.start.warm``
phase, and ONE set of ``jax.monitoring`` listeners a process folds JAX's
compile events into the ``compile_*`` keys both carry.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark import hostspans
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.obs import profiler
from kubeflow_tpu.runtime import bootstrap
from kubeflow_tpu.runtime.device_report import device_report
from kubeflow_tpu.runtime.mesh import build_mesh
from kubeflow_tpu.serve import engine as engine_mod
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.server import ModelServer
from kubeflow_tpu.train.trainer import Trainer, TrainerConfig
from test_obs_profiler import load_spans

ENGINE_START = [n.rpartition(".")[2] for n in profiler.ENGINE_START_PHASES]
TRAIN_START = [n.rpartition(".")[2] for n in profiler.TRAIN_START_PHASES]
ENGINE_START_KEYS = [f"start_{p}_sum_s" for p in ENGINE_START + ["other"]]
TRAIN_START_KEYS = [f"start_{p}_sum_s" for p in TRAIN_START]
COMPILE_KEYS = ["compile_backend_sum_s", "compile_backend_n",
                "compile_retrieval_sum_s", "compile_trace_lower_sum_s",
                "compile_cache_hits", "compile_cache_misses"]


@pytest.fixture(autouse=True)
def control_is_off():
    assert not profiler.active()
    yield
    profiler.stop()


def make_engine(**kw):
    cfg = preset("tiny", vocab_size=512)
    spec = dict(max_batch_size=4, max_seq_len=128, chunked_prefill_tokens=32,
                paged=True, page_size=16, decode_steps=4,
                prefill_interleave_steps=2)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec),
                     params=init_decoder_params(jax.random.PRNGKey(0), cfg))


def make_trainer(tmp_path, **kw):
    cfg = TrainerConfig(
        model="tiny", model_overrides={"n_layers": 1, "hidden": 32},
        optimizer={"learning_rate": 1e-3, "total_steps": 100},
        data={"global_batch": 8, "seq_len": 16, "vocab_size": 64},
        steps=6, log_every=2, watchdog_enabled=False, **kw)
    return Trainer(cfg, build_mesh({"fsdp": 8}),
                   metrics_path=str(tmp_path / "m.jsonl"))


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    yield eng
    eng.stop()


# -- the keys ---------------------------------------------------------------------

def test_the_names_stand_beside_the_loops_phases():
    assert ENGINE_START == ["place", "pool", "relay", "warm"]
    assert TRAIN_START == ["build", "resume", "first_step"]
    assert all(hostspans.PROGRAM_SPAN.match(name) for name in
               profiler.ENGINE_START_PHASES + profiler.TRAIN_START_PHASES)
    assert not set(profiler.ENGINE_START_PHASES) & set(profiler.ENGINE_PHASES)


def test_an_engine_has_every_key_from_construction_on(engine):
    c = engine.counters()
    for key in ENGINE_START_KEYS + COMPILE_KEYS:
        assert key in c, key
    assert set(engine.start_phase_seconds()) == set(ENGINE_START) | {"other"}
    # built: the pool was made, programs were run once, and the rest of the
    # constructor is under no phase
    assert c["start_pool_sum_s"] > 0.0 and c["start_warm_sum_s"] > 0.0
    assert c["start_other_sum_s"] > 0.0 and c["start_place_sum_s"] > 0.0
    programs = engine.start_programs()
    assert len(programs) >= 4
    # the ladder, shortest first, then the state sync, over the state as
    # the ladder's programs left it
    assert list(programs)[-4:] == [
        "paged_decode[1,greedy]", "paged_decode[2,greedy]",
        "paged_decode[4,greedy]",
        f"state_sync[{engine.num_slots},{engine._mpp}]"]
    assert sum(programs.values()) == pytest.approx(c["start_warm_sum_s"])
    programs.clear()                      # a copy: not the engine's own
    assert engine.start_programs()


def test_the_start_sums_are_constants_once_the_engine_is_built(engine):
    before = engine.counters()
    engine.generate(list(range(1, 40)), SamplingParams(max_new_tokens=6))
    after = engine.counters()
    assert after["decode_rounds"] > before["decode_rounds"]
    for key in ENGINE_START_KEYS:
        assert after[key] == before[key], key


def test_a_trainer_has_every_key_from_construction_and_three_after_a_step(
        tmp_path):
    tr = make_trainer(tmp_path)
    c = tr.counters()
    assert set(c) >= set(TRAIN_START_KEYS + COMPILE_KEYS
                         + ["stage_wait_sum_s"])
    assert c["start_build_sum_s"] > 0.0
    assert c["start_resume_sum_s"] == c["start_first_step_sum_s"] == 0.0
    seen = {}
    tr.run(on_step=lambda step, _m: seen.setdefault(step, tr.counters()))
    # log_every 2, and the bracket is the first step alone all the same:
    # closed when that step's callback runs, a constant from then on
    assert seen[1]["start_first_step_sum_s"] > 0.0
    assert seen[1]["start_resume_sum_s"] > 0.0
    for key in TRAIN_START_KEYS:
        assert seen[6][key] == seen[1][key], key
    assert seen[6]["start_build_sum_s"] == c["start_build_sum_s"]


# -- the identity on a clock moved by hand ---------------------------------------

class OneASecond:
    """A clock that stands a second later at every reading."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return float(self.reads)


def test_the_start_phases_and_other_add_up_to_the_bracket(monkeypatch):
    clocks = []

    class Clocked(profiler.PhaseClock):
        def __init__(self, names):
            clocks.append(OneASecond())
            super().__init__(names, clock=clocks[-1])

    monkeypatch.setattr(engine_mod.prof, "PhaseClock", Clocked)
    eng = make_engine()
    start, loop = clocks                  # the constructor's, the scheduler's
    c = eng.counters()
    # ``begin`` read the clock first and ``end`` last: every reading between
    # them is a boundary, and every second between two boundaries lies in
    # exactly one sum
    assert sum(c[k] for k in ENGINE_START_KEYS) == start.reads - 1
    # a phase that nothing interrupts is two readings: one second
    assert c["start_warm_sum_s"] == len(eng.start_programs())
    assert all(s == 1.0 for s in eng.start_programs().values())
    assert loop.reads == 0                # nothing of the loop has run
    eng.step()
    assert loop.reads > 0
    assert sum(eng.counters()[k] for k in ENGINE_START_KEYS) \
        == start.reads - 1
    eng.stop()


def test_the_trainers_three_count_what_they_bracket(tmp_path, monkeypatch):
    clock = OneASecond()
    real = profiler.PhaseClock
    monkeypatch.setattr(profiler, "PhaseClock",
                        lambda names: real(names, clock=clock))
    tr = make_trainer(tmp_path)
    assert tr.counters()["start_build_sum_s"] == 1.0      # in, out
    tr.run()
    c = tr.counters()
    assert (c["start_build_sum_s"], c["start_resume_sum_s"],
            c["start_first_step_sum_s"]) == (1.0, 1.0, 1.0)
    assert clock.reads == 6


# -- under a capture ---------------------------------------------------------------

def test_under_a_capture_the_start_sums_are_the_spans_innermost_segments(
        tmp_path):
    """PR 37's test of the loop's phases, for the constructor: one boundary,
    two sinks. (As there, a worker taken off its core between the clock's
    read and the span's own stamp puts the stall into one sink, so a capture
    that is off is taken once more and has to agree then.)"""
    def captured(at: str):
        profiler.start(at)
        eng = make_engine()
        profiler.stop()
        c = eng.counters()
        programs = eng.start_programs()
        eng.stop()
        builder = hostspans.thread_with(load_spans(at),
                                        profiler.ENGINE_START_PHASES)
        by_name: dict = {}
        for t0, t1, name in hostspans.innermost_segments(
                [s for s in builder if s[0] != profiler.ANCHOR]):
            by_name[name] = by_name.get(name, 0.0) + (t1 - t0)
        return c, programs, builder, by_name

    for attempt in ("t", "again"):
        c, programs, builder, by_name = captured(str(tmp_path / attempt))
        off = {}
        for name in profiler.ENGINE_START_PHASES:
            summed = c[f"start_{name.rpartition('.')[2]}_sum_s"]
            spanned = by_name.get(name, 0.0)
            if name == profiler.ENGINE_START_RELAY:
                assert summed < 0.05        # nothing is relaid on the CPU
            else:
                assert summed > 0.0, name
            if abs(summed - spanned) > max(0.05 * spanned, 1e-3):
                off[name] = (summed, spanned)
        if not off:
            break
    assert not off, off
    # each warmed program is one span, in order, and says which it is
    warm = [s for s in builder if s[0] == profiler.ENGINE_START_WARM]
    assert [s[3]["program"] for s in warm] == list(programs)
    assert list(programs)[0] == "kv_copy_pages[1]"      # the radix index's
    assert "paged_chunk_prefill[2x32,8]" in programs    # the program over rows
    for span, seconds in zip(warm, programs.values()):
        assert abs(span[2] - seconds) <= max(0.05 * span[2], 1e-3)
    # and no start span carries anything else
    assert all(not s[3] for s in builder
               if s[0] in profiler.ENGINE_START_PHASES
               and s[0] != profiler.ENGINE_START_WARM)


def test_a_trainers_start_is_three_spans_under_a_capture(tmp_path):
    at = str(tmp_path / "t")
    profiler.start(at)
    tr = make_trainer(tmp_path)
    tr.run()
    profiler.stop()
    c = tr.counters()
    loop = hostspans.thread_with(load_spans(at), hostspans.TRAINER_THREAD)
    for name in profiler.TRAIN_START_PHASES:
        spans = [s for s in loop if s[0] == name]
        assert len(spans) == 1, name
        summed = c[f"start_{name.rpartition('.')[2]}_sum_s"]
        assert abs(spans[0][2] - summed) <= max(0.05 * summed, 1e-3), name
    first = next(s for s in loop
                 if s[0] == profiler.TRAIN_START_FIRST_STEP)
    assert first[3] == {"step": 0}
    # the first step alone (log_every is 2): its dispatch and its one-off
    # sync inside the span, the second step's dispatch behind it, and the
    # span inside the first step's ``train`` annotation
    dispatches = sorted((s for s in loop if s[0] == profiler.TRAIN_DISPATCH),
                        key=lambda s: s[1])
    sync = min((s for s in loop if s[0] == profiler.TRAIN_SYNC),
               key=lambda s: s[1])
    end = first[1] + first[2]
    assert first[1] <= dispatches[0][1]
    assert dispatches[0][1] + dispatches[0][2] <= sync[1]
    assert sync[3] == {"step": 0} and sync[1] + sync[2] <= end + 1e-6
    assert end <= dispatches[1][1] + 1e-6


# -- compiles: one listener a process ----------------------------------------------

def test_a_fresh_jit_moves_the_compile_counters_by_one(engine):
    x = jnp.ones((7,))
    before = engine.counters()
    jax.jit(lambda x: x * 3.0 + float(before["compile_backend_n"]))(
        x).block_until_ready()
    after = engine.counters()
    assert after["compile_backend_n"] == before["compile_backend_n"] + 1
    assert after["compile_backend_sum_s"] > before["compile_backend_sum_s"]
    assert after["compile_trace_lower_sum_s"] \
        > before["compile_trace_lower_sum_s"]
    # the same program again compiles nothing
    again = engine.counters()
    assert again["compile_backend_n"] == after["compile_backend_n"]
    # a retrieval is inside the backend's seconds, never beside them
    assert after["compile_retrieval_sum_s"] <= after["compile_backend_sum_s"]


def test_a_trace_inside_a_trace_is_counted_once():
    totals = bootstrap.watch_compiles()

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    from jax._src import monitoring

    seen = []

    def listen(event, seconds, **_kw):
        seen.append((event, seconds))

    x = jnp.ones((5,))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        before = dict(totals)
        outer(x).block_until_ready()
        after = dict(totals)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    traces = [s for e, s in seen if e == bootstrap._TRACE_EVENT]
    lowerings = [s for e, s in seen if e.endswith("to_mlir_module_duration")]
    assert len(traces) >= 2               # outer's, and inner's inside it
    moved = after["trace_lower_s"] - before["trace_lower_s"]
    # the outermost trace (the last to end) and the lowering, nothing twice
    assert moved == pytest.approx(traces[-1] + sum(lowerings))
    assert moved < sum(traces) + sum(lowerings)


def test_two_engines_and_a_trainer_register_one_listener(tmp_path, engine):
    from jax._src import monitoring

    def registered():
        return (len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners()),
                len(monitoring.get_scalar_listeners()))

    before = registered()
    totals = bootstrap.watch_compiles()
    second = make_engine()
    tr = make_trainer(tmp_path)
    assert registered() == before
    assert bootstrap.watch_compiles() is totals
    # process-wide: every owner reads the same totals
    a, b, c = engine.counters(), second.counters(), tr.counters()
    for key in COMPILE_KEYS:
        assert a[key] == b[key] == c[key], key
    second.stop()


def test_the_caches_stats_hold_the_totals_where_the_cache_was_enabled(
        monkeypatch):
    totals = bootstrap.watch_compiles()
    monkeypatch.setattr(bootstrap, "_cache_enabled", False)
    assert bootstrap.compile_cache_stats() is None
    monkeypatch.setattr(bootstrap, "_cache_enabled", True)
    stats = bootstrap.compile_cache_stats()
    assert set(stats) == {"dir", "entries", *totals}
    assert set(totals) == {"hits", "misses", "backend_compiles",
                           "backend_compile_s", "retrieval_s",
                           "trace_lower_s"}


# -- where an operator reads it ------------------------------------------------------

def test_metrics_exports_the_start_phases_and_the_compiles(engine):
    text = ModelServer("m", engine).metrics_text()
    c = engine.counters()

    def sample(series: str) -> float:
        line = next(ln for ln in text.splitlines() if ln.startswith(series))
        return float(line.split()[-1])

    for phase in ENGINE_START + ["other"]:
        assert sample('kftpu_engine_start_seconds{model="m",'
                      f'phase="{phase}"}}') == pytest.approx(
            c[f"start_{phase}_sum_s"], rel=1e-6, abs=1e-9)
    for kind in ("backend", "retrieval", "trace_lower"):
        assert sample(f'kftpu_compile_seconds_total{{kind="{kind}"}}') \
            == pytest.approx(c[f"compile_{kind}_sum_s"], rel=1e-6, abs=1e-9)
    assert sample("kftpu_compiles_total") == c["compile_backend_n"]
    assert sample('kftpu_compile_cache_requests_total{result="hit"}') \
        == c["compile_cache_hits"]
    assert sample('kftpu_compile_cache_requests_total{result="miss"}') \
        == c["compile_cache_misses"]
    assert "# TYPE kftpu_engine_start_seconds gauge" in text
    assert "# TYPE kftpu_compile_seconds_total counter" in text
    assert "# TYPE kftpu_compiles_total counter" in text
    assert "# TYPE kftpu_compile_cache_requests_total counter" in text


def test_the_device_report_says_what_the_start_cost(engine, tmp_path):
    assert "start" not in device_report()   # its callers' section, not its
    payload = ModelServer("m", engine).device_payload()
    assert payload["start"] == {"m": {
        "phases": engine.start_phase_seconds(),
        "programs": engine.start_programs()}}
    assert set(payload["start"]["m"]["phases"]) \
        == set(ENGINE_START) | {"other"}
    from kubeflow_tpu.runtime.device_report import read_device_report

    tr = make_trainer(tmp_path)
    tr.workdir = str(tmp_path)
    tr._write_device_report()
    rep = read_device_report(str(tmp_path))
    assert rep["start"] == {"phases": tr.start_phase_seconds()}
    assert set(rep["start"]["phases"]) == set(TRAIN_START)
