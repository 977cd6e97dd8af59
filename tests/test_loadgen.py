"""Trace-driven serving loadgen (ISSUE 11): schedule determinism,
arrival-process statistics, shared-prefix generation, the shared
quantile helpers at their exact boundaries, the threshold gate's
regression logic, and an end-to-end scenario run against a real engine
with the full attribution join (client percentiles + /metrics scrape +
per-phase span breakdowns) and a quiescent trace ring."""

import math
import time

import numpy as np
import pytest
import jax

from kubeflow_tpu.loadgen import (
    ATTRIBUTION_SERIES, Arrival, EngineTarget, LengthDist, Scenario,
    arrival_times, build_report, build_schedule, compare_matrix,
    compare_scenario, measured_prefix_overlap, noise_band_pct,
    report_registry, run_scenario, spread_pct, standard_matrix,
)
from kubeflow_tpu.obs import stats
from kubeflow_tpu.obs.trace import Tracer, get_tracer

TRACER = get_tracer()


@pytest.fixture(autouse=True)
def _fresh_tracer():
    TRACER.reset()
    yield
    TRACER.reset()


# -- stats: the one quantile implementation ------------------------------------

class TestStats:
    def test_exact_boundaries(self):
        xs = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert stats.quantile(xs, 0.0) == 1.0       # min
        assert stats.quantile(xs, 1.0) == 5.0       # max
        assert stats.quantile(xs, 0.5) == 3.0       # odd-length median

    def test_interpolation_matches_numpy(self):
        rng = np.random.default_rng(7)
        xs = rng.exponential(1.0, size=257).tolist()
        for q in (0.0, 0.1, 0.5, 0.9, 0.95, 0.99, 1.0):
            assert stats.quantile(xs, q) == pytest.approx(
                float(np.percentile(np.asarray(xs), q * 100)), rel=1e-12)

    def test_single_element_and_pair(self):
        assert stats.quantile([2.5], 0.95) == 2.5
        # even-length median interpolates halfway
        assert stats.quantile([1.0, 2.0], 0.5) == 1.5

    def test_empty_and_bad_q_raise(self):
        with pytest.raises(ValueError):
            stats.quantile([], 0.5)
        with pytest.raises(ValueError):
            stats.quantile([1.0], 1.5)

    def test_quantiles_ms_keys_and_units(self):
        out = stats.quantiles_ms([0.010, 0.020, 0.030])
        assert set(out) == {"p50", "p95", "p99"}
        assert out["p50"] == 20.0
        assert stats.quantiles_ms([]) == {}

    def test_engine_metrics_uses_shared_quantile(self):
        # The p95 the engine snapshot reports must be the SAME statistic
        # as the client-side report (numpy linear interpolation).
        from kubeflow_tpu.serve.engine import EngineMetrics

        m = EngineMetrics()
        for v in (0.1, 0.2, 0.3, 0.4):
            m.observe_queue_delay(v)
        snap = m.snapshot()
        assert snap["queue_delay_p95_ms"] == pytest.approx(
            stats.quantile([0.1, 0.2, 0.3, 0.4], 0.95) * 1e3)


# -- schedule determinism ------------------------------------------------------

class TestScheduleDeterminism:
    SC = Scenario(name="det", num_requests=40,
                  arrival=Arrival(process="poisson", rate_rps=20.0),
                  prompt_len=LengthDist(kind="lognormal", mu=3.0,
                                        sigma=0.5, low=4, high=64),
                  output_len=LengthDist(kind="uniform", low=2, high=9),
                  qos_mix=(("interactive", 1.0), ("batch", 3.0)),
                  prefix_overlap=0.5, seed=42)

    def test_same_seed_identical_schedule(self):
        a = build_schedule(self.SC, vocab_size=256, max_prompt_len=100)
        b = build_schedule(self.SC, vocab_size=256, max_prompt_len=100)
        assert [(r.t, r.prompt_tokens, r.max_new_tokens, r.qos)
                for r in a] == \
               [(r.t, r.prompt_tokens, r.max_new_tokens, r.qos)
                for r in b]

    def test_different_seed_differs(self):
        import dataclasses

        a = build_schedule(self.SC, vocab_size=256, max_prompt_len=100)
        c = build_schedule(dataclasses.replace(self.SC, seed=43),
                           vocab_size=256, max_prompt_len=100)
        assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in c]

    def test_qos_mix_fractions(self):
        sched = build_schedule(
            Scenario(name="mix", num_requests=800,
                     qos_mix=(("interactive", 1.0), ("batch", 3.0)),
                     seed=3),
            vocab_size=256, max_prompt_len=64)
        frac = sum(1 for r in sched if r.qos == "batch") / len(sched)
        assert abs(frac - 0.75) < 0.05

    def test_unknown_qos_class_rejected(self):
        sc = Scenario(name="bad", qos_mix=(("gold", 1.0),))
        with pytest.raises(ValueError, match="gold"):
            build_schedule(sc, vocab_size=256, max_prompt_len=64)


# -- arrival processes ---------------------------------------------------------

class TestArrivals:
    def test_poisson_mean_interarrival(self):
        rng = np.random.default_rng(0)
        ts = arrival_times(Arrival(process="poisson", rate_rps=50.0),
                           1500, rng)
        gaps = np.diff(ts)
        assert abs(float(np.mean(gaps)) - 1 / 50.0) < 0.1 / 50.0
        assert all(g >= 0 for g in gaps)

    def test_uniform_exact_spacing(self):
        rng = np.random.default_rng(0)
        ts = arrival_times(Arrival(process="uniform", rate_rps=10.0),
                           5, rng)
        assert ts == pytest.approx([0.0, 0.1, 0.2, 0.3, 0.4])

    def test_bursty_depth_and_gap(self):
        rng = np.random.default_rng(0)
        ts = arrival_times(Arrival(process="bursty", rate_rps=10.0,
                                   burst_depth=4), 12, rng)
        # bursts of exactly 4 share one arrival instant...
        assert ts[0:4] == [ts[0]] * 4
        assert ts[4:8] == [ts[4]] * 4
        # ...and the default gap preserves the mean rate (depth/rate).
        assert ts[4] - ts[0] == pytest.approx(0.4)

    def test_ramp_rate_increases(self):
        rng = np.random.default_rng(0)
        ts = arrival_times(Arrival(process="ramp", rate_rps=5.0,
                                   ramp_to_rps=50.0), 1000, rng)
        gaps = np.diff(ts)
        first, second = gaps[:len(gaps) // 2], gaps[len(gaps) // 2:]
        assert float(np.mean(second)) < 0.5 * float(np.mean(first))

    def test_unknown_process_rejected(self):
        with pytest.raises(ValueError, match="unknown arrival"):
            arrival_times(Arrival(process="weibull"), 4,
                          np.random.default_rng(0))


# -- prompt generation ---------------------------------------------------------

class TestPrompts:
    def test_prefix_overlap_measured(self):
        sc = Scenario(name="pfx", num_requests=64, prefix_overlap=0.6,
                      prompt_len=LengthDist(kind="fixed", value=50),
                      seed=1)
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=80)
        got = measured_prefix_overlap([r.prompt_tokens for r in sched])
        assert abs(got - 0.6) < 0.05

    def test_zero_overlap_prompts_unique(self):
        sc = Scenario(name="uniq", num_requests=64, prefix_overlap=0.0,
                      prompt_len=LengthDist(kind="fixed", value=50),
                      seed=1)
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=80)
        assert measured_prefix_overlap(
            [r.prompt_tokens for r in sched]) < 0.05

    def test_length_dist_clipping(self):
        rng = np.random.default_rng(0)
        d = LengthDist(kind="lognormal", mu=10.0, sigma=1.0, low=4,
                       high=1 << 20)
        for _ in range(20):
            assert 4 <= d.sample(rng, 32) <= 32    # cap wins over high

    def test_length_kinds(self):
        rng = np.random.default_rng(0)
        assert LengthDist(kind="fixed", value=7).sample(rng, 100) == 7
        assert LengthDist(kind="choice",
                          choices=(5,)).sample(rng, 100) == 5
        u = LengthDist(kind="uniform", low=3, high=6)
        assert all(3 <= u.sample(rng, 100) <= 6 for _ in range(30))

    def test_standard_matrix_shape(self):
        m = standard_matrix(num_requests=8)
        assert [s.name for s in m] == ["uniform", "bursty_qos",
                                       "shared_prefix",
                                       "mixed_interference",
                                       "multi_adapter", "multi_turn"]
        assert m[2].prefix_overlap == 0.75
        assert dict(m[1].qos_mix).keys() == {"interactive", "batch"}
        assert m[4].adapter_ids and m[4].adapter_skew == 1.0
        assert m[5].turns == 3 and m[5].think_time_s > 0
        for s in m:
            s.validate()

    def test_shared_prefix_overlap_knob(self):
        """The 0.5–0.95 overlap sweep axis: the knob must land on the
        shared_prefix scenario verbatim."""
        for f in (0.5, 0.75, 0.95):
            m = standard_matrix(num_requests=8, shared_prefix_overlap=f)
            sc = next(s for s in m if s.name == "shared_prefix")
            assert sc.prefix_overlap == f

    def test_mixed_interference_correlates_class_and_shape(self):
        """The head-of-line-blocking probe: batch requests carry LONG
        prompts, interactive ones short — per request, not just on
        average (class_profiles correlation)."""
        sc = standard_matrix(num_requests=64, prompt_len=48)[3]
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=400)
        by_cls = {}
        for r in sched:
            by_cls.setdefault(r.qos, []).append(len(r.prompt_tokens))
        assert set(by_cls) == {"interactive", "batch"}
        assert max(by_cls["interactive"]) < min(by_cls["batch"]), \
            "class/shape correlation lost"
        # Determinism holds with profiles active.
        again = build_schedule(sc, vocab_size=256, max_prompt_len=400)
        assert [(r.prompt_tokens, r.qos, r.max_new_tokens)
                for r in sched] == \
               [(r.prompt_tokens, r.qos, r.max_new_tokens)
                for r in again]

    def test_class_profiles_validation(self):
        from kubeflow_tpu.loadgen import LengthDist, Scenario

        bad = Scenario(name="x", class_profiles=(
            ("gold", LengthDist(), LengthDist()),))
        with pytest.raises(ValueError, match="gold"):
            bad.validate()


class TestMultiAdapter:
    def test_zipf_skew_orders_popularity(self):
        """adapter_ids[0] is the hottest tenant under skew > 0; skew 0
        is uniform-ish; every request in an adapter scenario carries an
        id from the declared set; same seed → identical schedule."""
        ids = tuple(f"a{i}" for i in range(8))
        sc = Scenario(name="ma", num_requests=400, adapter_ids=ids,
                      adapter_skew=1.0, seed=3)
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=64)
        counts = {}
        for r in sched:
            assert r.adapter in ids
            counts[r.adapter] = counts.get(r.adapter, 0) + 1
        assert counts["a0"] > counts["a7"] * 2, counts
        again = build_schedule(sc, vocab_size=256, max_prompt_len=64)
        assert [(r.prompt_tokens, r.adapter) for r in sched] == \
               [(r.prompt_tokens, r.adapter) for r in again]

    def test_adapter_free_schedules_unchanged(self):
        """Appending the adapter draw must not perturb historical
        adapter-free schedules (drawn only when adapter_ids is set)."""
        sc = Scenario(name="plain", num_requests=16, seed=5)
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=64)
        assert all(r.adapter is None for r in sched)

    def test_session_mode_pins_adapter_per_session(self):
        sc = Scenario(name="s", num_requests=24, turns=3,
                      adapter_ids=("a0", "a1", "a2"), seed=1)
        sched = build_schedule(sc, vocab_size=256, max_prompt_len=64)
        by_session = {}
        for r in sched:
            by_session.setdefault(r.session, set()).add(r.adapter)
        assert all(len(s) == 1 for s in by_session.values()), \
            "a conversation must not switch tenants mid-flight"

    def test_validation(self):
        with pytest.raises(ValueError, match="unique"):
            Scenario(name="x", adapter_ids=("a", "a")).validate()
        with pytest.raises(ValueError, match="adapter_skew"):
            Scenario(name="x", adapter_ids=("a",),
                     adapter_skew=-1.0).validate()

    def test_per_adapter_report_split(self):
        """Outcomes carrying adapter ids aggregate into the per-adapter
        TTFT/TPOT block (the one-tenant-degrading attribution)."""
        from kubeflow_tpu.loadgen.runner import RequestOutcome, ScenarioRun
        from kubeflow_tpu.loadgen.report import build_report

        outs = []
        for i in range(8):
            aid = f"a{i % 2}"
            outs.append(RequestOutcome(
                idx=i, qos="standard", scheduled_t=0.0, lag_s=0.0,
                ttft_s=0.010 if aid == "a0" else 0.050,
                latency_s=0.1, tokens=8, status="ok", adapter=aid))
        run = ScenarioRun(
            scenario=Scenario(name="ma", num_requests=8,
                              adapter_ids=("a0", "a1")),
            outcomes=outs, wall_s=1.0, schedule=[])
        rep = build_report(run)
        assert set(rep["adapters"]) == {"a0", "a1"}
        assert rep["adapters"]["a0"]["ttft_ms"]["p50"] < \
            rep["adapters"]["a1"]["ttft_ms"]["p50"]
        assert rep["adapters"]["a0"]["requests"] == 4


class TestMultiTurn:
    """Session-mode schedules (Scenario.turns > 1): conversations
    re-arriving with their prior prefix + one new turn — the
    tiered-KV-cache traffic shape."""

    def _sc(self, **kw):
        base = dict(name="mt", num_requests=12, turns=3, think_time_s=0.1,
                    arrival=Arrival(process="poisson", rate_rps=4.0),
                    prompt_len=LengthDist(kind="fixed", value=24),
                    output_len=LengthDist(kind="fixed", value=4), seed=3)
        base.update(kw)
        return Scenario(**base)

    def test_session_structure(self):
        sched = build_schedule(self._sc(), vocab_size=256,
                               max_prompt_len=64)
        assert len(sched) == 12            # 4 sessions x 3 turns
        by_session: dict = {}
        for sr in sched:
            by_session.setdefault(sr.session, []).append(sr)
        assert len(by_session) == 4
        for turns in by_session.values():
            turns.sort(key=lambda r: r.turn)
            assert [r.turn for r in turns] == [0, 1, 2]
            assert turns[0].prev_idx is None and turns[0].think_s == 0.0
            for prev, cur in zip(turns, turns[1:]):
                assert cur.prev_idx == prev.idx
                assert cur.think_s == 0.1
                assert cur.t >= prev.t
                # one QoS class per conversation
                assert cur.qos == prev.qos

    def test_new_turns_are_short(self):
        sched = build_schedule(self._sc(), vocab_size=256,
                               max_prompt_len=64)
        first = [len(r.prompt_tokens) for r in sched if r.turn == 0]
        later = [len(r.prompt_tokens) for r in sched if r.turn > 0]
        assert max(later) < min(first)

    def test_session_schedule_deterministic(self):
        a = build_schedule(self._sc(), vocab_size=256, max_prompt_len=64)
        b = build_schedule(self._sc(), vocab_size=256, max_prompt_len=64)
        assert [(r.t, r.prompt_tokens, r.session, r.turn, r.prev_idx)
                for r in a] == \
               [(r.t, r.prompt_tokens, r.session, r.turn, r.prev_idx)
                for r in b]

    def test_think_validation(self):
        with pytest.raises(ValueError, match="turns"):
            self._sc(turns=0).validate()
        with pytest.raises(ValueError, match="think"):
            self._sc(think_time_s=-1.0).validate()

    def test_engine_run_composes_conversation(self):
        """E2E on a paged radix engine: every turn past the first must
        ride the conversation prefix — the radix index reports reused
        tokens, and all turns complete."""
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.models.decoder import init_decoder_params
        from kubeflow_tpu.serve.engine import LLMEngine

        cfg = preset("tiny", vocab_size=512)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        engine = LLMEngine(
            cfg, BatchingSpec(max_batch_size=4, max_seq_len=128,
                              paged=True, page_size=16,
                              chunked_prefill_tokens=16, decode_steps=4),
            params=params)
        engine.start()
        try:
            sc = self._sc(num_requests=6, turns=3, think_time_s=0.01,
                          prompt_len=LengthDist(kind="fixed", value=20),
                          request_timeout_s=60.0)
            run = run_scenario(EngineTarget(engine), sc, vocab_size=256,
                               max_prompt_len=64)
            assert all(o.ok for o in run.outcomes), \
                [(o.idx, o.status) for o in run.outcomes]
            tier = engine.kv_tier_stats()
            assert tier["prefix_hits"] >= 4      # every later turn hits
            assert tier["tokens_matched"] > 0
            deadline = time.time() + 20.0
            while engine.kv_pages_in_use() > 0 and time.time() < deadline:
                time.sleep(0.05)
            assert engine.kv_pages_in_use() == 0
            engine._allocator.assert_quiescent()
        finally:
            engine.stop()


# -- the threshold gate --------------------------------------------------------

def _row(name, req_s, ttft_p95, **extra):
    row = {"scenario": name, "req_s": req_s,
           "ttft_ms": {"p50": ttft_p95 / 2, "p95": ttft_p95}}
    row.update(extra)
    return row


class TestGate:
    def test_req_s_regression_flagged(self):
        out = compare_scenario(_row("u", 10.0, 50.0),
                               _row("u", 7.0, 50.0), band_pct=20.0)
        assert out and "req/s" in out[0]

    def test_ttft_regression_flagged_with_floor(self):
        out = compare_scenario(_row("u", 10.0, 50.0),
                               _row("u", 10.0, 90.0), band_pct=20.0)
        assert out and "ttft" in out[0]
        # under the absolute floor, a huge relative move is noise
        out = compare_scenario(_row("u", 10.0, 0.5),
                               _row("u", 10.0, 2.0), band_pct=20.0,
                               ttft_floor_ms=5.0)
        assert out == []

    def test_within_band_clean(self):
        assert compare_scenario(_row("u", 10.0, 50.0),
                                _row("u", 9.0, 55.0), band_pct=20.0) == []

    def test_matrix_coverage_drift(self):
        verdict = compare_matrix([_row("a", 1, 1), _row("b", 1, 1)],
                                 [_row("a", 1, 1)], band_pct=10.0)
        assert not verdict["ok"]
        assert any("'b'" in c for c in verdict["coverage"])

    def test_matrix_attribution_diff_attached(self):
        base = _row("u", 10.0, 50.0,
                    engine={"queue_delay_p95_ms": 3.0},
                    phases={"queued_ms": {"p50": 1}})
        cand = _row("u", 4.0, 500.0,
                    engine={"queue_delay_p95_ms": 400.0},
                    phases={"queued_ms": {"p50": 300}})
        verdict = compare_matrix([base], [cand], band_pct=15.0)
        assert not verdict["ok"]
        diff = verdict["regressions"][0]["diff"]
        assert diff["engine"]["candidate"]["queue_delay_p95_ms"] == 400.0
        assert diff["engine"]["baseline"]["queue_delay_p95_ms"] == 3.0

    def test_noise_band_floor_and_cap(self):
        assert noise_band_pct([1.0]) == 10.0          # floor
        assert noise_band_pct([20.0]) == 40.0         # 2x spread
        assert noise_band_pct([90.0]) == 60.0         # cap
        assert spread_pct(10.0, 8.0) == pytest.approx(20.0)
        assert spread_pct(0.0, 0.0) == 0.0

    def test_matrix_requires_band(self):
        with pytest.raises(ValueError, match="noise band"):
            compare_matrix([_row("a", 1, 1)], [_row("a", 1, 1)])


# -- end-to-end against a real engine ------------------------------------------

@pytest.fixture(scope="module")
def scenario_engine():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = preset("tiny", vocab_size=512)
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg, BatchingSpec(max_batch_size=4, max_seq_len=128,
                          page_size=16, chunked_prefill_tokens=32,
                          decode_steps=4),
        params=params)
    engine.start()
    yield engine, cfg
    engine.stop()


class TestEndToEnd:
    def test_scenario_run_full_attribution(self, scenario_engine):
        engine, cfg = scenario_engine
        from kubeflow_tpu.serve.server import serving_metrics_registry

        sc = Scenario(
            name="e2e", num_requests=8,
            arrival=Arrival(process="poisson", rate_rps=30.0),
            prompt_len=LengthDist(kind="fixed", value=12),
            output_len=LengthDist(kind="fixed", value=4),
            qos_mix=(("interactive", 1.0), ("batch", 1.0)),
            slo_ttft_ms=60_000.0, request_timeout_s=60.0, seed=5)
        run = run_scenario(EngineTarget(engine), sc,
                           vocab_size=cfg.vocab_size, max_prompt_len=100,
                           tracer=TRACER)
        assert len(run.outcomes) == 8
        assert all(o.status == "ok" for o in run.outcomes)
        text = serving_metrics_registry([("e2e", engine)]).render()
        rep = build_report(run, metrics_text=text, tracer=TRACER)
        assert rep["req_s"] > 0
        assert rep["ttft_ms"]["p95"] > 0
        assert rep["goodput"]["ratio"] == 1.0
        # engine attribution joined off the real exposition
        assert rep["engine"]["requests_completed"] >= 8
        assert "queue_delay_p95_ms" in rep["engine"]
        assert {"interactive", "batch"} <= set(rep["engine"]["qos"])
        # per-phase span breakdown covers every traced request
        assert rep["phases"]["trace_coverage"] == 8
        assert rep["phases"]["decode_ms"]["p95"] > 0
        # quiescence: a full scenario run leaves no open spans
        assert TRACER.open_spans() == 0

    def test_overload_shed_reported(self, scenario_engine):
        engine, cfg = scenario_engine
        engine.max_queue, old = 2, engine.max_queue
        try:
            sc = Scenario(
                name="overload", num_requests=16,
                arrival=Arrival(process="bursty", rate_rps=100.0,
                                burst_depth=16),
                prompt_len=LengthDist(kind="fixed", value=12),
                output_len=LengthDist(kind="fixed", value=4),
                request_timeout_s=60.0, seed=6)
            run = run_scenario(EngineTarget(engine), sc,
                               vocab_size=cfg.vocab_size,
                               max_prompt_len=100, tracer=TRACER)
            rep = build_report(run, tracer=TRACER)
            assert rep["by_status"].get("shed", 0) >= 1
            assert rep["goodput"]["ratio"] < 1.0    # sheds count offered
            assert TRACER.open_spans() == 0
        finally:
            engine.max_queue = old

    def test_report_registry_lints_and_parses(self, scenario_engine):
        from kubeflow_tpu.obs.registry import parse_exposition

        reports = [
            {"scenario": "a", "requests": 4, "by_status": {"ok": 4},
             "req_s": 2.0, "offered_req_s": 2.5,
             "ttft_ms": {"p50": 5.0, "p95": 9.0},
             "tpot_ms": {"p50": 1.0},
             "goodput": {"ratio": 1.0, "slo_ttft_ms": 100.0},
             "schedule_lag_ms": {"p50": 0.1, "p95": 0.4}},
            {"scenario": "b", "requests": 4,
             "by_status": {"ok": 2, "shed": 2}, "req_s": 1.0,
             "offered_req_s": 2.5, "ttft_ms": {}, "tpot_ms": {}},
        ]
        reg = report_registry(reports)
        assert reg.lint() == []
        samples = parse_exposition(reg.render())
        by_name = {}
        for name, labels, value in samples:
            by_name.setdefault(name, {})[labels.get("scenario")] = value
        assert by_name["kftpu_loadgen_requests_total"]["a"] == 4
        assert by_name["kftpu_loadgen_requests_failed_total"]["b"] == 2
        assert by_name["kftpu_loadgen_ttft_p95_ms"]["a"] == 9.0
        assert by_name["kftpu_loadgen_goodput_ratio"]["a"] == 1.0

    def test_attribution_series_all_produced(self, scenario_engine):
        """The loadgen's scrape set must exist in a REAL rendered
        exposition — the producer half of the contract the X7xx lint
        checks statically (a renamed engine series fails here even if
        the AST extraction drifts). Two producers: the model server's
        registry (engine/serving series) and the fleet observability
        registry (kftpu_fleet_*/kftpu_obs_* — obs/fleet.py)."""
        engine, cfg = scenario_engine
        from kubeflow_tpu.obs.fleet import (
            FleetTraceCollector, MetricsHistory, fleet_obs_registry,
        )
        from kubeflow_tpu.obs.registry import parse_exposition
        from kubeflow_tpu.serve.engine import SamplingParams
        from kubeflow_tpu.serve.server import serving_metrics_registry

        # The host-gap quantiles are rendered once a round has been
        # dispatched behind another with no admission between them: a
        # stream of a few rounds, whatever the scenarios above left.
        engine.submit([5, 6, 7], SamplingParams(max_new_tokens=24,
                                                temperature=0.0)).result(60)
        text = serving_metrics_registry([("pin", engine)]).render()
        names = {n for n, _, _ in parse_exposition(text)}
        fleet = fleet_obs_registry(collector=FleetTraceCollector(),
                                   history=MetricsHistory()).render()
        names |= {n for n, _, _ in parse_exposition(fleet)}
        missing = [s for s in ATTRIBUTION_SERIES if s not in names]
        assert not missing, f"attribution series not rendered: {missing}"


# -- trace phase rollups -------------------------------------------------------

class TestPhases:
    def _spans(self):
        return [
            {"name": "engine.queued", "duration_ms": 4.0},
            {"name": "engine.queued", "duration_ms": 1.0},   # requeue
            {"name": "engine.prefill", "duration_ms": 10.0},
            {"name": "engine.decode", "duration_ms": 30.0},
            {"name": "server.request", "duration_ms": 50.0},
            {"name": "engine.decode", "duration_ms": None},  # still open
        ]

    def test_phase_durations_sums_per_phase(self):
        from kubeflow_tpu.obs.trace import phase_durations

        ph = phase_durations(self._spans())
        assert ph == {"queued_ms": 5.0, "prefill_ms": 10.0,
                      "decode_ms": 30.0}

    def test_debug_payload_carries_phases(self):
        from kubeflow_tpu.obs.trace import debug_traces_payload

        t = Tracer()
        with t.span("server.request") as root:
            sp = t.start_span("engine.queued", parent=root)
            sp.end()
            sp = t.start_span("engine.decode", parent=root)
            sp.end()
        doc = debug_traces_payload("/debug/traces?slowest=2", tracer=t)
        assert doc["traces"][0]["phases"].keys() == {"queued_ms",
                                                     "decode_ms"}

    def test_format_dump_prints_phase_rollup(self):
        from kubeflow_tpu.obs.trace import debug_traces_payload, format_dump

        t = Tracer()
        with t.span("server.request") as root:
            sp = t.start_span("engine.decode", parent=root)
            sp.end()
        doc = debug_traces_payload("/debug/traces", tracer=t)
        out = format_dump(doc)
        assert "decode=" in out and "ms]" in out

    def test_no_engine_spans_no_phase_key(self):
        from kubeflow_tpu.obs.trace import debug_traces_payload

        t = Tracer()
        with t.span("pipeline.run"):
            pass
        doc = debug_traces_payload("/debug/traces", tracer=t)
        assert "phases" not in doc["traces"][0]


def test_tokens_to_text_preserves_structure():
    from kubeflow_tpu.loadgen import tokens_to_text

    a = tokens_to_text((1, 2, 3, 4))
    b = tokens_to_text((1, 2, 9, 9))
    assert len(a) == 4
    assert a[:2] == b[:2] and a[2:] != b[2:]
    assert math.isfinite(len(a))
