"""Decode hot-loop host-overhead elimination (ISSUE 4): device-resident
scheduler state + pipelined double-buffered dispatch.

Contracts pinned here:
- greedy outputs are TOKEN-IDENTICAL with pipelining on and off, across
  plain and speculative engines (the pipeline must be invisible to
  outputs — only latency moves);
- steady-state decode rounds perform ZERO full-array host→device uploads
  of scheduler state (counter-asserted: the device_state stats stay at
  their construction values while rounds accumulate, and per-slot syncs
  stay flat across decode-only rounds);
- the one-round staleness contract is bounded: a cancellation decided
  while a round is in flight masks that round's results — output streams
  never contain post-cancel tokens — and paged-KV refcounts balance;
- first-token sampling batches per admit round (one fetch for N
  admissions);
- EngineMetrics surfaces host_gap/dispatch_depth and the model server
  exposes them on /metrics.
"""

import time

import pytest
import jax

from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


PROMPTS = [[5, 17, 3, 99, 42], list(range(1, 50)), [7] * 20,
           [9, 8, 7, 6, 5, 4]]


def make_engine(cfg, params, *, pipelined, paged=True, spec=None,
                chunk=32, decode_steps=4, slots=4):
    return LLMEngine(cfg, BatchingSpec(
        max_batch_size=slots, max_seq_len=128, chunked_prefill_tokens=chunk,
        paged=paged, page_size=16,
        decode_steps=decode_steps, pipelined_decode=pipelined,
        speculative=spec or SpeculativeSpec()), params=params)


def run_all(eng, reqs, max_steps=1200):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def gen_all(eng, prompts, max_new=12):
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    reqs = [eng.submit(list(p), sp) for p in prompts]
    run_all(eng, reqs)
    return [list(r.output_tokens) for r in reqs]


class TestTokenIdentity:
    """Pipelining on vs off must be invisible to greedy outputs on every
    engine flavor (the acceptance-criteria core)."""

    @pytest.fixture(scope="class")
    def want(self, cfg, params):
        return gen_all(make_engine(cfg, params, pipelined=False), PROMPTS)

    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~11s; test_spec_paged
    # keeps a fast pipelined-vs-off paged identity check in this class
    def test_paged(self, cfg, params, want):
        off = make_engine(cfg, params, pipelined=False, paged=True)
        on = make_engine(cfg, params, pipelined=True, paged=True)
        assert gen_all(off, PROMPTS) == want
        assert gen_all(on, PROMPTS) == want
        assert on.kv_pages_in_use() == 0

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_spec_ngram(self, cfg, params, want):
        spec = SpeculativeSpec(mode="ngram", k=4)
        off = make_engine(cfg, params, pipelined=False, spec=spec)
        on = make_engine(cfg, params, pipelined=True, spec=spec)
        assert gen_all(off, PROMPTS) == want
        assert gen_all(on, PROMPTS) == want

    def test_spec_paged(self, cfg, params, want):
        spec = SpeculativeSpec(mode="ngram", k=4)
        eng = make_engine(cfg, params, pipelined=True, paged=True,
                          spec=spec)
        assert gen_all(eng, PROMPTS) == want
        assert eng.kv_pages_in_use() == 0

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_staggered_admissions(self, cfg, params):
        """Requests joining while rounds are in flight (the one-round-late
        admission path) still decode exactly."""
        def staggered(eng):
            sp = SamplingParams(max_new_tokens=10, temperature=0.0)
            reqs = [eng.submit(list(PROMPTS[0]), sp),
                    eng.submit(list(PROMPTS[1]), sp)]
            for _ in range(2):
                eng.step()
            reqs += [eng.submit(list(PROMPTS[2]), sp),
                     eng.submit(list(PROMPTS[3]), sp)]
            run_all(eng, reqs)
            return [list(r.output_tokens) for r in reqs]

        out_off = staggered(make_engine(cfg, params, pipelined=False))
        out_on = staggered(make_engine(cfg, params, pipelined=True))
        assert out_on == out_off


class TestDeviceResidentState:
    """Tentpole (a): the scheduler state uploads ONCE, at construction;
    everything after is per-slot deltas — and decode-only rounds sync
    nothing at all."""

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_full_uploads_stay_at_construction(self, cfg, params):
        eng = make_engine(cfg, params, pipelined=True, paged=True)
        gen_all(eng, PROMPTS)
        rounds1 = eng.decode_rounds
        stats1 = dict(eng._dstate.stats)
        assert rounds1 > 0
        assert stats1["full_state_uploads"] == 1
        assert stats1["full_table_uploads"] == 1
        gen_all(eng, PROMPTS)
        stats2 = eng._dstate.stats
        assert eng.decode_rounds > rounds1
        assert stats2["full_state_uploads"] == 1
        assert stats2["full_table_uploads"] == 1

    def test_steady_state_rounds_sync_nothing(self, cfg, params):
        """Mid-generation decode rounds (no admissions, no reaps) must not
        scatter any slot state — the device carry is authoritative."""
        eng = make_engine(cfg, params, pipelined=True, decode_steps=2)
        req = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=40))
        for _ in range(4):
            eng.step()          # admit + enter steady decode
        assert not req.done.is_set()
        syncs_before = eng._dstate.stats["slot_syncs"]
        rounds_before = eng.decode_rounds
        for _ in range(5):
            eng.step()
        assert not req.done.is_set()
        assert eng.decode_rounds > rounds_before
        assert eng._dstate.stats["slot_syncs"] == syncs_before
        run_all(eng, [req])

    def test_steady_state_rounds_send_no_program(self, cfg, params):
        """Rounds between two page boundaries, nobody admitted or reaped:
        no upload and no sync program, under the guard that refuses any
        implicit transfer."""
        eng = make_engine(cfg, params, pipelined=True, decode_steps=1)
        req = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=40))
        for _ in range(4):
            eng.step()
        before = dict(eng._dstate.stats)
        rounds_before = eng.decode_rounds
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            for _ in range(5):          # lengths 7..12: inside page 0
                eng.step()
        assert not req.done.is_set()
        assert eng.decode_rounds > rounds_before
        assert eng._dstate.stats == before
        run_all(eng, [req])

    @pytest.mark.parametrize("spec", [None, "ngram"])
    def test_a_syncing_round_sends_one_program(self, cfg, params, spec):
        """Whatever a round dirtied (four admissions at once: four slots
        and four rows) goes in ONE program: as many programs as rounds that
        synced, fewer than the items they carried, on the plain path and
        through the speculative verify's sync of its rows; the tokens are
        the unpipelined plain engine's."""
        want = gen_all(make_engine(cfg, params, pipelined=False), PROMPTS)
        eng = make_engine(
            cfg, params, pipelined=True,
            spec=spec and SpeculativeSpec(mode=spec, k=4))
        assert eng.counters()["state_sync_dispatches"] == 0
        assert gen_all(eng, PROMPTS) == want
        c = eng.counters()
        assert c["state_sync_dispatches"] == c["state_sync_rounds"] > 0
        assert c["state_sync_dispatches"] \
            == eng._dstate.stats["sync_dispatches"]
        assert c["state_slot_syncs"] + c["state_row_syncs"] \
            > c["state_sync_dispatches"]
        assert eng._dstate.stats["full_state_uploads"] == 1
        assert eng._dstate.stats["full_table_uploads"] == 1

    def test_paged_growth_is_row_deltas(self, cfg, params):
        """Page-table growth mid-decode costs row scatters, never a full
        table upload."""
        eng = make_engine(cfg, params, pipelined=True, paged=True,
                          decode_steps=4)
        gen_all(eng, [[2, 3, 4]], max_new=60)   # grows across pages
        stats = eng._dstate.stats
        assert stats["full_table_uploads"] == 1
        assert stats["table_row_syncs"] > 0


class TestPipelinedCancellation:
    """The staleness contract's hard edge: results of a round dispatched
    before the cancel must never reach the stream."""

    def _drain_stream(self, req):
        toks = []
        while True:
            t = req.stream.get(timeout=5)
            if t is None:
                return toks
            toks.append(t)

    @pytest.mark.parametrize("paged", [True], ids=["paged"])
    def test_cancel_mid_flight_emits_nothing_after(self, cfg, params,
                                                   paged):
        eng = make_engine(cfg, params, pipelined=True, paged=paged,
                          decode_steps=4)
        req = eng.submit([4, 5, 6, 7], SamplingParams(max_new_tokens=100))
        for _ in range(3):
            eng.step()          # a round is now in flight past the cancel
        assert not req.done.is_set()
        assert eng._rounds, "pipelining should keep a round in flight"
        emitted_at_cancel = len(req.output_tokens)
        req.cancel()
        for _ in range(6):
            eng.step()
        assert req.done.is_set()
        assert req.finish_reason == "cancelled"
        assert len(req.output_tokens) == emitted_at_cancel, \
            "post-cancel tokens leaked into the output"
        streamed = self._drain_stream(req)
        assert streamed == req.output_tokens
        assert eng.kv_pages_in_use() == 0
        eng._allocator.assert_quiescent()

    def test_deadline_mid_flight_frees_pages(self, cfg, params):
        eng = make_engine(cfg, params, pipelined=True, paged=True,
                          decode_steps=4)
        req = eng.submit([9, 9, 9], SamplingParams(max_new_tokens=100),
                         deadline=time.monotonic() + 0.03)
        eng.step()
        time.sleep(0.05)
        for _ in range(8):
            eng.step()
        assert req.done.is_set() and req.finish_reason == "deadline"
        assert eng.kv_pages_in_use() == 0
        eng._allocator.assert_quiescent()

    def test_slot_reuse_after_mid_flight_cancel_is_clean(self, cfg, params):
        """A slot freed by a mid-flight cancel and immediately re-admitted
        must serve the newcomer untainted (its in-flight garbage KV is
        overwritten before ever being attended)."""
        want = gen_all(make_engine(cfg, params, pipelined=False),
                       [[11, 12, 13]], max_new=10)[0]
        eng = make_engine(cfg, params, pipelined=True, slots=1,
                          decode_steps=4)
        victim = eng.submit([4, 5, 6, 7], SamplingParams(max_new_tokens=80))
        for _ in range(3):
            eng.step()
        victim.cancel()
        fresh = eng.submit([11, 12, 13], SamplingParams(max_new_tokens=10))
        run_all(eng, [victim, fresh])
        assert victim.finish_reason == "cancelled"
        assert list(fresh.output_tokens) == want


class TestFirstTokenBatching:
    """Satellite: first-token fetches batch per admit round — one sampler
    dispatch + one device_get for every admission in the pass."""

    def test_chunked_completions_share_one_fetch(self, cfg, params):
        eng = make_engine(cfg, params, pipelined=True, chunk=16, slots=4)
        eng.max_concurrent_prefills = 3
        sp = SamplingParams(max_new_tokens=4, temperature=0.0)
        # Three same-length long prompts chunk in lockstep and complete in
        # the same admit pass.
        reqs = [eng.submit([i + 1] * 33, sp) for i in range(3)]
        before = eng.first_token_fetches
        while not all(r.first_token_time is not None for r in reqs):
            eng.step()
        assert eng.first_token_fetches == before + 1
        run_all(eng, reqs)

    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~8s; the one-fetch
    # accounting stays fast via test_chunked_completions_share_one_fetch
    def test_batched_first_tokens_match_reference(self, cfg, params):
        """The batched sampler path must not perturb greedy outputs."""
        want = gen_all(make_engine(cfg, params, pipelined=False),
                       PROMPTS, max_new=6)
        eng = make_engine(cfg, params, pipelined=True, chunk=16)
        assert gen_all(eng, PROMPTS, max_new=6) == want


class TestPrefillBudget:
    """ISSUE 34: one prefill program a decode step while a stream is live.
    What the pacer samples is decided by what an iteration SENT, as before:
    one that deferred a chunk sent a program and is no sample of the host's
    time; one whose prefills could not get pages sent none and is one."""

    def test_a_sample_unless_the_iteration_sent_a_prefill_program(
            self, cfg, params, monkeypatch):
        eng = make_engine(cfg, params, pipelined=True, decode_steps=1)
        eng.max_concurrent_prefills = 3     # two rows a program: one waits
        assert eng._plan.rows == 2
        samples = []
        eng._pacer.note_host = lambda k, seconds: samples.append(
            eng.counters()["prefill_programs_dispatched"])
        live = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=60,
                                                    temperature=0.0))
        while live.first_token_time is None:
            eng.step()
        for _ in range(3):
            eng.step()
        assert samples and eng._prefill_budget() == 1
        sp = SamplingParams(max_new_tokens=3, temperature=0.0)
        reqs = [eng.submit([i + 1] * 70, sp) for i in range(3)]
        before, n = eng.counters(), len(samples)
        eng.step()
        eng.step()
        c = eng.counters()
        assert c["prefill_chunks_deferred"] - before[
            "prefill_chunks_deferred"] == 2
        assert c["prefill_passes"] - before["prefill_passes"] == 2
        assert len(samples) == n            # both sent a program: no sample
        # No page for any prefill: they keep their lanes, nothing is sent.
        ensure = eng._ensure_pages
        held = {ch.slot for ch in eng._chunkings}
        assert len(held) == 3
        monkeypatch.setattr(
            eng, "_ensure_pages",
            lambda slot, upto: slot not in held and ensure(slot, upto))
        eng.step()
        monkeypatch.setattr(eng, "_ensure_pages", ensure)
        after = eng.counters()
        assert after["prefill_passes"] == c["prefill_passes"]
        # ... and no budget is spent: all three had their turn
        assert after["prefill_chunks_deferred"] == c["prefill_chunks_deferred"]
        assert len(samples) == n + 1 and samples[-1] == c[
            "prefill_programs_dispatched"]
        run_all(eng, [live, *reqs])


class TestTransferGuard:
    """Runtime half of the static device-hygiene rules (ISSUE 5): the
    engine's transfer contract is that every steady-state host<->device
    move is EXPLICIT (device_put at the sync sites, device_get at the
    designed fetch points). Proven by running mid-generation decode
    rounds under ``jax.transfer_guard("disallow")`` — an implicit
    transfer anywhere raises — on plain and speculative engines, and by the
    ``KFTPU_SANITIZE=1`` mode that wires the same guard inside step()."""

    def _steady_state_under_guard(self, eng, warmup=6, guarded=5):
        sp = SamplingParams(max_new_tokens=60, temperature=0.0)
        req = eng.submit([3, 1, 4, 1, 5], sp)
        for _ in range(warmup):
            eng.step()          # admit + first token + enter steady decode
        assert not req.done.is_set()
        rounds_before = eng.decode_rounds
        with jax.transfer_guard("disallow"):
            for _ in range(guarded):
                eng.step()
        assert eng.decode_rounds > rounds_before
        run_all(eng, [req])
        return req

    def test_paged_steady_state(self, cfg, params):
        eng = make_engine(cfg, params, pipelined=True, paged=True)
        self._steady_state_under_guard(eng)
        assert eng.kv_pages_in_use() == 0

    def test_spec_steady_state(self, cfg, params):
        spec = SpeculativeSpec(mode="ngram", k=4)
        self._steady_state_under_guard(
            make_engine(cfg, params, pipelined=True, spec=spec))

    @pytest.mark.slow  # tier-1 budget (ISSUE 12): >10s on the gate host
    def test_sanitize_mode_token_identity(self, cfg, params, monkeypatch):
        """KFTPU_SANITIZE=1 engines guard every decode pass themselves and
        still produce reference greedy outputs on every flavor."""
        want = gen_all(make_engine(cfg, params, pipelined=False), PROMPTS)
        monkeypatch.setenv("KFTPU_SANITIZE", "1")
        for kw in ({"paged": True},
                   {"spec": SpeculativeSpec(mode="ngram", k=4)}):
            eng = make_engine(cfg, params, pipelined=True, **kw)
            assert eng.sanitize
            assert gen_all(eng, PROMPTS) == want

    def test_sanitize_mode_off_by_default(self, cfg, params, monkeypatch):
        monkeypatch.delenv("KFTPU_SANITIZE", raising=False)
        assert not make_engine(cfg, params, pipelined=True).sanitize
        monkeypatch.setenv("KFTPU_SANITIZE", "0")
        assert not make_engine(cfg, params, pipelined=True).sanitize


class TestHotLoopMetrics:
    """Satellite: host_gap + dispatch_depth in EngineMetrics.snapshot()
    and on /metrics through the PR 3 registry."""

    def test_snapshot_has_host_gap_and_depth(self, cfg, params):
        for pipelined, want_depth in ((False, 0), (True, 1)):
            eng = make_engine(cfg, params, pipelined=pipelined)
            gen_all(eng, [[2] * 6], max_new=30)
            snap = eng.metrics.snapshot()
            assert snap["dispatch_depth"] == want_depth
            assert "host_gap_seconds" in snap
            assert snap["host_gap_p50_ms"] >= 0.0
            assert snap["host_gap_p99_ms"] >= snap["host_gap_p50_ms"]
            buckets, counts, total, n = eng.metrics.host_gap_histogram()
            assert n > 0 and sum(counts) == n
            assert total >= 0.0
            if pipelined:
                # Steady-state pipelined rounds have zero host gap by
                # construction — the distribution must reflect it.
                assert snap["host_gap_p50_ms"] == 0.0

    def test_metrics_endpoint_series(self, cfg, params):
        from kubeflow_tpu.obs.registry import parse_exposition
        from kubeflow_tpu.serve.server import ModelServer

        eng = make_engine(cfg, params, pipelined=True)
        gen_all(eng, [[2] * 6], max_new=20)
        srv = ModelServer("hotloop", eng, port=0)
        try:
            names = {n for n, _, _ in parse_exposition(srv.metrics_text())}
        finally:
            srv.httpd.server_close()
        for need in ("kftpu_engine_host_gap_seconds_bucket",
                     "kftpu_engine_host_gap_seconds_sum",
                     "kftpu_engine_host_gap_seconds_count",
                     "kftpu_engine_dispatch_depth"):
            assert need in names, f"missing {need}"

    def test_decode_span_host_gap_attribute(self, cfg, params):
        from kubeflow_tpu.obs.trace import get_tracer

        tracer = get_tracer()
        eng = make_engine(cfg, params, pipelined=True)
        sp = SamplingParams(max_new_tokens=40, temperature=0.0)
        with tracer.span("test.root") as root:
            req = eng.submit([3, 1, 4], sp, trace_parent=root)
            run_all(eng, [req])
        tr = tracer.trace(root.trace_id)
        gaps = []
        for s in tr["spans"]:
            if s["name"] != "engine.decode":
                continue
            for ev in s.get("events", []):
                if ev["name"] == "decode_round" and "host_gap_ms" in ev:
                    gaps.append(ev["host_gap_ms"])
        assert gaps, "no decode_round event carried host_gap_ms"
        assert all(isinstance(g, float) and g >= 0.0 for g in gaps)
