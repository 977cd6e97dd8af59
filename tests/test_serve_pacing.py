"""The length of a decode round is the scheduler's choice (ISSUE 31), on the
CPU: the choice as a pure function, the two running averages it is made
from, and the engine's side: the ladder is compiled when the engine is
built, a round's length follows the measurements under the two options as
caps, greedy tokens are the same at any length, and the counters count it.

An engine's measurements are set here through its seam, ``LLMEngine._pacer``
(``pin``): a CPU run's own times say nothing about a chip's."""

import jax
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.runtime.sanitize import (
    install_recompile_watchdog, recompile_report,
    uninstall_recompile_watchdog,
)
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.pacing import (
    COVER, COVER_TO_SHORTEN, MIN_SAMPLES, RETRY_ROUNDS, SAMPLES, RoundPacer,
    decode_ladder, host_estimates, round_steps,
)

pytestmark = pytest.mark.paced      # the choice as deployed, not the caps

CHAT = (1, 8, 32)          # the ladder under the defaults 32 / 8
FINE = (1, 2, 4, 8, 32)
STEP = 0.014               # a decode step of the chat cell on a v5e


@pytest.mark.parametrize("decode_steps, interleave, ladder", [
    (32, 8, (1, 8, 32)),    # the defaults, the chat cell
    (1, 1, (1,)),           # both expert cells: nothing to choose
    (4, 8, (1, 4)),         # the smaller cap is the interleave cap too
    (16, 1, (1, 16)),
    (8, 8, (1, 8)),
])
def test_the_ladder_is_one_step_and_the_two_caps(decode_steps, interleave,
                                                 ladder):
    assert decode_ladder(decode_steps, interleave) == ladder


def flat(host_s, ladder=CHAT):
    """A host whose time does not depend on the round (a tunnel)."""
    return dict.fromkeys(ladder, host_s)


@pytest.mark.parametrize("host_s, step_s, cap, ladder, current, want", [
    # the host is faster than a step: one step a round, from any length
    (flat(0.003), STEP, 32, CHAT, 32, 1),
    (flat(0.003), STEP, 8, CHAT, 8, 1),
    (flat(0.003), STEP, 32, CHAT, 1, 1),
    # ... also where its time grows with the round (the emit loop and the
    # handler threads it wakes: a v5e host's chat cell reads this)
    ({1: 0.0056, 8: 0.023, 32: 0.042}, STEP, 32, CHAT, 32, 1),
    ({1: 0.0056, 8: 0.023, 32: 0.042}, STEP, 8, CHAT, 8, 1),
    # the host is slower (a tunnel's 50 ms a dispatch): the SHORTEST length
    # whose device time covers it with the margin, not the cap
    (flat(0.050), STEP, 32, CHAT, 1, 8),
    (flat(0.050), STEP, 32, CHAT, 32, 8),
    (flat(0.050, FINE), STEP, 32, FINE, 1, 8),
    (flat(0.020, FINE), STEP, 32, FINE, 1, 2),
    # nothing covers it: the cap, never more
    (flat(0.500), STEP, 32, CHAT, 1, 32),
    (flat(0.500), STEP, 8, CHAT, 1, 8),
    (flat(0.500), STEP, 4, CHAT, 1, 1),     # one length is under this cap
    # never above the cap in force, whatever was chosen under the other
    (flat(0.200), STEP, 8, CHAT, 32, 8),
    # an operator's 1 / 1: the ladder holds one length
    (flat(0.500, (1,)), STEP, 1, (1,), 1, 1),
    (flat(0.001, (1,)), STEP, 1, (1,), 1, 1),
    # nothing measured yet: the cap, as the options alone would have it
    (flat(None), None, 32, CHAT, 32, 32),
    (flat(0.003), None, 8, CHAT, 32, 8),
    (flat(None), STEP, 32, CHAT, 1, 32),
])
def test_the_shortest_length_that_hides_the_host(host_s, step_s, cap, ladder,
                                                 current, want):
    assert round_steps(host_s, step_s, cap, ladder, current) == want


def test_a_longer_round_at_once_a_shorter_one_on_the_wider_margin():
    assert 1.0 < COVER < COVER_TO_SHORTEN
    # between the two margins the length in force stays
    host_s = flat(STEP / (0.5 * (COVER + COVER_TO_SHORTEN)))
    assert round_steps(host_s, STEP, 32, CHAT, 1) == 1
    assert round_steps(host_s, STEP, 32, CHAT, 8) == 8
    assert round_steps(host_s, STEP, 32, CHAT, 32) == 8   # 8 covers widely
    # past either margin it moves
    assert round_steps(flat(STEP / (0.9 * COVER)), STEP, 32, CHAT, 1) == 8
    assert round_steps(flat(STEP / (1.1 * COVER_TO_SHORTEN)), STEP, 32,
                       CHAT, 8) == 1


@pytest.mark.parametrize("measured, want", [
    ({}, {1: None, 8: None, 32: None}),
    # what is measured stands
    ({1: 0.005, 8: 0.02, 32: 0.04}, {1: 0.005, 8: 0.02, 32: 0.04}),
    # a shorter length that has not run: a longer one's time in proportion
    # (the least it can be); a longer one: at least the shorter's
    ({32: 0.040}, {1: 0.00125, 8: 0.010, 32: 0.040}),
    ({1: 0.050}, {1: 0.050, 8: 0.050, 32: 0.050}),
    # between two measured lengths: the larger of the two bounds
    ({1: 0.050, 32: 0.052}, {1: 0.050, 8: 0.050, 32: 0.052}),
    ({1: 0.001, 32: 0.040}, {1: 0.001, 8: 0.010, 32: 0.040}),
])
def test_a_length_that_has_not_run_is_held_to_the_least_it_can_be(measured,
                                                                  want):
    got = host_estimates(measured, CHAT)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == (want[k] if want[k] is None
                          else pytest.approx(want[k]))


class TestTheSamples:
    def _run(self, pacer, host_of, step_s=STEP, rounds=1, cap=32):
        """``rounds`` scheduler iterations: choose, dispatch, and the host
        time an iteration at the length in force comes back as a sample."""
        chosen = []
        for _ in range(rounds):
            k = pacer.choose(cap)
            pacer.note_host(k, host_of(k))
            pacer.note_step(step_s)
            chosen.append(k)
        return chosen

    def test_nothing_measured_is_the_caps(self):
        pacer = RoundPacer(CHAT)
        assert pacer.step_s is None
        assert pacer.host_s() == {1: None, 8: None, 32: None}
        assert pacer.choose(32) == 32 and pacer.choose(8) == 8

    def test_an_estimate_is_the_median_of_the_last_samples(self):
        pacer = RoundPacer(CHAT)
        for i in range(MIN_SAMPLES - 1):
            pacer.note_host(8, 0.010 + i)
            assert pacer.host_s()[8] is None        # too few to stand
        pacer.note_host(8, 0.010)
        assert pacer.host_s()[8] is not None
        for _ in range(SAMPLES):
            pacer.note_host(8, 0.020)
        assert pacer.host_s()[8] == 0.020           # the old ones are gone
        for x in (0.1, 0.2, 0.3):
            pacer.note_step(x)
        assert pacer.step_s == 0.2

    def test_a_local_chip_comes_down_to_one_step_and_stays(self):
        """The chat cell on a v5e: the host's time grows with the round, so
        what it reads at the caps says nothing against one step."""
        pacer = RoundPacer(CHAT)
        at = {1: 0.0056, 8: 0.023, 32: 0.042}
        chosen = self._run(pacer, at.__getitem__, rounds=200)
        assert chosen[:MIN_SAMPLES] == [32] * MIN_SAMPLES
        assert set(chosen[MIN_SAMPLES:]) == {1}
        assert pacer.host_s()[1] == 0.0056

    def test_a_tunnel_tries_one_step_and_settles_where_the_host_is_hidden(
            self):
        pacer = RoundPacer(CHAT)
        chosen = self._run(pacer, lambda k: 0.050 + 0.0001 * k,
                           rounds=RETRY_ROUNDS // 2)
        # at the cap until it is measured, one step while THAT is measured
        # (the bound said it might do), then eight for good
        assert chosen[:MIN_SAMPLES] == [32] * MIN_SAMPLES
        assert chosen[MIN_SAMPLES:2 * MIN_SAMPLES] == [1] * MIN_SAMPLES
        assert set(chosen[2 * MIN_SAMPLES:]) == {8}

    def test_what_was_measured_at_a_length_is_forgotten_and_tried_again(self):
        pacer = RoundPacer(CHAT)
        chosen = self._run(pacer, lambda k: 0.050, rounds=3 * RETRY_ROUNDS)
        again = [i for i, (a, b) in enumerate(zip(chosen, chosen[1:]))
                 if (a, b) == (8, 1)]
        assert len(again) == 2          # once every RETRY_ROUNDS, no oftener
        assert again[1] - again[0] == RETRY_ROUNDS + MIN_SAMPLES
        assert chosen.count(1) == 3 * MIN_SAMPLES

    def test_one_slow_iteration_does_not_flip_the_choice(self):
        pacer = RoundPacer(CHAT)
        self._run(pacer, lambda k: 0.003 * k, rounds=40)
        assert pacer.k == 1
        for slow in (0.100, 2.0):       # a collection; a first dispatch
            pacer.note_host(1, slow)
            assert self._run(pacer, lambda k: 0.003, rounds=8) == [1] * 8

    def test_a_host_that_stays_slow_does_and_back(self):
        pacer = RoundPacer(CHAT)
        self._run(pacer, lambda k: 0.003, rounds=40)
        chosen = self._run(pacer, lambda k: 0.050, rounds=40)
        assert chosen[0] == 1 and chosen[-1] == 8
        assert chosen == sorted(chosen)         # up once, not back and forth
        assert chosen.index(8) == SAMPLES // 2 + 1   # most samples saw it
        # fast again: one step is tried again when its samples are forgotten
        chosen = self._run(pacer, lambda k: 0.003, rounds=RETRY_ROUNDS + 40)
        assert chosen[0] == 8 and chosen[-1] == 1
        assert chosen == sorted(chosen, reverse=True)


# -- the engine ------------------------------------------------------------------

PROMPTS = [[5, 17, 3, 99, 42], list(range(1, 50)), [7] * 20,
           [9, 8, 7, 6, 5, 4]]
FAST_HOST = (0.001, 0.010)      # (host_s, step_s): one step hides the host
SLOW_HOST = (1.0, 0.001)        # nothing does: the caps


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def make_engine(cfg, params, **kw):
    kw = {"max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
          "chunked_prefill_tokens": 32, "decode_steps": 8,
          "prefill_interleave_steps": 2, **kw}
    return LLMEngine(cfg, BatchingSpec(**kw), params=params)


def pin(eng, host_s, step_s):
    """The seam: the estimates are these at every length, and the engine's
    own samples stop."""
    pacer = eng._pacer
    for name in ("note_host", "note_step"):
        vars(pacer).pop(name, None)
    for _ in range(SAMPLES):
        for k in pacer.ladder:
            pacer.note_host(k, host_s)
        pacer.note_step(step_s)
    pacer.note_host = pacer.note_step = lambda *sample: None


def run_all(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def gen_all(eng, prompts, max_new=20):
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    reqs = [eng.submit(list(p), sp) for p in prompts]
    run_all(eng, reqs)
    return [list(r.output_tokens) for r in reqs]


def steps_a_round(eng, before):
    c = eng.counters()
    return (c["decode_steps_dispatched"] - before["decode_steps_dispatched"]) \
        / (c["decode_rounds"] - before["decode_rounds"])


@pytest.fixture(scope="module")
def want(cfg, params):
    """Today's tokens: every round at its cap (an unpipelined engine
    overlaps nothing, so it is never paced)."""
    return gen_all(make_engine(cfg, params, pipelined_decode=False), PROMPTS)


@pytest.fixture()
def recompile_wd():
    wd = install_recompile_watchdog()
    wd.reset()
    try:
        yield wd
    finally:
        uninstall_recompile_watchdog()


def test_from_the_caps_down_to_one_step_and_back_same_tokens_no_compile(
        cfg, params, want, recompile_wd):
    eng = make_engine(cfg, params)
    assert eng._pacer.ladder == (1, 2, 8)
    recompile_wd.reset()        # the engine is built: count from here
    # Everything but the decode ladder compiles at its first use: one pass
    # at the caps, which is all that traffic reached before this PR.
    pin(eng, *SLOW_HOST)
    before = eng.counters()
    assert gen_all(eng, PROMPTS) == want
    assert steps_a_round(eng, before) > 2.0
    c = eng.counters()
    assert c["decode_rounds_at_cap"] == c["decode_rounds"]
    recompile_wd.mark_warm()

    pin(eng, *FAST_HOST)
    before = eng.counters()
    assert gen_all(eng, PROMPTS) == want
    assert steps_a_round(eng, before) == 1.0
    assert eng.counters()["decode_rounds_at_cap"] == c["decode_rounds"]

    pin(eng, *SLOW_HOST)
    before = eng.counters()
    assert gen_all(eng, PROMPTS) == want
    assert steps_a_round(eng, before) > 2.0
    after = eng.counters()
    assert after["decode_rounds_at_cap"] - before["decode_rounds_at_cap"] \
        == after["decode_rounds"] - before["decode_rounds"]

    rep = recompile_report()
    assert rep["steady_count"] == 0, rep["steady"]
    # the decode program never compiled at a dispatch of the scheduler's
    assert not [e for e in rep["warmup"] if "paged_decode" in e["fn"]], rep
    assert eng.kv_pages_in_use() == 0
    eng._allocator.assert_quiescent()


def test_a_length_between_the_caps_while_a_prefill_is_in_flight(cfg, params):
    """The cap in force changes with the prefills in flight; the choice is
    held under it. Estimates that ask for 8 steps: 2 while a prompt is being
    chunked, 8 after."""
    eng = make_engine(cfg, params)
    pin(eng, 0.005, 0.001)              # 8 x 1 ms covers 1.25 x 5 ms
    sp = SamplingParams(max_new_tokens=60, temperature=0.0)
    first = eng.submit([3, 1, 4], sp)
    while first.first_token_time is None:
        eng.step()
    eng.step()
    assert eng._rounds[-1].k_steps == 8
    second = eng.submit(list(range(1, 100)), sp)    # four chunks of 32
    eng.step()
    assert eng._chunkings and eng._rounds[-1].k_steps == 2
    run_all(eng, [first, second])


def test_an_operators_one_step_is_one_step(cfg, params, want):
    eng = make_engine(cfg, params, decode_steps=1, prefill_interleave_steps=1)
    assert eng._pacer.ladder == (1,)
    pin(eng, *SLOW_HOST)
    assert gen_all(eng, PROMPTS) == want
    c = eng.counters()
    assert c["decode_steps_dispatched"] == c["decode_rounds"] \
        == c["decode_rounds_at_cap"] > 0


def test_a_round_that_overlaps_nothing_runs_at_its_cap(cfg, params, want):
    """An unpipelined engine and the speculative path's fallback consume a
    round at once: no length hides anything, so the estimates are not
    asked."""
    eng = make_engine(cfg, params, pipelined_decode=False)
    pin(eng, *FAST_HOST)
    assert gen_all(eng, PROMPTS) == want
    c = eng.counters()
    assert c["decode_rounds_at_cap"] == c["decode_rounds"] > 0
    spec = make_engine(cfg, params,
                       speculative=SpeculativeSpec(mode="ngram", k=4))
    pin(spec, *FAST_HOST)
    assert gen_all(spec, PROMPTS) == want
    c = spec.counters()
    assert c["decode_rounds_at_cap"] == c["decode_rounds"]


def test_the_engine_measures_itself(cfg, params):
    eng = make_engine(cfg, params)
    assert eng._pacer.step_s is None
    assert set(eng._pacer.host_s().values()) == {None}
    gen_all(eng, PROMPTS, max_new=60)
    # its own time an iteration at the lengths that ran (the others are
    # held to their bound), and a step's from the rounds' spacing
    assert all(0.0 < h < 1.0 for h in eng._pacer.host_s().values())
    assert 0.0 < eng._pacer.step_s < 1.0
    assert eng._pacer.k in eng._pacer.ladder


def test_a_round_behind_a_chunk_is_not_a_sample_of_the_step(cfg, params):
    eng = make_engine(cfg, params)
    pin(eng, *FAST_HOST)
    sp = SamplingParams(max_new_tokens=60, temperature=0.0)
    first = eng.submit([3, 1, 4], sp)
    while first.first_token_time is None:
        eng.step()
    eng.step()
    eng.step()
    assert eng._rounds[-1].alone
    second = eng.submit(list(range(1, 100)), sp)
    eng.step()      # a chunk went to the device before this pass's round
    assert not eng._rounds[-1].alone
    run_all(eng, [first, second])
    eng.step()


def test_an_iteration_that_sent_a_chunk_is_no_sample_of_the_host(cfg, params):
    """Its dispatch and first tokens cost the host more, and the device has
    the chunk's time to spend on it: what a round must hide is the host's
    time in the iterations that send a round alone."""
    eng = make_engine(cfg, params)
    seen = []
    eng._pacer.note_host = lambda k, seconds: seen.append(
        eng._prefill_programs_dispatched)
    sp = SamplingParams(max_new_tokens=40, temperature=0.0)
    first = eng.submit([3, 1, 4], sp)
    while first.first_token_time is None:
        eng.step()
    for _ in range(3):
        eng.step()
    assert seen and len(set(seen)) == 1       # one chunk so far, then none
    plain = len(seen)
    second = eng.submit(list(range(1, 100)), sp)    # four chunks of 32
    for _ in range(4):
        eng.step()
    assert eng._prefill_programs_dispatched == seen[0] + 4
    assert len(seen) == plain                 # not one sample among them
    run_all(eng, [first, second])
    assert len(seen) > plain


def test_the_prefill_budget_is_the_length_of_the_round_in_force(cfg, params):
    """A pass may send one prefill program for each decode step of the round
    behind it (ISSUE 34): eight while the scheduler holds eight-step rounds,
    one once it has come down to one step. One lane of 32-token chunks, so
    a program is one chunk, and short prompts that hand the lane on within
    the pass."""
    eng = make_engine(cfg, params, max_batch_size=24,
                      prefill_interleave_steps=8, max_concurrent_prefills=1)
    assert eng._plan.rows == 1 and eng._prefill_budget() is None
    pin(eng, *SLOW_HOST)
    live = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=100,
                                                temperature=0.0))
    while live.first_token_time is None:
        eng.step()
    eng.step()
    assert eng._pacer.k == 8 == eng._prefill_budget()

    def one_iteration():
        before = eng.counters()
        eng.step()
        after = eng.counters()
        return tuple(after[k] - before[k] for k in (
            "prefill_programs_dispatched", "prefill_passes",
            "prefill_chunks_deferred"))

    sp = SamplingParams(max_new_tokens=2, temperature=0.0)
    short = [eng.submit([i + 1] * 5, sp) for i in range(10)]
    # eight prompts through the one lane; the ninth has it and waits
    assert one_iteration() == (8, 1, 1)
    assert sum(r.first_token_time is not None for r in short) == 8
    pin(eng, *FAST_HOST)
    assert one_iteration() == (2, 1, 0)     # the length in force is still 8
    assert eng._pacer.k == 1 == eng._prefill_budget()
    more = [eng.submit([i + 1] * 5, sp) for i in range(3)]
    for waiting in (1, 1, 0):
        assert one_iteration() == (1, 1, waiting)
    run_all(eng, [live, *short, *more])


def test_the_two_counters_from_construction_and_they_only_grow(cfg, params):
    eng = make_engine(cfg, params)
    before = eng.counters()
    assert before["sched_host_busy_sum_s"] == 0.0
    assert before["decode_rounds_at_cap"] == 0 == before["decode_rounds"]
    sp = SamplingParams(max_new_tokens=12, temperature=0.0)
    reqs = [eng.submit(list(p), sp) for p in PROMPTS]
    snaps = [before]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
        snaps.append(eng.counters())
    for a, b in zip(snaps, snaps[1:]):
        assert b["sched_host_busy_sum_s"] > a["sched_host_busy_sum_s"]
        assert b["decode_rounds_at_cap"] >= a["decode_rounds_at_cap"]
        assert b["decode_rounds_at_cap"] <= b["decode_rounds"]
    idle = eng.counters()["sched_host_busy_sum_s"]
    eng.step()      # an iteration with nothing to do is the host's time too
    assert eng.counters()["sched_host_busy_sum_s"] > idle


def test_building_the_ladder_draws_no_key_and_counts_no_round(cfg, params):
    eng = make_engine(cfg, params)
    assert eng.decode_rounds == 0 and not eng._rounds
    assert eng._dstate.stats["slot_syncs"] == 0
    # a sampled stream is what it was: the build consumed nothing of the
    # engine's key (seed 0: PRNGKey(seed + 1))
    assert (jax.random.key_data(eng._rng)
            == jax.random.key_data(jax.random.PRNGKey(1))).all()


def test_no_new_option():
    assert len(BatchingSpec.model_fields) == 30
