"""One prefill program for each decode step of the round behind it (ISSUE 34),
on the CPU: while a decode stream is live an admit pass sends no more prefill
programs than the round in force has steps, the prefills take their turn by
class and then by age, the rest wait with their lanes held and are counted;
the hand-on of a freed lane is not deferred, only the newcomer's chunk; with
no live slot a pass is what it was; and the tokens are the same in any order.

The tiny models and prompts are ``test_serve_chunk_rows``'s. ``dense`` runs at
256 tokens a chunk, where the engine builds no program over rows (one chunk a
program: the budget defers the younger prompt's); the expert kinds at 32,
where both lanes' chunks ride in the one program a pass may send."""


import pytest

from kubeflow_tpu.serve.engine import SamplingParams
from test_serve_chunk_rows import (
    CHUNK, KINDS, _engine, _greedy, _model, _run, _tokens, record_spans,
)

GREEDY = SamplingParams(max_new_tokens=4, temperature=0.0)


def _chunk(kind: str) -> int:
    return 256 if kind == "dense" else CHUNK


def _one_step_engine(kind: str, **kw):
    """An engine whose round is one step long, as both expert cells set it
    and the chat cell's scheduler chooses it: a budget of ONE program."""
    _, cfg, params = _model(kind)
    dense = kind == "dense"
    return _engine(cfg, params, chunk=_chunk(kind),
                   max_len=1024 if dense else 256, decode_steps=1,
                   prefill_interleave_steps=1, **kw)


def _prompt(kind: str, seed: int, chunks: float) -> list:
    return list(map(int, _tokens(seed, int(chunks * _chunk(kind)) - seed)))


def _live_stream(eng, new_tokens: int = 200):
    """A decode stream that outlives the test's prefills."""
    req = eng.submit([3, 1, 4], SamplingParams(max_new_tokens=new_tokens,
                                               temperature=0.0))
    while req.first_token_time is None:
        eng.step()
    assert any(s is not None for s in eng.slots) and not eng._chunkings
    return req


def _delta(eng, before: dict) -> dict:
    after = eng.counters()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("kind", KINDS)
def test_the_two_counters_from_construction(kind):
    eng = _one_step_engine(kind)
    c = eng.counters()
    assert c["prefill_passes"] == 0 == c["prefill_chunks_deferred"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_pass_sends_one_program_a_step_and_the_older_prompt_first(kind):
    eng = _one_step_engine(kind)
    rows = eng._plan.rows
    assert rows == (1 if kind == "dense" else 2)
    _live_stream(eng)
    assert eng._prefill_budget() == eng._pacer.k == 1
    before = eng.counters()
    older = eng.submit(_prompt(kind, 4, 3), GREEDY)     # three chunks
    younger = eng.submit(_prompt(kind, 5, 2), GREEDY)   # two
    snaps = [before]
    while not (older.done.is_set() and younger.done.is_set()):
        eng.step()
        snaps.append(eng.counters())
    for a, b in zip(snaps, snaps[1:]):
        sent = b["prefill_programs_dispatched"] \
            - a["prefill_programs_dispatched"]
        assert sent <= 1
        assert b["prefill_passes"] - a["prefill_passes"] == sent
    d = _delta(eng, before)
    assert d["prefill_programs_dispatched"] == d["prefill_passes"]
    assert d["prefill_chunks_dispatched"] == 3 + 2
    if rows == 1:
        # the younger waited for each of the older's three chunks, and the
        # older has its first token after three passes, not five
        assert d["prefill_passes"] == 5
        assert d["prefill_chunks_deferred"] == 3
        assert older.first_token_time < younger.first_token_time
    else:
        # both lanes in the one program: nobody waited
        assert d["prefill_passes"] == 3
        assert d["prefill_chunks_deferred"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_no_live_slot_no_budget(kind):
    """Nobody waits for the pass: four short prompts are prefilled by ONE
    admit pass, lanes handed on within it, as before."""
    eng = _one_step_engine(kind)
    assert eng._prefill_budget() is None
    reqs = [eng.submit(list(map(int, _tokens(s, 20 + s))), GREEDY)
            for s in range(4)]
    eng._admit()
    assert not eng._chunkings
    assert all(len(r.output_tokens) == 1 for r in reqs)
    c = eng.counters()
    assert c["prefill_chunks_dispatched"] == 4
    assert c["prefill_programs_dispatched"] == 4 // eng._plan.rows
    assert (c["prefill_passes"], c["prefill_chunks_deferred"]) == (1, 0)
    _run(eng, reqs)


@pytest.mark.parametrize("kind", KINDS)
def test_a_newcomer_is_admitted_in_the_pass_and_its_chunk_rides_the_next(
        kind, monkeypatch):
    """At 32 tokens a chunk every kind has the two-row program. The lane a
    finished prefill frees goes to the waiting request within the pass (its
    queue wait ends, its pages are taken); its first chunk is the second
    row of the NEXT pass's program, not a one-row program of its own."""
    _, cfg, params = _model(kind)
    eng = _engine(cfg, params, decode_steps=1, prefill_interleave_steps=1)
    assert eng._plan.rows == 2
    _live_stream(eng)
    seen = record_spans(monkeypatch)
    before = eng.counters()
    short = eng.submit(list(map(int, _tokens(4, 20))), GREEDY)
    long = eng.submit(list(map(int, _tokens(5, 3 * CHUNK - 5))), GREEDY)
    newcomer = eng.submit(list(map(int, _tokens(6, 2 * CHUNK - 6))), GREEDY)
    eng._admit()
    d = _delta(eng, before)
    assert (d["prefill_programs_dispatched"], d["prefill_chunks_dispatched"],
            d["prefill_chunks_deferred"], d["prefill_passes"]) == (1, 2, 1, 1)
    assert len(short.output_tokens) == 1
    assert [ch.request for ch in eng._chunkings] == [long, newcomer]
    assert newcomer.admitted_time is not None
    assert eng._chunkings[1].pos == 0
    eng._admit()
    d = _delta(eng, before)
    assert (d["prefill_programs_dispatched"], d["prefill_chunks_dispatched"],
            d["prefill_chunks_deferred"]) == (2, 4, 1)
    _run(eng, [short, long, newcomer])
    chunks = [attrs["chunks"] for name, attrs in seen
              if name == "engine.prefill_dispatch"]
    assert chunks == [2, 2, 2]


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_without_pages_spends_no_budget(kind, monkeypatch):
    eng = _one_step_engine(kind)
    C = _chunk(kind)
    _live_stream(eng)
    reqs = [eng.submit(_prompt(kind, 4, 3), GREEDY),
            eng.submit(_prompt(kind, 5, 3), GREEDY)]
    eng._admit()
    a, b = eng._chunkings
    first = (C, 0) if eng._plan.rows == 1 else (C, C)
    assert (a.pos, b.pos) == first
    before = eng.counters()
    ensure = eng._ensure_pages
    monkeypatch.setattr(
        eng, "_ensure_pages",
        lambda slot, upto: slot != a.slot and ensure(slot, upto))
    eng._admit()
    # the older one stalled, and the pass's one program went to the younger
    assert (a.pos, a.stalls, b.pos, b.stalls) == (C, 1, first[1] + C, 0)
    d = _delta(eng, before)
    assert (d["prefill_programs_dispatched"], d["prefill_chunks_dispatched"],
            d["prefill_chunks_deferred"], d["prefill_passes"]) == (1, 1, 0, 1)
    monkeypatch.setattr(eng, "_ensure_pages", ensure)
    _run(eng, reqs)


@pytest.mark.parametrize("kind", KINDS)
def test_a_higher_class_goes_first(kind, monkeypatch):
    eng = _one_step_engine(kind)
    C = _chunk(kind)
    _live_stream(eng)
    batch = eng.submit(_prompt(kind, 4, 3), GREEDY, qos="batch")
    eng._admit()
    urgent = eng.submit(_prompt(kind, 5, 2), GREEDY, qos="interactive")
    before = eng.counters()
    with monkeypatch.context() as patch:
        seen = record_spans(patch)
        eng._admit()
    a, b = eng._chunkings           # admission order: the batch one is older
    assert (a.request, b.request) == (batch, urgent)
    slots = [attrs["slot"] for name, attrs in seen
             if name == "engine.prefill_dispatch"]
    assert slots == [b.slot]        # the program's first row is the urgent one
    if eng._plan.rows == 1:
        assert (a.pos, b.pos) == (C, C)
        assert _delta(eng, before)["prefill_chunks_deferred"] == 1
    else:
        assert (a.pos, b.pos) == (2 * C, C)
    _run(eng, [batch, urgent])
    if eng._plan.rows == 1:
        assert urgent.first_token_time < batch.first_token_time


@pytest.mark.parametrize("kind", KINDS)
def test_the_round_in_flight_goes_out_before_the_wait_for_first_tokens(kind):
    """The first tokens' fetch waits for the pass's chunk, which the device
    runs behind the round in flight: that round's tokens are handed out
    before the wait, so a stream's gap is a step and ONE chunk, not two."""
    eng = _one_step_engine(kind)
    live = _live_stream(eng)
    eng.step()
    assert len(eng._rounds) == 1            # pipelined: one round in flight
    had = len(live.output_tokens)
    seen = []
    admit = eng._admit_with_token

    def admit_with_token(req, *rest):
        seen.append(len(live.output_tokens))
        admit(req, *rest)

    eng._admit_with_token = admit_with_token
    new = eng.submit(list(map(int, _tokens(4, 20))), GREEDY)
    eng._admit()
    assert seen == [had + 1] and not eng._rounds
    assert len(new.output_tokens) == 1
    _run(eng, [new])


@pytest.mark.parametrize("kind", KINDS)
def test_the_tokens_are_those_of_one_prefill_at_a_time(kind):
    """The order in which chunks reach the device moves no token: against an
    engine with one prefill lane (the older prompt's chunks, then the
    younger's: the order a budget of one gives a one-row engine)."""
    _, cfg, params = _model(kind)
    eng = _one_step_engine(kind)
    live = _live_stream(eng, new_tokens=24)
    prompts = [_prompt(kind, 4, 3), _prompt(kind, 5, 2), _prompt(kind, 6, 1)]
    reqs = [eng.submit(p, GREEDY) for p in prompts]
    _run(eng, [live, *reqs])
    assert eng.counters()["prefill_chunks_deferred"] > 0
    dense = kind == "dense"
    alone = [_greedy(_engine(cfg, params, chunk=_chunk(kind),
                             max_len=1024 if dense else 256,
                             max_concurrent_prefills=1), [p])[0]
             for p in prompts]
    assert [list(r.output_tokens) for r in reqs] == alone
    assert eng.kv_pages_in_use() == 0
    eng._allocator.assert_quiescent()
