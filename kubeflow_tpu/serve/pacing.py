"""How long a decode round is: the scheduler's choice, from what it measures.

A decode round is ONE device program of ``k`` steps, and its tokens reach the
host when the whole round is fetched: a stream's tokens come in bursts of
``k``, and a request that arrives waits behind the rounds in flight. What a
longer round buys is cover: with pipelined dispatch the device runs round
N+1 while the host emits round N, reaps, admits and dispatches round N+2,
and the device goes idle if that host work outlasts the round. So the round
need only be long enough to hide the host's own time an iteration, and how
long that is depends on the machine (a local chip: a few milliseconds
against a 12-15 ms step; a chip behind a tunnel: tens of milliseconds a
dispatch), which the scheduler can measure and an operator should not have
to.

The host's time an iteration is NOT one number: part of it is paid once an
iteration (reaping, admission, the dispatch itself) and part of it grows
with the round (the emit loop, and the handler threads that wake for every
emitted token and take the interpreter's lock from the scheduler: on a v5e
host the chat cell reads 5.6 ms an iteration at one step a round, 23 ms at
eight). So it is kept BY LENGTH, as it was measured while rounds of that
length were in force, and a length that has not run yet is held to the
least its time can be given the lengths that have: no less than a shorter
length's (the fixed part does not shrink), no less than a longer length's
in proportion (the rest shrinks at most with the steps). That bound is
optimistic on purpose: a length that looks long enough is tried, measured,
and left again at once if it is not.

``round_steps`` is the choice and ``host_estimates`` what it is made from
(both pure); ``RoundPacer`` keeps the samples. The lengths are a fixed
ladder (the step count is a static argument of the decode program, so each
length is a program, and the engine compiles them all when it is built); the
operator's ``decode_steps`` and ``prefill_interleave_steps`` are caps on the
choice.
"""

from __future__ import annotations

import collections
import statistics
from typing import Mapping, Optional, Sequence

#: A round is long enough when its device time is this many times the
#: host's own time an iteration: the shortest such length is chosen.
COVER = 1.25
#: ... and a round that is longer than that already gets shorter only once
#: the shorter one would cover the host this many times over (hysteresis: a
#: shorter round must not be chosen on a margin the next sample takes back).
COVER_TO_SHORTEN = 1.75
#: An estimate is the MEDIAN of the last so many samples (a sample is one
#: scheduler iteration that dispatched a round): it follows a change once
#: more than half of them have seen it, eight iterations, and nothing that
#: fewer than half of them saw moves it: a collection, a program's first
#: dispatch, the iterations around a change of length.
SAMPLES = 15
#: Samples at a length before its own measurement stands in for the bound.
MIN_SAMPLES = 4
#: Rounds at other lengths after which what was measured at a length is
#: forgotten, so that it is tried again where the bound allows.
RETRY_ROUNDS = 128


def decode_ladder(decode_steps: int, prefill_interleave_steps: int
                  ) -> tuple[int, ...]:
    """The round lengths an engine with these two caps can dispatch: one
    step, the cap while a prefill is in flight, the cap otherwise."""
    return tuple(sorted({1, min(decode_steps, prefill_interleave_steps),
                         decode_steps}))


def host_estimates(measured: Mapping[int, float], ladder: Sequence[int]
                   ) -> dict[int, Optional[float]]:
    """The host's time an iteration at each length of ``ladder``: what was
    ``measured`` at that length, else the least it can be given the lengths
    that were (at least a shorter length's, at least a longer length's in
    proportion to the steps), else None (nothing is measured anywhere)."""
    out: dict[int, Optional[float]] = {}
    for k in ladder:
        if k in measured:
            out[k] = measured[k]
            continue
        bounds = [h if j < k else h * k / j for j, h in measured.items()]
        out[k] = max(bounds) if bounds else None
    return out


def round_steps(host_s: Mapping[int, Optional[float]],
                step_s: Optional[float], cap: int, ladder: Sequence[int],
                current: int) -> int:
    """The SHORTEST length of ``ladder`` (ascending, holds 1) not above
    ``cap`` whose device time ``k * step_s`` covers ``COVER`` times the
    host's time an iteration at that length, ``host_s[k]``; the cap where
    nothing is measured yet or no length covers it. ``current`` is the
    length last chosen: a longer round is taken at once (a pipeline run dry
    costs the device's time), a shorter one only on the wider margin
    ``COVER_TO_SHORTEN``."""
    allowed = [k for k in ladder if k <= cap]
    if step_s is None or any(host_s.get(k) is None for k in allowed):
        return allowed[-1]

    def shortest(cover: float) -> int:
        return next((k for k in allowed if k * step_s >= cover * host_s[k]),
                    allowed[-1])

    need = shortest(COVER)
    held = max(k for k in allowed if k <= current)
    if need >= held:
        return need
    return min(held, shortest(COVER_TO_SHORTEN))


class RoundPacer:
    """The scheduler thread's measurements and the length chosen from them.

    ``note_host(k, seconds)``: the host's own time in a scheduler iteration
    that dispatched a round (wall time less the time blocked on the
    device), filed under the length ``k`` in force. ``note_step(seconds)``:
    the device's time a decode step, from the spacing of two consecutive
    rounds' ready times over the later round's steps, taken only while the
    pipeline is full and no other program ran between them; where the host
    is the slower of the two the spacing is the host's, an over-estimate
    that can only ask for a longer round. Scheduler-confined."""

    def __init__(self, ladder: Sequence[int]):
        self.ladder = tuple(ladder)
        self._host = {k: collections.deque(maxlen=SAMPLES)
                      for k in self.ladder}
        self._step: collections.deque = collections.deque(maxlen=SAMPLES)
        self._away = dict.fromkeys(self.ladder, 0)
        self.k = self.ladder[-1]

    @property
    def step_s(self) -> Optional[float]:
        return statistics.median(self._step) if self._step else None

    def host_s(self) -> dict[int, Optional[float]]:
        """The host's time an iteration by length (``host_estimates``)."""
        return host_estimates(
            {k: statistics.median(d) for k, d in self._host.items()
             if len(d) >= MIN_SAMPLES}, self.ladder)

    def note_host(self, k: int, seconds: float) -> None:
        self._host[k].append(seconds)

    def note_step(self, seconds: float) -> None:
        self._step.append(seconds)

    def choose(self, cap: int) -> int:
        self.k = round_steps(self.host_s(), self.step_s, cap, self.ladder,
                             self.k)
        for k in self.ladder:
            self._away[k] = 0 if k == self.k else self._away[k] + 1
            if self._away[k] == RETRY_ROUNDS:
                self._host[k].clear()
        return self.k
