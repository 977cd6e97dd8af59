"""The one general traffic generator: a traffic file's parameters and a seed
give a plan of requests (or of training batches). Imports no JAX: the load
generator's process reads it too, and that process must stay off the chip.

Every seed gets the SAME multiset of sizes and gaps, in another order: the
values are the distribution's quantiles at evenly spaced points, and the seed
only permutes them and fills in the token ids. Runs with different seeds then
do the same amount of work, and differ by what order does to a scheduler,
which is what a serving benchmark should be sensitive to.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

# Token ids below this are specials in every tokenizer the repo ships; the
# generator keeps prompts off them.
FIRST_ORDINARY_TOKEN = 3


class TrafficError(Exception):
    pass


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n, clipped
    to [min, max] and rounded. ``dist``: fixed(value) | uniform(min, max) |
    lognormal(median, sigma, min, max)."""
    kind = dist["dist"]
    u = _quantiles(n)
    if kind == "fixed":
        out = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        out = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        out = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise TrafficError(f"unknown length distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", float("inf"))
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def arrival_offsets(arrival: dict, n: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due instants in (0, seconds) of ``n`` requests. ``poisson``: the
    exponential distribution's quantiles as gaps, permuted; ``uniform``:
    even spacing; ``bursty``: groups of ``burst_depth`` due together, the
    groups a Poisson process of the same mean rate."""
    process = arrival["process"]
    if process == "uniform":
        return (np.arange(n) + 0.5) * seconds / n
    if process == "poisson":
        groups, depth = n, 1
    elif process == "bursty":
        depth = int(arrival["burst_depth"])
        groups = max(1, n // depth)
    else:
        raise TrafficError(f"unknown arrival process {process!r}")
    gaps = -np.log1p(-_quantiles(groups))
    gaps = gaps[rng.permutation(groups)]
    at = (np.cumsum(gaps) - gaps / 2.0) * seconds / gaps.sum()
    return np.repeat(at, depth)[:n] if depth > 1 else at


def build_plan(traffic: dict, *, seed: int, seconds: float, vocab: int,
               model: str) -> dict:
    """The requests of one run. Open loop: round(rate x seconds) requests
    with their due instants. Closed loop: a pool of requests that ``clients``
    clients walk round-robin until the window ends."""
    kind = traffic["kind"]
    if kind not in ("open_loop", "closed_loop"):
        raise TrafficError(f"build_plan is for serving traffic, not {kind!r}")
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    if kind == "open_loop":
        n = max(1, int(round(traffic["arrival"]["rate_rps"] * seconds)))
        due = arrival_offsets(traffic["arrival"], n, seconds, rng)
    else:
        n = int(traffic["pool"])
        due = np.zeros(n)
    p_len = lengths(traffic["prompt_len"], n)[rng.permutation(n)]
    o_len = lengths(traffic["output_len"], n)[rng.permutation(n)]
    shared = int(traffic.get("shared_prefix_tokens", 0))
    prefixes = int(traffic.get("prefix_pool", 1))
    requests = [{"i": i, "due_s": float(due[i]), "prompt_len": int(p_len[i]),
                 "max_tokens": int(o_len[i]),
                 "prefix": int(rng.integers(prefixes)) if shared else -1}
                for i in range(n)]
    return {"kind": kind, "seed": int(seed), "seconds": float(seconds),
            "vocab": int(vocab), "model": model,
            "shared_prefix_tokens": shared,
            "clients": int(traffic.get("clients", 0)),
            "temperature": float(traffic.get("temperature", 0.0)),
            "request_timeout_s": float(traffic.get("request_timeout_s", 120)),
            "drain_timeout_s": float(traffic.get("drain_timeout_s", 60)),
            "warmup": traffic.get("warmup", []),
            "requests": requests}


def prompt_tokens(plan: dict, req: dict) -> np.ndarray:
    """The token ids of one request, over the WHOLE vocabulary, from the
    run's seed and the request's index. A shared prefix comes from the seed
    and the prefix's id, so requests with the same prefix id share it."""
    n, vocab, seed = req["prompt_len"], plan["vocab"], plan["seed"]
    own = np.random.default_rng([seed, 1, req["i"]]).integers(
        FIRST_ORDINARY_TOKEN, vocab, n)
    shared = min(plan["shared_prefix_tokens"], n) if req["prefix"] >= 0 else 0
    if shared:
        own[:shared] = np.random.default_rng(
            [seed, 2, req["prefix"]]).integers(
                FIRST_ORDINARY_TOKEN, vocab, plan["shared_prefix_tokens"]
            )[:shared]
    return own


def train_batch(seed: int, step: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """[batch, seq_len + 1] int32 tokens of one training step, a pure
    function of (seed, step): uniform over the vocabulary."""
    return np.random.default_rng([int(seed), 3, int(step)]).integers(
        0, vocab, (batch, seq_len + 1), dtype=np.int32)


def encode_ids(ids) -> str:
    """Token ids as the text the benchmark's tokenizer turns back into
    exactly these ids (``IdTokenizer``, benchmark/serving.py)."""
    return " ".join(str(int(t)) for t in ids)


def decode_ids(text: str) -> list[int]:
    return [int(t) for t in text.split()]


def n_chunks(prompt_len: int, chunk: int) -> int:
    return math.ceil(prompt_len / chunk)
