"""ISSUE 31 on the chip, beside the benchmark and editing none of it.

    python3 scripts/round_pacing_chip.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|2> [--pin-steps K]

``python3 -m benchmark.run`` with the numbers a decode round's length is
chosen from printed beside its result (to standard error; the result line is
the benchmark's own and stays the last line of standard output): over the
window, from the snapshots of ``LLMEngine.counters()`` the harness takes,
the rounds, the steps a round, the rounds left at their cap and the
scheduler's own seconds a round and as a share of the window, the prefill
programs a scheduler pass sent and the chunks a pass's budget deferred
(ISSUE 34: ``prefill_programs_a_pass``, ``prefill_chunks_deferred``); at the
window's end the scheduler's two running averages (the host's time an
iteration, the device's time a step) and the length in force; the client's
gaps between tokens and times to the first token at several percentiles;
of a traced run the tail's rounds by length, its decode and chunk programs
(executions, mean milliseconds) and the scheduler thread's spans by name;
and of the set-up, when the engine was built, how long each length of the
decode ladder took to compile or load, and when each program variant was
first dispatched. It runs on a checkout without the mechanism too (the parent commit):
what that engine does not count is left out.

``--pin-steps K`` is an experiment, not an option of the program: the
engine's choice is replaced, from outside, by ``min(K, cap)``, to read the
host's time an iteration at a length the scheduler would not choose here.
``--rate R`` is another: an open loop's arrivals at ``R`` requests a second
in place of the traffic file's, for the first readings of a sweep.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _log(msg: str) -> None:
    print(f"[round_pacing] {msg}", file=sys.stderr, flush=True)


def _percentiles(values: list, qs=(50, 90, 95, 99)) -> str:
    from benchmark.stats import percentile

    if not values:
        return "none"
    return " ".join(f"p{q} {percentile(values, q):.1f}" for q in qs) \
        + f" n {len(values)}"


def _client_side(workload: str, seconds: float) -> None:
    from benchmark import run, serving

    path = os.path.join(run.OUT_ROOT, workload, "loadgen.json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        red = serving.reduce_requests(json.load(f)["results"], seconds)
    _log(f"client itl ms: {_percentiles(red['itl_ms'])}")
    _log(f"client ttft ms: {_percentiles(red['ttft_ms'])}")


def _tail(record: dict) -> None:
    """The traced tail: rounds by length, programs, the scheduler's spans."""
    trace = record.get("trace")
    if not trace or not trace["devices"]:
        return
    by: dict = {}
    for name, _, dur in trace["devices"][0]["modules"]:
        n = by.setdefault(re.sub(r"\(\d+\)$", "", name), [])
        n.append(dur)
    for name, durs in sorted(by.items(), key=lambda kv: -sum(kv[1]))[:6]:
        durs.sort()
        _log(f"tail program {name}: {len(durs)} x mean "
             f"{1e3 * sum(durs) / len(durs):.3f} ms (median "
             f"{1e3 * durs[len(durs) // 2]:.3f}, max {1e3 * durs[-1]:.3f}) "
             f"= {sum(durs):.4f} s")
    names: dict = {}
    lengths: dict = {}
    for thread in record.get("host_spans") or []:
        for name, _, dur, attrs in thread:
            if not name.startswith("engine."):
                continue
            n = names.setdefault(name, [0, 0.0])
            n[0], n[1] = n[0] + 1, n[1] + dur
            if name == "engine.decode_dispatch":
                k = attrs.get("k_steps")
                lengths[k] = lengths.get(k, 0) + 1
    _log(f"tail rounds by k_steps: {json.dumps(lengths, sort_keys=True)}")
    # Prefill programs by the admit pass that sent them (from the spans, so
    # that a checkout without the counter ``prefill_passes`` reads too).
    sent: dict = {}
    for thread in record.get("host_spans") or []:
        admits = [(t0, t0 + dur) for name, t0, dur, _ in thread
                  if name == "engine.admit"]
        for name, t0, _, _ in thread:
            if name == "engine.prefill_dispatch":
                owner = next((a for a in admits if a[0] <= t0 < a[1]), None)
                sent[owner] = sent.get(owner, 0) + 1
    if sent:
        by_count = collections.Counter(sent.values())
        _log(f"tail prefill programs a pass: "
             f"{sum(sent.values()) / len(sent):.3f} (passes by programs "
             f"sent: {json.dumps(by_count, sort_keys=True)})")
    for name, (n, total) in sorted(names.items(), key=lambda kv: -kv[1][1]):
        _log(f"tail span {name}: {n} x {1e3 * total / n:.3f} ms = "
             f"{total:.4f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pin-steps", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0)
    args, rest = ap.parse_known_args()

    from benchmark import manifest as mf
    from benchmark import run, serving
    from kubeflow_tpu.serve.engine import LLMEngine

    t_origin = time.monotonic()

    def at(msg: str) -> None:
        _log(f"t={time.monotonic() - t_origin:.3f} {msg}")

    snapshots = []
    take, read, load = \
        serving.program_counters, mf.read_layer_metrics, mf.load_traffic

    def recording(**parts):
        snap = take(**parts)
        snapshots.append(snap)
        at(f"counters snapshot {len(snapshots)}")
        pacer = getattr(parts.get("engine"), "_pacer", None)
        if pacer is not None:
            _log(f"pacer at snapshot {len(snapshots)}: host_s "
                 f"{pacer.host_s()} step_s {pacer.step_s} k {pacer.k} "
                 f"samples { {k: len(d) for k, d in pacer._host.items()} }")
        return snap

    def reading(manifest, cell_name, record):
        try:
            _tail(record)
        except Exception as exc:    # boundary: the run's result comes first
            _log(f"tail not printed: {type(exc).__name__}: {exc}")
        return read(manifest, cell_name, record)

    def at_rate(name):
        traffic = load(name)
        traffic["arrival"]["rate_rps"] = args.rate
        return traffic

    class Timeline(dict):
        """``LLMEngine.program_kernels`` that says when each variant was
        first dispatched (its lowering is done, its compile or its load
        from the cache follows)."""

        def __setitem__(self, key, value):
            at(f"first dispatch of {key}")
            super().__setitem__(key, value)

    build = LLMEngine.__init__
    ladder = getattr(LLMEngine, "_warm_decode_ladder", None)

    def built(self, *a, **kw):
        t0 = time.monotonic()
        build(self, *a, **kw)
        self.program_kernels = Timeline(self.program_kernels)
        at(f"LLMEngine() took {time.monotonic() - t0:.3f} s")
        if args.pin_steps:
            self._pacer.choose = lambda cap: min(args.pin_steps, cap)

    def ladder_timed(self):
        t0 = time.monotonic()
        dispatch = self._dispatch_decode

        def one(k, mode, key):
            t1 = time.monotonic()
            out = dispatch(k, mode, key)
            out.block_until_ready()
            _log(f"decode program of {k} steps: first dispatch "
                 f"{time.monotonic() - t1:.3f} s")
            return out

        self._dispatch_decode = one
        ladder(self)
        del self._dispatch_decode
        _log(f"decode ladder {self._pacer.ladder} compiled and run in "
             f"{time.monotonic() - t0:.3f} s")

    serving.program_counters = recording
    mf.read_layer_metrics = reading
    if args.rate:
        mf.load_traffic = at_rate
    LLMEngine.__init__ = built
    if ladder is not None:          # the parent commit has none
        LLMEngine._warm_decode_ladder = ladder_timed
    rc = run.main(["--workload", args.workload, "--seconds",
                   str(args.seconds), *rest])
    if len(snapshots) >= 2 and snapshots[0] and "engine" in snapshots[0]:
        a, b = snapshots[0]["engine"], snapshots[1]["engine"]
        d = {k: b[k] - a[k] for k in b if k in a and k.startswith(
            ("decode_", "sched_", "prefill_", "first_token", "host_gap",
             "queue_delay"))}
        rounds = d.get("decode_rounds") or 0
        if rounds:
            d["steps_a_round"] = d["decode_steps_dispatched"] / rounds
            if "sched_host_busy_sum_s" in d:
                d["sched_host_busy_ms_a_round"] = \
                    1e3 * d["sched_host_busy_sum_s"] / rounds
                d["sched_host_busy_share"] = \
                    d["sched_host_busy_sum_s"] / args.seconds
        if d.get("prefill_passes"):         # ISSUE 34; the parent has none
            d["prefill_programs_a_pass"] = \
                d["prefill_programs_dispatched"] / d["prefill_passes"]
        _log(f"window counters: {json.dumps(d, sort_keys=True)}")
    _client_side(args.workload, args.seconds)
    return rc


if __name__ == "__main__":
    sys.exit(main())
