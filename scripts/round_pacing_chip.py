"""ISSUE 31 on the chip, beside the benchmark and editing none of it.

    python3 scripts/round_pacing_chip.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|2> [--pin-steps K] [--trace-start]

``python3 -m benchmark.run`` with the numbers a decode round's length is
chosen from printed beside its result (to standard error; the result line is
the benchmark's own and stays the last line of standard output): over the
window, from the snapshots of ``LLMEngine.counters()`` the harness takes,
the rounds, the steps a round, the rounds left at their cap and the
scheduler's own milliseconds a round, the prefill
programs a scheduler pass sent and the chunks a pass's budget deferred
(ISSUE 34: ``prefill_programs_a_pass``, ``prefill_chunks_deferred``), the
chunks a program carried, the rows that carried a further chunk of a prompt
already in the program and the rows that carried nothing (ISSUE 56:
``prefill_chunks_a_program``, ``prefill_rows_ahead``, ``prefill_rows_dead``
and its share of the rows sent); the
host's time a token over the window (ISSUE 37, by
``benchmark/phase_readers.py``'s definitions: the scheduler's share of the
window, which the line's ``engine.sched_busy_share_window.*`` prints where it
is declared, each of its phases in milliseconds a round and as a share, what
the state syncs sent a round and the programs that carried them, the server's
write share and wake time); at the
window's end the scheduler's two running averages (the host's time an
iteration, the device's time a step) and the length in force; the client's
gaps between tokens and times to the first token at several percentiles;
of a traced run the tail's rounds by length, its decode and chunk programs
(executions, mean milliseconds), the scheduler thread's spans by name and,
phase by phase, the always-on sum over the traced stretch beside what
``hostspans.innermost_segments`` cuts out of the spans for it, and the
thread's time under no span by the spans on either side of it;
and of the set-up (ISSUE 53), the program's own start-up clock as the
snapshot taken when the window opens holds it: ``start_*`` (the engine's
constructor by phase, or the trainer's three), ``compile_*`` (the process's
compile seconds and cache traffic), ``LLMEngine.start_programs()`` (each
program the constructor ran once), what ``benchmark/startup_readers.py``
reads from them; and that the ``compile_*`` keys stood still over the
window. It reads the training cell too (its start-up alone), and a checkout
without the clock (the parent commit): what a snapshot does not hold is
left out. What the harness does around the program is in its own log lines
(``engine built at``, ``correctness done at``, ``window opens``).

``--trace-start`` is an experiment: a capture through ``obs/profiler.py``
around the engine's construction (from the harness's weights made to its
reference check's first line), reduced with ``benchmark/hostspans.py``: per
start phase and per warmed program the host's seconds, the device's busy
seconds under them and the rest, idle. What the capture costs is this run's
``start_*`` and ``setup_s`` against a run's without it.

``--pin-steps K`` is an experiment, not an option of the program: from the
window's opening on the engine's choice is replaced, from outside, by
``min(K, cap)``, to read the host's time an iteration at a length the
scheduler would not choose here.
``--rate R`` is another: an open loop's arrivals at ``R`` requests a second
in place of the traffic file's, for the first readings of a sweep.
``--switch-interval S`` a third: ``sys.setswitchinterval(S)`` for the run
(the interpreter hands its lock on after 5 ms by default), to see how much
of the scheduler's time under no phase is the wait for that lock behind the
handler threads a round's tokens woke. ``--probe-other`` a fourth: the
seconds since the scheduler's last phase boundary, read as each of
``_consume_round``, ``_decode_once`` and ``_iterate`` returns and as
``_iterate`` begins, summed a call: where between ``engine.emit`` and the
next ``engine.reap`` the loop's time under no phase goes.
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


EDGE = 0.1      # seconds of a capture's ends left out of ``recorded``'s readings


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


def _window(before: dict, after: dict, seconds: float) -> None:
    """The host's time a token over the window, by the definitions the
    per-layer metrics of ISSUE 37 have (``benchmark/phase_readers.py``): a
    checkout without a key prints ``null`` there."""
    from benchmark import phase_readers as pr

    run = {"counters_before": before, "counters_after": after,
           "window_s": seconds}
    phases = sorted(k[len("sched_"):-len("_sum_s")]
                    for k in after.get("engine", {})
                    if k.startswith("sched_") and k.endswith("_sum_s")
                    and k != "sched_host_busy_sum_s")
    table = {
        "sched_busy_share_window_pct": pr.sched_busy_share_window(run),
        "prefill_dispatch_ms_per_program":
            pr.prefill_dispatch_ms_per_program(run),
        "state_syncs_per_round": pr.state_syncs_per_round(run),
        "state_slot_syncs_per_round": pr.per(
            run, "engine", ("state_slot_syncs",), "decode_rounds"),
        "state_sync_rounds_share": pr.per(
            run, "engine", ("state_sync_rounds",), "decode_rounds"),
        # programs the syncs sent (PR 54: one a syncing round; a checkout
        # that sent one an item has no such key)
        "state_sync_dispatches_per_round": pr.per(
            run, "engine", ("state_sync_dispatches",), "decode_rounds"),
        "stream_write_share_pct": pr.stream_write_share(run),
        "stream_wake_mean_ms": pr.stream_wake_mean_ms(run),
        "stream_behind_share": pr.per(
            run, "server", ("stream_behind_n",), "stream_chunks_n"),
        "phase_ms_per_round": {p: pr.phase_ms_per_round(run, p)
                               for p in phases},
        "phase_share_of_window_pct": {
            p: pr.share_of_window(run, "engine", f"sched_{p}_sum_s")
            for p in phases},
    }
    _log(f"window host time: {json.dumps(table, sort_keys=True)}")


def _sched_pieces(record: dict) -> list:
    """The scheduler thread's traced time as (start, end, innermost span)."""
    from benchmark import hostspans

    sched = hostspans.thread_with(record.get("host_spans"),
                                  hostspans.ENGINE_THREAD)
    return hostspans.innermost_segments(
        [s for s in sched or [] if s[0] != hostspans.ANCHOR])


def _tail_sums_against_spans(record: dict, tail: dict) -> None:
    """One boundary, two sinks: each phase's always-on seconds between the
    two readings ``recorded`` took inside the capture, beside the seconds
    of its spans' innermost segments between the same two instants (laid on
    the trace's timeline through the capture's anchor). What is left is a
    phase under way at either reading: the sum has the whole of the first
    and none of the second."""
    from benchmark import hostspans

    pieces = _sched_pieces(record)
    anchor = hostspans.anchor(record.get("host_spans"))
    if not tail or not pieces or not anchor:
        return
    sums = {k: tail["b"][k] - tail["a"][k] for k in tail["b"]}
    lo, hi = (anchor["trace_s"] + t - anchor["mono_ns"] / 1e9
              for t in (tail["t_a"], tail["t_b"]))
    spanned: dict = {}
    for t0, t1, name in pieces:
        part = min(t1, hi) - max(t0, lo)
        if part > 0:
            spanned[name] = spanned.get(name, 0.0) + part
    for name in sorted(set(spanned) | {"engine." + p for p in sums}):
        a, b = sums.get(name.rpartition(".")[2]), spanned.get(name)
        if a is None or not b:
            _log(f"tail phase {name}: sum {a} spans {b}")
            continue
        _log(f"tail phase {name}: sum {1e3 * a:.3f} ms spans "
             f"{1e3 * b:.3f} ms ({100 * (a - b) / b:+.2f}%)")


def _tail_untraced(record: dict) -> None:
    """The scheduler thread's time under NO span (``sched_other_sum_s``
    over a window), by the two spans each stretch lies between."""
    pieces = _sched_pieces(record)
    between: dict = {}
    for (_, end, before), (start, _, after) in zip(pieces, pieces[1:]):
        if start - end > 1e-7:
            n = between.setdefault(f"{before} -> {after}", [0, 0.0, 0.0])
            n[0], n[1] = n[0] + 1, n[1] + start - end
            n[2] = max(n[2], start - end)
    for key, (n, total, worst) in sorted(between.items(),
                                         key=lambda kv: -kv[1][1])[:8]:
        _log(f"tail untraced {key}: {n} x {1e3 * total / n:.3f} ms = "
             f"{total:.4f} s (max {1e3 * worst:.3f} ms)")


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
        # one name, several programs (the chunk program beside the table's
        # row uploads, all ``jit__lambda``): the long ones apart
        long = [d for d in durs if d >= 2e-3]
        if long and len(long) < len(durs):
            _log(f"tail program {name} of 2 ms or more: {len(long)} x median "
                 f"{1e3 * long[len(long) // 2]:.3f} ms (min "
                 f"{1e3 * long[0]:.3f}, max {1e3 * long[-1]:.3f})")
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
    # The chunk programs by the chunks they carried (ISSUE 41: the one-row
    # program apart from the program over rows): the k-th
    # ``engine.prefill_dispatch`` span sent the k-th long ``jit__lambda``;
    # the capture's edges may cut one program or one span.
    # (ISSUE 63: and by whether the slots' step rode, the span's
    # ``live_rows``: ONE chunk with the step riding is a program with a dead
    # row, one without it the one-row program)
    carried = sorted((t0, f"{attrs.get('chunks', 1)}"
                      + (" with the step riding"
                         if attrs.get("live_rows") else ""))
                     for thread in record.get("host_spans") or []
                     for name, t0, _, attrs in thread
                     if name == "engine.prefill_dispatch")
    programs = sorted((t0, dur) for name, t0, dur
                      in trace["devices"][0]["modules"]
                      if name.startswith("jit__lambda") and dur >= 2e-3)
    if 0 < len(programs) - len(carried) <= 2:
        # (sent before the capture began: an engine that sends a round ahead
        # has two programs in flight, PR 58)
        programs = programs[len(programs) - len(carried):]
    elif len(carried) == len(programs) + 1:
        carried = carried[:-1]
    if carried and len(carried) == len(programs):
        by_chunks: dict = {}
        for (_, chunks), (_, dur) in zip(carried, programs):
            by_chunks.setdefault(chunks, []).append(dur)
        for chunks, durs in sorted(by_chunks.items()):
            durs.sort()
            _log(f"tail chunk program carrying {chunks}: {len(durs)} x "
                 f"median {1e3 * durs[len(durs) // 2]:.3f} ms (min "
                 f"{1e3 * durs[0]:.3f}, max {1e3 * durs[-1]:.3f}) = "
                 f"{sum(durs):.4f} s")
    elif carried:
        _log(f"tail chunk programs {len(programs)} against "
             f"{len(carried)} dispatch spans: not paired")


def _start_up(snapshot: dict, setup_s, programs: dict | None) -> None:
    """The program's start-up clock, from the snapshot taken as the window
    opens: its ``start_*`` and ``compile_*`` keys as they are, and the
    readings ``benchmark/startup_readers.py`` takes of them. A snapshot
    without the clock has none of the keys, and ``unattributed_s`` is
    ``setup_s``."""
    from benchmark import startup_readers as sr

    part = snapshot.get("engine") or snapshot.get("trainer") or {}
    run = {"counters_before": snapshot, "values": {"setup_s": setup_s}}
    table = {
        **{k: v for k, v in part.items()
           if k.startswith((sr.START, "compile_"))},
        "setup_s": setup_s,
        "readers": {"program_start_s": sr.program_start_s(run),
                    "unattributed_s": sr.unattributed_s(run),
                    "compile_s": sr.compile_s(run),
                    "cache_misses": sr.cache_misses(run),
                    "warm_s": sr.warm_s(run)}}
    _log(f"start-up: {json.dumps(table, sort_keys=True)}")
    if programs:
        _log(f"start programs ({len(programs)}): {json.dumps(programs)}")


def _compiles_over_the_window(before: dict, after: dict) -> None:
    """The window compiles nothing: the process's compile keys stand still
    between the two snapshots (what ``CompileCounter`` asserts from
    outside)."""
    a = before.get("engine") or before.get("trainer") or {}
    b = after.get("engine") or after.get("trainer") or {}
    moved = {k: b[k] - a[k] for k in b
             if k.startswith("compile_") and k in a}
    if moved:
        _log(f"compile keys over the window: {json.dumps(moved)} "
             f"({'STOOD STILL' if not any(moved.values()) else 'MOVED'})")


def _start_capture(trace: dict, engine) -> None:
    """A capture around the engine's construction, by start phase and by
    warmed program: the builder thread's seconds (innermost segments), the
    device's busy seconds under them, and the rest, idle."""
    from benchmark import hostspans
    from benchmark.tracing import measure, union
    from kubeflow_tpu.obs import profiler

    spans = hostspans.thread_with(trace.get("host_spans"),
                                  profiler.ENGINE_START_PHASES)
    if not spans:
        _log("start capture: no engine.start.* span in it (a checkout "
             "without the clock)")
        return
    busy = union((t, t + d) for dev in trace["devices"][:1]
                 for _, t, d in dev["ops"]) if trace["devices"] else []

    def busy_in(t0: float, t1: float) -> float:
        return measure([(max(a, t0), min(b, t1)) for a, b in busy
                        if a < t1 and b > t0])

    phases: dict = {}
    for t0, t1, name in hostspans.innermost_segments(
            [s for s in spans if s[0] != hostspans.ANCHOR]):
        n = phases.setdefault(name, [0.0, 0.0])
        n[0], n[1] = n[0] + t1 - t0, n[1] + busy_in(t0, t1)
    sums = engine.start_phase_seconds()
    for name, (host, dev) in sorted(phases.items()):
        short = name.rpartition(".")[2]
        _log(f"start capture {name}: spans {host:.4f} s (sum "
             f"{sums.get(short)}), device busy {dev:.4f} s, idle "
             f"{host - dev:.4f} s")
    for name, t0, dur, attrs in spans:
        if name == profiler.ENGINE_START_WARM:
            dev = busy_in(t0, t0 + dur)
            _log(f"start capture program {attrs.get('program')}: "
                 f"{dur:.4f} s, device busy {dev:.4f} s")
    _log(f"start capture: {trace['window_s']:.3f} s traced, device busy "
         f"{measure(busy):.4f} s, constructor "
         f"{sum(sums.values()):.4f} s by its own clock")


def main() -> int:
    # (no abbreviations: ``--trace`` is the benchmark's own, passed on)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0],
                                 allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pin-steps", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--switch-interval", type=float, default=0.0)
    ap.add_argument("--probe-other", action="store_true")
    ap.add_argument("--trace-start", action="store_true")
    args, rest = ap.parse_known_args()
    t_origin = time.monotonic()

    from benchmark import correctness, training
    from benchmark import manifest as mf
    from benchmark import run, serving, tracing
    from benchmark.device import sleep_until
    from kubeflow_tpu.serve.engine import LLMEngine

    def at(msg: str) -> None:
        _log(f"t={time.monotonic() - t_origin:.3f} {msg}")

    snapshots = []
    opened: list = []       # when the first snapshot was taken
    take, read, load = \
        serving.program_counters, mf.read_layer_metrics, mf.load_traffic

    def recording(**parts):
        snap = take(**parts)
        snapshots.append(snap)
        if not opened:
            # The harness takes it as its window opens: from the entry of
            # its ``main`` to here is ``setup_s`` within a millisecond.
            opened.append(time.monotonic())
        engine = parts.get("engine")
        if engine is None:      # a trainer: one snapshot a sync point
            return snap
        at(f"counters snapshot {len(snapshots)}")
        if engine not in engines:
            # The first snapshot is the first the script sees of the engine:
            # taken as the window opens, with the whole start-up in it.
            engines.append(engine)
            if args.pin_steps:
                engine._pacer.choose = lambda cap: min(args.pin_steps, cap)
        pacer = getattr(engine, "_pacer", None)
        if pacer is not None:
            _log(f"pacer at snapshot {len(snapshots)}: host_s "
                 f"{pacer.host_s()} step_s {pacer.step_s} k {pacer.k} "
                 f"samples { {k: len(d) for k, d in pacer._host.items()} }")
        return snap

    engines: list = []
    tail_sums: dict = {}

    def recorded(trace_dir, seconds):
        """``tracing.record`` with the engine's phase sums read twice
        inside the capture, ``EDGE`` seconds from either end (the
        profiler's start stalls whatever phase it falls into)."""
        phases = getattr(engines[-1], "sched_phase_seconds", None) \
            if engines else None
        tracing.start(trace_dir)
        t_on = time.monotonic()
        if phases:
            time.sleep(EDGE)
            tail_sums.update(a=phases(), t_a=time.monotonic())
            time.sleep(seconds - 2 * EDGE)
            tail_sums.update(b=phases(), t_b=time.monotonic())
        sleep_until(t_on + seconds)
        return tracing.stop(trace_dir, time.monotonic() - t_on)

    def reading(manifest, cell_name, record):
        try:
            _tail(record)
            _tail_sums_against_spans(record, tail_sums)
            _tail_untraced(record)
        except Exception as exc:    # boundary: the run's result comes first
            _log(f"tail not printed: {type(exc).__name__}: {exc}")
        return read(manifest, cell_name, record)

    def at_rate(name):
        traffic = load(name)
        traffic["arrival"]["rate_rps"] = args.rate
        return traffic

    trace_dir = os.path.join(run.OUT_ROOT, args.workload + ".start")
    make, judge = serving.make_params, correctness.serving_numbers
    capture_on: list = []

    def made(*a, **kw):
        """The weights are made: the engine's construction follows."""
        params = make(*a, **kw)
        tracing.start(trace_dir)
        capture_on.append(time.monotonic())
        return params

    def judged(engine, *a, **kw):
        """The reference check's first line: the engine is built."""
        trace = tracing.stop(trace_dir, time.monotonic() - capture_on.pop())
        try:
            _start_capture(trace, engine)
        except Exception as exc:    # boundary: the run's result comes first
            _log(f"start capture not printed: {type(exc).__name__}: {exc}")
        return judge(engine, *a, **kw)

    if args.switch_interval:
        sys.setswitchinterval(args.switch_interval)
    probed: dict = {}
    if args.probe_other:
        def since_boundary(self, where):
            n = probed.setdefault(where, [0, 0.0])
            n[0], n[1] = n[0] + 1, \
                n[1] + time.monotonic() - self._phases._mark

        def probing(name):
            inner = getattr(LLMEngine, name)

            def outer(self, *a, **kw):
                if name == "_iterate":
                    since_boundary(self, "_iterate begins")
                out = inner(self, *a, **kw)
                since_boundary(self, name + " returned")
                return out
            setattr(LLMEngine, name, outer)

        for name in ("_consume_round", "_decode_once", "_iterate"):
            probing(name)
    serving.program_counters = training.program_counters = recording
    tracing.record = recorded
    mf.read_layer_metrics = reading
    if args.rate:
        mf.load_traffic = at_rate
    if args.trace_start:    # the two calls of the harness's it lies between
        serving.make_params, correctness.serving_numbers = made, judged
    t_main = time.monotonic()
    rc = run.main(["--workload", args.workload, "--seconds",
                   str(args.seconds), *rest])
    if snapshots and snapshots[0]:
        programs = getattr(engines[-1], "start_programs", None) \
            if engines else None
        _start_up(snapshots[0], opened[0] - t_main, programs and programs())
        if engines and engines[-1].program_kernels:
            _log("program_kernels: "
                 + json.dumps(sorted(engines[-1].program_kernels)))
        if len(snapshots) >= 2 and snapshots[-1]:
            _compiles_over_the_window(snapshots[0], snapshots[-1])
    if len(snapshots) >= 2 and snapshots[0] and "engine" in snapshots[0]:
        a, b = snapshots[0]["engine"], snapshots[1]["engine"]
        d = {k: b[k] - a[k] for k in b if k in a and k.startswith(
            ("decode_", "sched_", "state_", "prefill_", "mixed_",
             "first_token", "host_gap", "queue_delay"))}
        rounds = d.get("decode_rounds") or 0
        if rounds:
            d["steps_a_round"] = d["decode_steps_dispatched"] / rounds
            if "sched_host_busy_sum_s" in d:
                d["sched_host_busy_ms_a_round"] = \
                    1e3 * d["sched_host_busy_sum_s"] / rounds
        if d.get("prefill_passes"):         # ISSUE 34; the parent has none
            d["prefill_programs_a_pass"] = \
                d["prefill_programs_dispatched"] / d["prefill_passes"]
        if d.get("prefill_programs_dispatched"):
            # ISSUE 56: how full the chunk programs went. The rows that
            # carried a further chunk of a prompt already in the program
            # (``prefill_rows_ahead``) and those that carried nothing
            # (``prefill_rows_dead``) are among the deltas above; the share
            # is of the rows sent (a checkout without them prints neither)
            d["prefill_chunks_a_program"] = d["prefill_chunks_dispatched"] \
                / d["prefill_programs_dispatched"]
            if "prefill_rows_dead" in d:
                d["prefill_rows_dead_share"] = d["prefill_rows_dead"] / (
                    d["prefill_chunks_dispatched"] + d["prefill_rows_dead"])
            # ISSUE 63: the two shares its readers were to print
            # (``engine.step_riding_share`` as the long-document cell's
            # reader takes it, ``engine.rows_ahead_share``), here because
            # BENCHMARK.json's ``per_layer`` list is full (PERF.md section 7)
            if "mixed_programs_dispatched" in d:
                d["step_riding_share"] = 100.0 * d[
                    "mixed_programs_dispatched"] / d[
                        "prefill_programs_dispatched"]
            if "prefill_rows_ahead" in d:
                d["rows_ahead_share"] = 100.0 * d["prefill_rows_ahead"] \
                    / d["prefill_chunks_dispatched"]
        _log(f"window counters: {json.dumps(d, sort_keys=True)}")
        # ISSUE 39: a constant of the engine's load path (the parent has none)
        _log(f"weights_relaid_bytes: {b.get('weights_relaid_bytes')}")
        if "server" in snapshots[0]:
            a, b = snapshots[0]["server"], snapshots[1]["server"]
            _log("window server counters: " + json.dumps(
                {k: b[k] - a[k] for k in b if k in a}, sort_keys=True))
        _window(snapshots[0], snapshots[1], args.seconds)
    for where, (n, total) in probed.items():
        _log(f"probe {where}: {n} x {1e3 * total / n:.3f} ms since the "
             f"last phase boundary = {total:.3f} s")
    _client_side(args.workload, args.seconds)
    return rc


if __name__ == "__main__":
    sys.exit(main())
