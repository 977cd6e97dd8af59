"""A serving cell: the program's engine behind its real HTTP server on a
loopback port, driven by the benchmark's own load generator in a child
process. From the program it takes ``LLMEngine``, ``ModelServer``,
``BatchingSpec``, the engine's counters and, through the configuration's
architecture (``benchmark/architecture.py``), its config object; everything
that measures is the benchmark's.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

from benchmark import architecture, correctness, tracing
from benchmark.device import (
    CompileCounter, memory_peak_bytes, sleep_until,
)
from benchmark.stats import percentile
from benchmark.traffic import build_plan, decode_ids, n_chunks
from benchmark.weights import make_params


class RunFailed(Exception):
    """The run cannot report: it prints no result and exits non-zero."""


class IdTokenizer:
    """Text <-> token ids with no vocabulary in between: "17 4093 2" is the
    three ids 17, 4093 and 2, so prompts reach the model over HTTP as ids
    over its WHOLE vocabulary (the bundled byte tokenizer only ever embeds
    259 of them). No id is an end-of-sequence: a request generates exactly
    ``max_tokens`` tokens, which fixes the work per request."""

    bos_id = 1
    eos_id = -1

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list[int]:
        return decode_ids(text)

    def decode(self, ids: list[int]) -> str:
        return "".join(f"{int(t)} " for t in ids)


def engine_snapshot(engine) -> dict:
    """Running sums and counts of the engine's own counters: they exist
    whatever the traffic did (the conditional keys of
    ``EngineMetrics.snapshot`` do not: PR 23)."""
    m = engine.metrics
    _, _, qd_sum, qd_n = m.queue_delay_histogram()
    _, _, hg_sum, hg_n = m.host_gap_histogram()
    return {"queue_delay_sum_s": qd_sum, "queue_delay_n": qd_n,
            "host_gap_sum_s": hg_sum, "host_gap_n": hg_n,
            "preemptions": m.preemptions, "shed": m.requests_shed,
            "completed": m.requests_completed,
            "decode_rounds": engine.decode_rounds}


def program_counters(**parts) -> dict | None:
    """The program's own total snapshots (``LLMEngine.counters()``,
    ``ModelServer.counters()``, ``Trainer.counters()``), whole, by part:
    a reader works on the difference of two. None where the program has
    no such snapshot."""
    if not all(hasattr(obj, "counters") for obj in parts.values()):
        return None
    return {name: obj.counters() for name, obj in parts.items()}


# The tail of a ``--trace 2`` run sends a second plan: another seed, and
# request indices far from the measured plan's and the warm-up's, so that
# no prompt of the tail shares a prefix with one the window cached.
TAIL_SEED_XOR = 0x5A177A11
TAIL_INDEX_BASE = 2 * 10**6


def tail_plan(traffic: dict, *, seed: int, vocab: int, model: str) -> dict:
    """The traffic that is traced once the window has closed: the same
    distributions for ``trace_start_s`` of ramp and ``trace_seconds`` of
    trace (and a second for the profiler to stop in)."""
    seconds = float(traffic["trace_start_s"]) \
        + float(traffic["trace_seconds"]) + 1.0
    plan = build_plan(traffic, seed=int(seed) ^ TAIL_SEED_XOR,
                      seconds=seconds, vocab=vocab, model=model)
    plan["warmup"] = []
    plan["index_base"] = TAIL_INDEX_BASE
    for r in plan["requests"]:
        r["i"] += TAIL_INDEX_BASE
    return plan


def trace_tail(child, traffic: dict, out_dir: str, log) -> dict:
    """Behind the closed window: start and stop the profiler once for
    nothing, set the tail's traffic going, trace ``trace_seconds`` of it
    after ``trace_start_s`` of ramp. Returns the trace in plain form."""
    t_warm = time.monotonic()
    tracing.warm(os.path.join(out_dir, "trace_warm"))
    child.stdin.write("TAIL\n")
    child.stdin.flush()
    t_tail = time.monotonic()
    sleep_until(t_tail + float(traffic["trace_start_s"]))
    t_on = time.monotonic()
    traced = tracing.record(os.path.join(out_dir, "trace"),
                            float(traffic["trace_seconds"]))
    t_read = time.monotonic()
    if child.stdout.readline().strip() != "TAILDONE":
        raise RunFailed("the load generator died in the tail")
    log(f"tail: profiler warm start {t_tail - t_warm:.3f}s, traced "
        f"{traced['window_s']:.3f}s from {t_on - t_tail:.3f}s on, trace "
        f"stopped and read in {t_read - t_on - traced['window_s']:.3f}s; "
        f"{time.monotonic() - t_warm:.3f}s behind the closed window")
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    return traced


def required_programs(traffic: dict, batching) -> set[str]:
    """Program variants (as ``LLMEngine.program_kernels`` names them) the
    cell's traffic can reach: a chunk prefill per context bucket up to the
    longest prompt plus the longest answer (a preempted request prefills
    again with what it had generated), the decode dispatch at both its
    lengths."""
    from kubeflow_tpu.serve.paged import context_bucket

    chunk, pg = batching.chunked_prefill_tokens, batching.page_size
    mpp = batching.max_seq_len // pg
    longest = sum(traffic[k].get("max", traffic[k].get("value"))
                  for k in ("prompt_len", "output_len"))
    # Any start position, not only multiples of the chunk: the radix prefix
    # index resumes a prefill wherever an earlier prompt stopped matching,
    # and with a 32k vocabulary a first-token match happens a few times a
    # run.
    need = {f"paged_chunk_prefill[1x{chunk},"
            f"{context_bucket(pos, chunk, pg, mpp)}]"
            for pos in range(int(longest))}
    steps = {batching.decode_steps,
             min(batching.decode_steps, batching.prefill_interleave_steps)}
    return need | {f"paged_decode[{k},greedy]" for k in steps}


def warm_first_token_sampler(engine, vocab: int) -> None:
    """The engine samples the first tokens of every prefill that finished in
    one admit pass together, padded to a power of two: as many shapes as
    powers of two up to the slots, and which of them a run meets depends on
    how arrivals fall. Traffic cannot be made to reach each one, so they are
    warmed here, through the engine's own sampler and the same calls
    ``LLMEngine._sample_first_batch`` makes. Before the engine starts."""
    import jax.numpy as jnp

    width = 1
    while width <= engine.num_slots:
        rows = [jnp.zeros((vocab,), jnp.float32)] * width
        out = engine._sampler(
            jnp.stack(rows), engine._next_key(),
            jnp.asarray([0.0] * width, jnp.float32),
            jnp.asarray([0] * width, jnp.int32),
            jnp.asarray([1.0] * width, jnp.float32), "greedy")
        out.block_until_ready()
        width *= 2


def reduce_requests(results: list[dict], seconds: float) -> dict:
    """From the generator's stamps to what the cell reports. A request
    failed if it errored, timed out, returned another number of tokens than
    asked, or an id outside the vocabulary. A closed loop's request that
    the end of the window cut was neither completed nor failed; the tokens
    it was given inside the window are work the window did."""
    done, failed, cut = [], [], 0
    for r in results:
        if r.get("cut"):
            cut += 1
        elif (r["ok"] and r["n_tokens"] == r["max_tokens"]
              and r["ids_in_vocab"]):
            done.append(r)
        else:
            failed.append(r)
    in_window = [r for r in done if r["end"] <= seconds]
    ttft = [(r["token_t"][0] - r["due"]) * 1e3 for r in done]
    gaps = [(b - a) * 1e3 for r in done
            for a, b in zip(r["token_t"], r["token_t"][1:])]
    late = [(r["sent"] - r["due"]) * 1e3 for r in results]
    # Work the window saw, token by token: a prompt counts when its first
    # token arrives (its prefill is then done), a generated token when it
    # arrives. A request the window's end cut has done part of its work
    # inside the window, and that part counts; what was still being
    # prefilled then does not.
    served = done + [r for r in results if r.get("cut")]
    prefilled = [r["prompt_len"] for r in served
                 if r["token_t"] and r["token_t"][0] <= seconds]
    generated = sum(1 for r in served for t in r["token_t"] if t <= seconds)
    return {
        "attempted": len(done) + len(failed), "failed": len(failed),
        "cut": cut, "completed": len(done),
        "completed_in_window": len(in_window),
        "errors": sorted({str(r.get("error")) for r in failed})[:5],
        "ttft_ms": ttft, "itl_ms": gaps, "late_ms": late,
        "prompt_lens_in_window": prefilled,
        "tokens_in_window": sum(prefilled) + generated,
    }


def window_numbers(results_path: str, traffic: dict, seconds: float,
                   setup_s: float, log) -> tuple[dict, dict]:
    """The closed window's numbers from the generator's results: the
    reduced requests and the cell's end-to-end values."""
    with open(results_path) as f:
        gen = json.load(f)
    if gen["warmup_errors"]:
        raise RunFailed(f"warm-up failed: {gen['warmup_errors']}")
    red = reduce_requests(gen["results"], seconds)
    log(f"requests: attempted {red['attempted']} failed {red['failed']} "
        f"cut {red['cut']} completed in window {red['completed_in_window']} "
        f"errors {red['errors']}")
    if not red["completed"]:
        raise RunFailed("no request completed")

    values = {"setup_s": setup_s}
    if traffic["kind"] == "open_loop":
        values["itl_p95_ms"] = percentile(red["itl_ms"], 95)
        log(f"ttft ms p50 {percentile(red['ttft_ms'], 50):.1f} "
            f"p95 {percentile(red['ttft_ms'], 95):.1f} "
            f"n {len(red['ttft_ms'])}; "
            f"itl ms p50 {percentile(red['itl_ms'], 50):.2f} "
            f"p95 {values['itl_p95_ms']:.1f} n {len(red['itl_ms'])}")
    else:
        values["serve_tokens_per_s"] = red["tokens_in_window"] / seconds
        log(f"serve_tokens_per_s {values['serve_tokens_per_s']:.1f}")
    return red, values


def run(manifest: dict, cell: dict, conf: dict, traffic: dict, *, seed: int,
        seconds: float, trace: int, dev: dict, t_start: float,
        out_dir: str, log) -> dict:
    import jax

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine
    from kubeflow_tpu.serve.server import ModelServer

    compiles = CompileCounter()
    cfg = architecture.part(conf, "program").program_config(conf)
    counts = architecture.part(conf, "counts")
    batching = BatchingSpec(**traffic["engine"])
    params = make_params(conf, seed, cfg.param_dtype)
    engine = LLMEngine(cfg, batching, params=params, seed=seed & 0x7FFFFFFF)
    log(f"engine built at {time.monotonic() - t_start:.1f}s: "
        f"{counts.params_total(conf) / 1e9:.2f} B parameters, "
        f"{engine._num_pages} pages of {engine.page_size}")

    numbers = correctness.serving_numbers(engine, params, conf,
                                          conf["correctness"], seed)
    correct, lines = correctness.judge(numbers, conf["correctness"]["limits"])
    for line in lines:
        log(line)
    log(f"compared beside: {json.dumps(numbers)}")
    log(f"correctness done at {time.monotonic() - t_start:.1f}s")

    warm_first_token_sampler(engine, conf["vocab_size"])
    os.makedirs(out_dir, exist_ok=True)
    plan = build_plan(traffic, seed=seed, seconds=seconds,
                      vocab=conf["vocab_size"], model=cell["config"])
    plan_path = os.path.join(out_dir, "plan.json")
    results_path = os.path.join(out_dir, "loadgen.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    tail_args = []
    if trace == 2:
        tail_args = [os.path.join(out_dir, "tail_plan.json"),
                     os.path.join(out_dir, "tail_loadgen.json")]
        with open(tail_args[0], "w") as f:
            json.dump(tail_plan(traffic, seed=seed, vocab=conf["vocab_size"],
                                model=cell["config"]), f)
    server = ModelServer(cell["config"], engine,
                         tokenizer=IdTokenizer(conf["vocab_size"]))
    server.start()
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen", plan_path, results_path,
         server.url] + tail_args,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    traced = None
    try:
        if child.stdout.readline().strip() != "READY":
            raise RunFailed("the load generator died during warm-up")
        if engine.program_kernels:       # recorded on the TPU only
            missing = required_programs(traffic, batching) \
                - set(engine.program_kernels)
            if missing:
                raise RunFailed(
                    f"warm-up did not reach {sorted(missing)}; it reached "
                    f"{sorted(engine.program_kernels)}")
        before = engine_snapshot(engine)
        counters_before = program_counters(engine=engine, server=server)
        compiles.start()
        child.stdin.write("GO\n")
        child.stdin.flush()
        t0 = time.monotonic()
        setup_s = t0 - t_start
        log(f"window opens, setup_s {setup_s:.3f}")
        if trace == 1:
            t_on = t0 + float(traffic.get("trace_start_s", 0.4 * seconds))
            sleep_until(t_on)
            traced = tracing.record(
                os.path.join(out_dir, "trace"),
                float(traffic.get("trace_seconds", 3.0)))
        sleep_until(t0 + seconds)
        after = engine_snapshot(engine)
        counters_after = program_counters(engine=engine, server=server)
        if trace != 2:
            compiles.stop()
        if child.stdout.readline().strip() != "DONE":
            raise RunFailed("the load generator died in the window")
        # The window is closed: its numbers are taken here, before any
        # tail starts.
        red, values = window_numbers(results_path, traffic, seconds,
                                     setup_s, log)
        memory_peak = memory_peak_bytes(jax.local_devices())
        if trace == 2:
            # The compile counter stays on: a compile in the tail fails
            # the run as one in the window does.
            traced = trace_tail(child, traffic, out_dir, log)
            compiles.stop()
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        tracing.abort()
        server.stop()
    if compiles.count:
        raise RunFailed(f"{compiles.count} program(s) compiled inside the "
                        f"window: {compiles.names}")
    chunk = batching.chunked_prefill_tokens
    lens = red["prompt_lens_in_window"] or [r["prompt_len"]
                                            for r in plan["requests"]]
    run_record = {
        "kind": traffic["kind"], "window_s": seconds, "config": conf,
        "engine_before": before, "engine_after": after,
        "counters_before": counters_before, "counters_after": counters_after,
        "loadgen": red, "trace": traced, "peaks": dev["peaks"],
        "host_spans": traced.get("host_spans") if traced else None,
        "weight_bytes_per_param": jax.numpy.dtype(cfg.param_dtype).itemsize,
        "prefill": {
            "chunk": chunk, "mean_useful_flops_per_chunk":
                sum(counts.prefill_flops(conf, n) for n in lens)
                / sum(n_chunks(n, chunk) for n in lens)},
        "values": values,
    }
    return {"correct": correct, "attempted": red["attempted"],
            "failed": red["failed"], "values": values, "record": run_record,
            "memory_peak_bytes": memory_peak, "traced": traced}
