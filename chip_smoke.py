#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the platform starts on the chip.

Drives the platform's two main paths once, end to end, through the entry
points a user calls: a manifest submitted to a ``ControlPlane``, and a worker
process that owns the chip. The model is Gemma-2B at its published widths
(8 query heads / 1 KV head x 256, hidden 2048, MLP 16384, vocab 256128, tied
head, GeGLU, (1+w) norms); only depth, batch and sequence length are cut,
and the weights are random, made from ``--seed``. This is a smoke test, not
a benchmark: the times it prints are not records and go into no file.

    python chip_smoke.py             # one chip: a JAXJob, then an InferenceService
    python chip_smoke.py --chips 4   # four chips: fsdp=4 training and a model=4
                                     # engine, each against its one-device
                                     # reference — and no other phase

One process per chip: this parent never initialises a JAX backend (it checks
that before it reports). What it knows of the device it learns from a probe
child that has exited before any worker starts, and from what the workers
report (``device_report.json``, ``GET /debug/device``). It needs a TPU: with
``JAX_PLATFORMS`` holding the program to the CPU, with no accelerator, or in
a directory that holds nothing else of the repo, it exits non-zero and
prints no result. Any failed check makes it exit non-zero.

Each phase prints one JSON line; the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Worker logs, metrics and reports stay under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import random
import shutil
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

MODEL = "gemma-2b"
# -- sizes, settled with the chip's compiler (tests/test_aot_8b.py compiles
# exactly these programs for a described v5e under ``slow``) ------------------
# One chip, 15.75 GB usable: 4 of Gemma-2B's 18 layers carry the full
# embedding (0.97 B parameters; fp32 weights, bf16 first and fp32 second Adam
# moment).
TRAIN_ONE = {"n_layers": 4, "global_batch": 2, "seq_len": 2048, "steps": 6}
# Four chips, fsdp=4: the depth one chip cannot hold, 2 sequences per chip.
TRAIN_FOUR = {"n_layers": 8, "global_batch": 8, "seq_len": 2048, "steps": 4}
# The pair that is compared (same seed, same global batch, fsdp=4 against one
# device): cut until the one-device side fits a chip.
TRAIN_PAIR = {"n_layers": 2, "global_batch": 4, "seq_len": 1024, "steps": 2}
# Serving, full depth in bf16: 32 slots x 4096 tokens over 1024 pages of 128,
# 512-token prefill chunks, 32 decode steps per dispatch.
BATCHING = {"paged": True, "max_batch_size": 32, "max_seq_len": 4096,
            "page_size": 128, "max_pages": 1024,
            "chunked_prefill_tokens": 512, "decode_steps": 32}
SERVE_OVERRIDES = {"dtype": "bfloat16", "param_dtype": "bfloat16"}
# (prompt tokens, new tokens): a few hundred to about 2k in, 32-64 out.
TRAFFIC = [(300, 32), (700, 64), (1100, 32), (1500, 64), (1900, 32),
           (2040, 64)]

# What the first loss must be, from the initialisation alone. The embedding
# is drawn from a unit normal truncated at +-2 (std 0.8796; layers.py
# init_embedding) and Gemma ties the head to it, so at step one the logit of
# each INPUT token is h.e = |e|^2 / rms(e) = hidden x 0.8796 = 1801, every
# other logit is noise of std ~40, and the loss is that gap (the target is
# another token). Not ln(vocab): this init predicts its own input, loudly.
EMBED_INIT_STD = 0.8796
FIRST_LOSS_OVER_PREDICTED = (0.8, 1.2)

# -- tolerances of the four-chip comparisons ----------------------------------
# fsdp=4 against one device, first step, same seed and batch. Activations are
# bf16 (8 bits of mantissa, ~4e-3 per rounding), the mesh side runs XLA's
# norm/GeGLU/cross-entropy where the one-device side runs the fused kernels,
# and its gradients are reduce-scattered in another order. The loss is a mean
# over 8k tokens, so roundings average out; the gradient norm sums squares of
# a billion bf16-derived entries and is the looser of the two.
LOSS_RTOL = 2e-2
GRAD_NORM_RTOL = 5e-2
# model=4 engine against the one-chip engine, greedy, same prompts: the FIRST
# token of each request must agree for at least this share of requests. The
# weights are random, so the logits over 256128 entries are close to flat and
# the top two can swap on a last-bit difference — and tensor parallelism
# changes every reduction's order (psum of four partial matmuls). Identity is
# therefore not required; after a first disagreement greedy decoding follows
# another path, so later tokens are reported, not judged.
FIRST_TOKEN_AGREEMENT = 0.75


class SmokeFailure(Exception):
    """A phase's pass condition did not hold."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# -- manifests (what a user would `kftpu apply`) -------------------------------

def train_job(name: str, sizes: dict, *, seed: int, chips: int,
              fsdp: int = 1) -> dict:
    return {
        "apiVersion": "training.tpu.kubeflow.dev/v1", "kind": "JAXJob",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            # Checkpoints of a ~10 GB state are not what a smoke run is for.
            "run_policy": {"checkpoint": {"enabled": False}},
            "replica_specs": {"worker": {
                "replicas": 1,
                # A failure here is a finding, not weather: no restarts
                # (each would compile again on the chip's clock).
                "restart_policy": "Never",
                "template": {"entrypoint": "llm_pretrain", "config": {
                    "model": MODEL,
                    "model_overrides": {
                        "n_layers": sizes["n_layers"],
                        "max_seq_len": sizes["seq_len"],
                        "remat_policy": "dots_flash"},
                    "attn_impl": "pallas",
                    "optimizer": {"mu_dtype": "bfloat16", "warmup_steps": 0},
                    "data": {"global_batch": sizes["global_batch"],
                             "seq_len": sizes["seq_len"], "seed": seed},
                    "seed": seed,
                    "steps": sizes["steps"],
                    "log_every": 1,
                }},
                "resources": {"tpu_chips": chips},
            }},
            **({"parallelism": {"fsdp": fsdp}} if fsdp > 1 else {}),
        },
    }


def inference_service(name: str, *, model_parallel: int = 1) -> dict:
    return {
        "apiVersion": "serving.tpu.kubeflow.dev/v1",
        "kind": "InferenceService",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"predictor": {
            "model": {"model_format": "llm", "model_name": name,
                      "config": {"preset": MODEL,
                                 "overrides": SERVE_OVERRIDES}},
            "min_replicas": 1, "max_replicas": 1,
            "batching": BATCHING,
            **({"parallelism": {"model": model_parallel}}
               if model_parallel > 1 else {}),
        }},
    }


# -- helpers -------------------------------------------------------------------

def model_facts(n_layers: int | None = None, **overrides) -> dict:
    from kubeflow_tpu.models.config import preset

    if n_layers is not None:
        overrides["n_layers"] = n_layers
    cfg = preset(MODEL, **overrides)
    return {"model": MODEL, "hidden": cfg.hidden, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "mlp_dim": cfg.mlp_dim, "vocab_size": cfg.vocab_size,
            "n_layers": cfg.n_layers, "params": cfg.num_params()}


def worker_log_tail(cp, n: int = 60) -> str:
    logs = os.path.join(cp.config.base_dir, "logs")
    out = []
    for name in sorted(os.listdir(logs)) if os.path.isdir(logs) else []:
        with open(os.path.join(logs, name), errors="replace") as f:
            out.append(f"--- {name} ---\n" + "".join(f.readlines()[-n:]))
    return "\n".join(out)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def check_device(report: dict, probe: dict) -> dict:
    """The worker's own view of the device must be the chip the probe saw."""
    seen = {k: report[k] for k in ("platform", "device_kind", "device_count")}
    check(seen["platform"] == "tpu",
          f"worker ran on {seen['platform']!r}, not on the tpu")
    check(seen["device_kind"] == probe["device_kind"]
          and seen["device_count"] == probe["count"],
          f"worker saw {seen}, the probe saw {probe}")
    cache = report["compile_cache"]
    check(cache is not None and cache["entries"] > 0,
          f"the persistent compile cache holds nothing: {cache}")
    # The worker got past its compiles WITH the perf flags, delivered where
    # the installed stack accepts them.
    flags = report["flags"]
    check("--xla_tpu_enable_latency_hiding_scheduler" in
          flags["LIBTPU_INIT_ARGS"] and "xla_tpu" not in flags["XLA_FLAGS"],
          f"perf flags not where they belong: {flags}")
    return {**seen, "perf_flags_via": "LIBTPU_INIT_ARGS"}


def expect_kernels(program: str, kernels: dict, required: tuple) -> None:
    """A branch that silently took XLA (or the interpreter) fails the phase:
    every kernel family the smoke's model should reach must be IN the
    program the chip was given."""
    missing = [name for name in required if name not in kernels]
    check(not missing,
          f"{program}: Pallas kernels {missing} are not in the lowered "
          f"program (found {sorted(kernels)}): a layer took the XLA branch")


def check_every_device_holds_bytes(who: str, bytes_in_use: list) -> None:
    """Code that has never run on more than one chip may put everything on
    the first."""
    check(len(bytes_in_use) == 4 and all(b and b > 0 for b in bytes_in_use),
          f"{who}: per-device bytes_in_use {bytes_in_use}: not every one "
          "of four devices holds something")


def peak_bytes(report: dict) -> list:
    return [m["peak_bytes_in_use"] for m in report["memory"]]


# Kernels by the names their pallas_calls carry into the lowered program.
FLASH = ("flash_attention_fwd", "flash_attention_bwd_dkdv",
         "flash_attention_bwd_dq")
FUSED_TRAIN = ("fused_xent_fwd", "fused_xent_bwd_dh", "fused_xent_bwd_dw",
               "rmsnorm_fwd", "rmsnorm_bwd", "add_rmsnorm_fwd",
               "glu_fwd", "glu_bwd")
FUSED_SERVE = ("rmsnorm_fwd", "glu_fwd")
PAGED_ATTN = ("paged_decode_attention",)


# -- the train phase -----------------------------------------------------------

def run_train(cp, manifest: dict, probe: dict, *, timeout: float) -> dict:
    """Submit a JAXJob, follow its metrics, wait for Succeeded; returns the
    phase's facts. Times are host clock readings of a polled file (±0.2 s):
    set-up + compile runs to the first logged step, run covers the rest."""
    from kubeflow_tpu.core.manifest import load_manifest
    from kubeflow_tpu.runtime.device_report import read_device_report

    job = load_manifest(manifest)
    workdir = os.path.join(cp.jaxjob_reconciler.job_dir(job), "worker-0")
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    t0 = time.monotonic()
    cp.submit(job)
    rows: list[dict] = []
    t_first = t_last = None
    deadline = t0 + timeout
    while True:
        if os.path.exists(metrics_path):
            with open(metrics_path) as f:
                seen = [json.loads(ln) for ln in f if ln.strip()]
            if len(seen) > len(rows):
                t_last = time.monotonic()
                if not rows:
                    t_first = t_last
                rows = seen
        cur = cp.get_job(job.metadata.name)
        check(cur is not None, f"job {job.metadata.name} disappeared")
        if cur.status.has_condition("Failed"):
            cond = cur.status.get_condition("Failed")
            raise SmokeFailure(
                f"job {job.metadata.name} Failed: {cond.reason} "
                f"{cond.message}\n{worker_log_tail(cp)}")
        if cur.status.has_condition("Succeeded"):
            break
        check(time.monotonic() < deadline,
              f"job {job.metadata.name} not Succeeded in {timeout:.0f}s\n"
              f"{worker_log_tail(cp)}")
        time.sleep(0.2)
    with open(metrics_path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    steps = manifest["spec"]["replica_specs"]["worker"]["template"][
        "config"]["steps"]
    check([r["step"] for r in rows] == list(range(1, steps + 1)),
          f"expected steps 1..{steps} in metrics.jsonl, got "
          f"{[r['step'] for r in rows]}")
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss among {losses}")
    check(all(math.isfinite(r["grad_norm"]) for r in rows),
          "non-finite gradient norm")
    report = read_device_report(workdir)
    check(report is not None, f"no device_report.json in {workdir}")
    t_first = t_first if t_first is not None else time.monotonic()
    return {
        "job": job.metadata.name, "condition": "Succeeded",
        **check_device(report, probe),
        "losses": losses, "first_loss": losses[0], "last_loss": losses[-1],
        "first_grad_norm": rows[0]["grad_norm"],
        "setup_compile_s": round(t_first - t0, 1),
        "run_s": round((t_last or t_first) - t_first, 1),
        "run_steps": steps - 1,
        "peak_bytes_in_use": peak_bytes(report),
        "bytes_in_use": [m["bytes_in_use"] for m in report["memory"]],
        "mesh": report["mesh"],
        "largest_param": report["largest_param"],
        "compile_cache": report["compile_cache"],
        "kernels": report["programs"]["train_step"],
    }


def phase_train_one_chip(cp, probe: dict, seed: int) -> dict:
    out = run_train(cp, train_job("smoke-train", TRAIN_ONE, seed=seed,
                                  chips=1), probe, timeout=900)
    # One device, fused_kernels=auto, attn_impl=pallas: every fused layer
    # and the flash kernels are expected in the step that ran.
    expect_kernels("train_step", out["kernels"], FLASH + FUSED_TRAIN)
    predicted = model_facts()["hidden"] * EMBED_INIT_STD
    lo, hi = (predicted * f for f in FIRST_LOSS_OVER_PREDICTED)
    check(lo < out["first_loss"] < hi,
          f"first loss {out['first_loss']:.1f} is not within "
          f"{FIRST_LOSS_OVER_PREDICTED} of the {predicted:.0f} that the "
          "initialisation predicts")
    return {"phase": "train", **model_facts(TRAIN_ONE["n_layers"]),
            **{k: TRAIN_ONE[k] for k in ("global_batch", "seq_len")},
            "branches": {"fused_kernels": "pallas", "attention": "pallas"},
            **out}


# -- the serve phase -----------------------------------------------------------

def prompt_text(n_tokens: int, rng: random.Random) -> str:
    """ASCII text the byte tokenizer turns into exactly ``n_tokens`` ids
    (one per character, plus BOS)."""
    return "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ")
                   for _ in range(n_tokens - 1))


def complete(url: str, model: str, prompt: str, max_tokens: int) -> dict:
    body = json.dumps({"model": model, "prompt": prompt,
                       "max_tokens": max_tokens, "temperature": 0.0,
                       "timeout": 900}).encode()
    req = urllib.request.Request(
        url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def run_serve(cp, manifest: dict, probe: dict, seed: int, *,
              timeout: float) -> dict:
    """Submit an InferenceService, wait for Ready, send the traffic over
    HTTP at ``status.url`` (the router), read the replica's device report,
    delete the service and see the replica exit."""
    from kubeflow_tpu.core.jobs import Worker
    from kubeflow_tpu.core.manifest import load_manifest
    from kubeflow_tpu.core.serving import InferenceService
    from kubeflow_tpu.serve.isvc_controller import LABEL_ISVC

    isvc = load_manifest(manifest)
    name = isvc.metadata.name
    vocab = model_facts()["vocab_size"]
    t0 = time.monotonic()
    cp.submit(isvc)
    try:
        ready = cp.wait_for(isvc, "Ready", timeout=timeout, poll=0.2)
    except TimeoutError as exc:
        raise SmokeFailure(f"{exc}\n{worker_log_tail(cp)}") from exc
    t_ready = time.monotonic()
    url = ready.status.url
    rng = random.Random(seed)

    def answers(batch, at_once: bool) -> list:
        work = [(prompt_text(n, rng), new) for n, new in batch]
        if not at_once:
            return [complete(url, name, p, new) for p, new in work]
        with concurrent.futures.ThreadPoolExecutor(len(work)) as pool:
            futs = [pool.submit(complete, url, name, p, new)
                    for p, new in work]
            return [f.result() for f in futs]

    def judge(batch, outs) -> list:
        ids = []
        for (n_prompt, new), out in zip(batch, outs):
            usage, choice = out["usage"], out["choices"][0]
            check(usage["prompt_tokens"] == n_prompt,
                  f"prompt of {n_prompt} tokens arrived as {usage}")
            check(usage["completion_tokens"] == new
                  and len(choice["token_ids"]) == new,
                  f"asked for {new} tokens, got {usage} "
                  f"(finish_reason={choice['finish_reason']})")
            check(all(0 <= t < vocab for t in choice["token_ids"]),
                  f"token id outside [0, {vocab})")
            ids.append(choice["token_ids"])
        return ids

    # Round 1, one request at a time: every program the traffic needs
    # compiles here (a chunk-prefill per context bucket, the decode
    # dispatch, the samplers). Round 2, fresh prompts of the same lengths,
    # all at once: continuous batching over one multi-step decode dispatch.
    warm = judge(TRAFFIC, answers(TRAFFIC, at_once=False))
    t_warm = time.monotonic()
    judge(TRAFFIC, answers(TRAFFIC, at_once=True))
    t_run = time.monotonic()

    workers = cp.store.list(Worker, namespace="default",
                            label_selector={LABEL_ISVC: name})
    check(len(workers) == 1, f"expected one replica, found {len(workers)}")
    replica = f"http://127.0.0.1:{workers[0].spec.template.config['port']}"
    with urllib.request.urlopen(replica + "/debug/device", timeout=30) as r:
        report = json.loads(r.read())

    cp.store.delete(InferenceService, name, "default")
    gone = time.monotonic() + 60
    while cp.runtime.procman.alive():
        check(time.monotonic() < gone,
              f"replica still running 60 s after the service was deleted: "
              f"{cp.runtime.procman.alive()}")
        time.sleep(0.2)
    return {
        "service": name, "requests": 2 * len(TRAFFIC),
        "answered": 2 * len(TRAFFIC), "replica_exited": True,
        **check_device(report, probe),
        "setup_s": round(t_ready - t0, 1),
        "compile_round_s": round(t_warm - t_ready, 1),
        "run_round_s": round(t_run - t_warm, 1),
        "peak_bytes_in_use": peak_bytes(report),
        "bytes_in_use": [m["bytes_in_use"] for m in report["memory"]],
        "compile_cache": report["compile_cache"],
        "programs": report["programs"][name],
        "warm_token_ids": warm,
    }


def serve_branches(programs: dict, *, mesh: bool) -> dict:
    """Which branch each device switch took, read from the programs the
    engine dispatched (LLMEngine.program_kernels), not from the spec."""
    decode = {k: v for k, v in programs.items()
              if k.startswith("paged_decode[")}
    chunks = {k: v for k, v in programs.items()
              if k.startswith("paged_chunk_prefill[")}
    check(decode and chunks,
          f"no paged decode / chunk-prefill program ran: {sorted(programs)}")
    # A round's length is the scheduler's choice under its cap; the
    # engine compiles and runs every length it can choose when it is built.
    steps = BATCHING["decode_steps"]
    check(all(any(k.startswith(f"paged_decode[{n},") for k in decode)
              for n in (1, steps)),
          f"the decode program is not there at one step and at its cap, "
          f"{steps}: {sorted(decode)}")

    def has(kernels, names):
        return any(n in kernels for n in names)

    branches = {
        # paged_attn_impl after "auto": the direct-page-read kernel on one
        # chip, the XLA gather under a mesh (engine.py, by design).
        "paged_attn_impl": "pallas" if all(
            has(v, PAGED_ATTN) for v in decode.values()) else "gather",
        "fused_kernels": "pallas" if all(
            all(n in v for n in FUSED_SERVE)
            for v in {**decode, **chunks}.values()) else "xla",
        # Paged mode prefills in chunks through the cached-attention XLA
        # path: the engine has no kernel choice there (serve/paged.py), so
        # "xla" is the expected branch, per context bucket.
        "prefill_attention": {k: "pallas" if has(v, FLASH) else "xla"
                              for k, v in sorted(chunks.items())},
    }
    if not mesh:
        check(branches["paged_attn_impl"] == "pallas",
              f"decode took the gather branch on one chip: {decode}")
        check(branches["fused_kernels"] == "pallas",
              f"a serving program ran without the fused norm/GeGLU "
              f"kernels: {programs}")
    return branches


def phase_serve_one_chip(cp, probe: dict, seed: int) -> dict:
    out = run_serve(cp, inference_service("smoke-serve"), probe, seed,
                    timeout=600)
    out.pop("warm_token_ids")
    return {"phase": "serve", **model_facts(**SERVE_OVERRIDES),
            "batching": BATCHING,
            "branches": serve_branches(out["programs"], mesh=False), **out}


# -- four chips ----------------------------------------------------------------

def phase_train_four_chips(cp, probe: dict, seed: int) -> dict:
    big = run_train(cp, train_job("smoke-fsdp4", TRAIN_FOUR, seed=seed,
                                  chips=4, fsdp=4), probe, timeout=900)
    check(big["mesh"] == {"fsdp": 4}, f"mesh was {big['mesh']}")
    check(len(set(big["largest_param"]["devices"])) == 4,
          f"the largest parameter's shards sit on "
          f"{big['largest_param']['devices']}, not on four devices")
    check_every_device_holds_bytes("fsdp=4 trainer", big["bytes_in_use"])
    # Under a mesh the fused kernels give way to XLA (models/layers.py) and
    # flash attention runs per shard through shard_map.
    expect_kernels("train_step[fsdp=4]", big["kernels"], FLASH)
    pair = {}
    for side, chips, fsdp in (("fsdp4", 4, 4), ("one_device", 1, 1)):
        pair[side] = run_train(
            cp, train_job(f"smoke-pair-{side.replace('_', '-')}",
                          TRAIN_PAIR, seed=seed, chips=chips, fsdp=fsdp),
            probe, timeout=900)
    check(pair["one_device"]["mesh"] == {}
          and len(set(pair["one_device"]["largest_param"]["devices"])) == 1,
          f"the reference did not run on one device: "
          f"{pair['one_device']['mesh']} "
          f"{pair['one_device']['largest_param']}")
    diffs = {}
    for key, rtol in (("first_loss", LOSS_RTOL),
                      ("first_grad_norm", GRAD_NORM_RTOL)):
        a, b = pair["fsdp4"][key], pair["one_device"][key]
        diffs[key] = {"fsdp4": a, "one_device": b,
                      "rel_diff": abs(a - b) / abs(b), "rtol": rtol}
        check(abs(a - b) <= rtol * abs(b),
              f"{key}: fsdp=4 {a} vs one device {b} differ by more than "
              f"{rtol}")
    return {"phase": "train[fsdp=4]", **model_facts(TRAIN_FOUR["n_layers"]),
            **{k: TRAIN_FOUR[k] for k in ("global_batch", "seq_len")},
            "branches": {"fused_kernels": "xla (mesh)",
                         "attention": "pallas via shard_map"},
            **big, "pair_sizes": TRAIN_PAIR, "pair": diffs,
            "pair_kernels": {s: sorted(p["kernels"])
                             for s, p in pair.items()}}


def phase_serve_four_chips(cp, probe: dict, seed: int) -> dict:
    tp = run_serve(cp, inference_service("smoke-tp4", model_parallel=4),
                   probe, seed, timeout=600)
    one = run_serve(cp, inference_service("smoke-tp1"), probe, seed,
                    timeout=600)
    a, b = tp.pop("warm_token_ids"), one.pop("warm_token_ids")
    first = [x[0] == y[0] for x, y in zip(a, b)]
    prefix = []
    for x, y in zip(a, b):
        n = 0
        while n < len(x) and x[n] == y[n]:
            n += 1
        prefix.append(n)
    agreement = sum(first) / len(first)
    check(agreement >= FIRST_TOKEN_AGREEMENT,
          f"model=4 and one-chip engines agree on the first token of "
          f"{sum(first)}/{len(first)} requests, under "
          f"{FIRST_TOKEN_AGREEMENT}")
    check_every_device_holds_bytes("model=4 engine", tp["bytes_in_use"])
    return {"phase": "serve[model=4]", **model_facts(**SERVE_OVERRIDES),
            "batching": BATCHING,
            "branches": serve_branches(tp["programs"], mesh=True), **tp,
            "against_one_chip": {
                "first_token_agreement": agreement,
                "threshold": FIRST_TOKEN_AGREEMENT,
                "matching_prefix_tokens": prefix,
                "new_tokens": [new for _, new in TRAFFIC],
                "one_chip_branches": serve_branches(one["programs"],
                                                    mesh=False),
                "one_chip_peak_bytes_in_use": one["peak_bytes_in_use"]}}


# -- main ----------------------------------------------------------------------

def parent_backend_initialised() -> bool:
    bridge = sys.modules.get("jax._src.xla_bridge")
    return bridge is not None and bridge.backends_are_initialized()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the train and serve phases on one chip "
                         "(default). 4: the fsdp=4 / model=4 paths and their "
                         "one-device references, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the trainer's weights and data and the "
                         "prompts")
    args = ap.parse_args(argv)

    held = os.environ.get("JAX_PLATFORMS", "")
    if held and "tpu" not in held.split(","):
        log(f"JAX_PLATFORMS={held!r} holds the program off the TPU; this "
            "script proves the chip path and has no other")
        return 2
    try:
        from kubeflow_tpu.operator.control_plane import (
            ControlPlane, ControlPlaneConfig,
        )
        from kubeflow_tpu.runtime.topology import (
            chip_for_device_kind, detect_local_cluster, probe_devices,
        )
    except ImportError as exc:
        log(f"the kubeflow_tpu package is not beside this script: {exc}")
        return 2
    try:
        probe = probe_devices("tpu")
    except RuntimeError as exc:
        log(f"no TPU: {exc}")
        return 2
    if probe["count"] != args.chips:
        log(f"--chips {args.chips} needs exactly that many chips; the "
            f"probe found {probe['count']} ({probe['device_kind']})")
        return 2

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    cp = ControlPlane(ControlPlaneConfig(
        base_dir=OUT_DIR, platform="tpu",
        # The probe has already said what is here: no second one.
        cluster=detect_local_cluster(
            num_chips=probe["count"], platform="tpu",
            generation=chip_for_device_kind(probe["device_kind"]).name),
        # A worker compiling an 18-layer program beats no heart for a while
        # on a busy host; the lease is not what this run tests.
        heartbeat_timeout=300.0))
    phases = ([phase_train_one_chip, phase_serve_one_chip]
              if args.chips == 1 else
              [phase_train_four_chips, phase_serve_four_chips])
    cp.start()
    try:
        check(cp.observation_store.backend == "native",
              "the control plane's metadata store is not the native "
              "library built from the committed source")
        for phase in phases:
            log(f"{phase.__name__} ...")
            line = phase(cp, probe, args.seed)
            check(not parent_backend_initialised(),
                  "this parent process initialised a JAX backend")
            print(json.dumps(line), flush=True)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    finally:
        cp.stop()
    print(json.dumps({"ok": True, "device": {
        "platform": probe["platform"], "kind": probe["device_kind"],
        "count": probe["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
