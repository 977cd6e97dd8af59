"""The training cell: the program's ``Trainer`` on the cell's mesh, fed the
benchmark's weights and the benchmark's batches, timed between the trainer's
own sync points (the ``log_every`` fetch of the metrics); the benchmark adds
no synchronisation to the hot loop and ends the window by raising from
``on_step``. With ``--trace 2`` it keeps ``on_step`` alive for ``trace_steps``
more steps behind the closed window, with the profiler on, and raises then.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark import architecture, correctness, tracing
from benchmark.device import CompileCounter, memory_peak_bytes
from benchmark.serving import RunFailed, program_counters
from benchmark.traffic import train_batch
from benchmark.weights import make_params, param_shapes


class _WindowOver(Exception):
    """Raised from ``on_step`` to end ``Trainer.run``; private to this file."""


class SeededBatches:
    """The data source the trainer is given: ``batch_at(step)`` is a pure
    function of (seed, step), made by the benchmark's generator."""

    def __init__(self, seed: int, batch: int, seq_len: int, vocab: int):
        self.seed, self.batch, self.seq_len, self.vocab = (
            seed, batch, seq_len, vocab)

    def batch_at(self, step: int) -> np.ndarray:
        return train_batch(self.seed, step, self.batch, self.seq_len,
                           self.vocab)


def trainer_config(conf: dict, traffic: dict, seed: int):
    from kubeflow_tpu.train.trainer import TrainerConfig

    prog = conf["program"]
    return TrainerConfig(
        model=prog["preset"],
        model_overrides={**prog["overrides"],
                         "max_seq_len": traffic["seq_len"]},
        optimizer=dict(conf["trainer"]["optimizer"]),
        data={"global_batch": traffic["global_batch"],
              "seq_len": traffic["seq_len"], "seed": seed & 0x7FFFFFFF},
        steps=int(traffic["max_steps"]), log_every=int(traffic["log_every"]),
        checkpoint_dir=None, checkpoint_every=0, watchdog_enabled=False,
        seed=seed & 0x7FFFFFFF, attn_impl=conf["trainer"]["attn_impl"])


def run(manifest: dict, cell: dict, conf: dict, traffic: dict, *, seed: int,
        seconds: float, trace: int, dev: dict, t_start: float,
        out_dir: str, log) -> dict:
    import jax
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.train.trainer import Trainer

    compiles = CompileCounter()
    program = architecture.part(conf, "program")
    cfg = program.program_config(conf)
    devices = jax.devices()[:dev["count"]]
    mesh = build_mesh(conf["mesh"], devices)
    tcfg = trainer_config(conf, traffic, seed)
    # The benchmark's weights and batches in place of the trainer's own: the
    # reference is given the same arrays and nothing of the program's. It
    # runs BEFORE the trainer exists, while the chips hold the weights alone.
    p_sh = program.param_shardings(
        cfg, mesh, param_shapes(conf, cfg.param_dtype))
    params = make_params(conf, seed, cfg.param_dtype, shardings=p_sh)
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    data = SeededBatches(seed, gb, seq, conf["vocab_size"])
    spec = conf["correctness"]
    axes = tuple(a for a, n in conf["mesh"].items() if n > 1)
    ref_loss, ref_gnorm = correctness.reference_loss_and_grad_norm(
        params, data.batch_at(0), conf, micro=int(spec.get("micro", 0)),
        mesh=mesh, batch_axes=axes or None)
    log(f"reference done at {time.monotonic() - t_start:.1f}s: loss "
        f"{ref_loss:.6f} grad norm {ref_gnorm:.6f}")

    os.makedirs(out_dir, exist_ok=True)
    trainer = Trainer(tcfg, mesh, workdir=None,
                      metrics_path=os.path.join(out_dir, "metrics.jsonl"))
    if (trainer.data_cfg.global_batch, trainer.data_cfg.seq_len) != (gb, seq):
        raise RunFailed(f"the trainer runs {trainer.data_cfg.global_batch} x "
                        f"{trainer.data_cfg.seq_len}, the traffic file says "
                        f"{gb} x {seq}")
    # The optimizer state starts at zero whatever the weights.
    trainer.task.state["params"] = jax.device_put(
        params, trainer.task.state_shardings["params"])
    del params
    trainer.data = data
    log(f"trainer built at {time.monotonic() - t_start:.1f}s: "
        f"{architecture.part(conf, 'counts').params_total(conf) / 1e9:.2f} "
        f"B parameters, mesh "
        f"{conf['mesh']}, batch {gb} x {seq}")

    warm = int(traffic["warmup_steps"])
    trace_at = int(traffic.get("trace_at_step", 4))
    trace_steps = int(traffic.get("trace_steps", 3))
    st = {"first": None, "t0": None, "marks": [], "setup_s": None,
          "trace": None, "trace_on": None, "closed": None,
          "counters_before": None, "counters_after": None}
    trace_dir = os.path.join(out_dir, "trace")

    def trace_on() -> None:
        tracing.start(trace_dir)
        st["trace_on"] = time.monotonic()

    def trace_off() -> None:
        window = time.monotonic() - st["trace_on"]
        st["trace_on"] = None
        st["trace"] = tracing.stop(trace_dir, window)

    def on_step(step: int, metrics: dict) -> None:
        now = time.monotonic()
        if step == 1:
            st["first"] = dict(metrics)
        if step == warm:
            # The window's first mark, (warm, t0): the counters' window
            # runs from here to the last mark, as ``elapsed`` does.
            st["t0"], st["setup_s"] = now, now - t_start
            st["counters_before"] = program_counters(trainer=trainer)
            compiles.start()
            log(f"window opens, setup_s {st['setup_s']:.3f}")
        if st["t0"] is None:
            return
        if st["closed"] is not None:
            # The tail: the window closed at step ``closed`` and its marks
            # stopped there; these steps are only traced.
            if step >= st["closed"] + trace_steps:
                trace_off()
                raise _WindowOver()
            return
        if step > warm and step % tcfg.log_every == 0:
            st["marks"].append((step, now))
            st["counters_after"] = program_counters(trainer=trainer)
        if trace == 1:
            if step == warm + trace_at:
                trace_on()
            elif step == warm + trace_at + trace_steps:
                trace_off()
        if now - st["t0"] >= seconds:
            if trace != 2:
                raise _WindowOver()
            st["closed"] = step
            tracing.warm(os.path.join(out_dir, "trace_warm"))
            trace_on()

    try:
        trainer.run(on_step=on_step)
        raise RunFailed(f"the trainer ran out of its {tcfg.steps} steps "
                        "before the window ended")
    except _WindowOver:
        pass
    finally:
        if st["trace_on"] is not None:
            tracing.abort()
    n_compiles = compiles.stop()
    if n_compiles:
        raise RunFailed(f"{n_compiles} program(s) compiled inside the "
                        f"window: {compiles.names}")
    marks = [(warm, st["t0"])] + st["marks"]
    if len(marks) < 3:
        raise RunFailed(f"only {len(marks) - 1} sync points in the window")
    steps = marks[-1][0] - marks[0][0]
    elapsed = marks[-1][1] - marks[0][1]
    tokens_per_s_chip = steps * gb * seq / elapsed / dev["count"]
    step_times = [(b[1] - a[1]) / (b[0] - a[0])
                  for a, b in zip(marks, marks[1:])]

    first = st["first"] or {}
    numbers = {
        "loss_rel_diff": correctness.relative(first.get("loss", float("nan")),
                                              ref_loss),
        "grad_norm_rel_diff": correctness.relative(
            first.get("grad_norm", float("nan")), ref_gnorm)}
    correct, lines = correctness.judge(numbers, spec["limits"])
    for line in lines:
        log(line)
    log(f"compared beside: program loss {first.get('loss')} grad norm "
        f"{first.get('grad_norm')}; reference loss {ref_loss} grad norm "
        f"{ref_gnorm}")
    log(f"{steps} steps in {elapsed:.3f}s between sync points; step s "
        f"median {float(np.median(step_times)):.4f} "
        f"min {min(step_times):.4f} max {max(step_times):.4f}")

    peak = memory_peak_bytes(devices)
    planned = 0
    if dev["platform"] == "tpu":
        # PR 21: the counter missed the step's temporaries, so the
        # compiler's own plan for the step stands beside it.
        batch0 = trainer.make_global_batch(trainer.data.batch_at(0))
        ma = trainer.task.step_fn.lower(
            trainer.task.state, batch0).compile().memory_analysis()
        planned = int(getattr(ma, "peak_memory_in_bytes", 0)
                      or ma.argument_size_in_bytes + ma.temp_size_in_bytes)
        log(f"memory: peak_bytes_in_use {peak}, compiler's planned peak of "
            f"the step {planned} (arguments {ma.argument_size_in_bytes}, "
            f"temp {ma.temp_size_in_bytes})")
    values = {"setup_s": st["setup_s"],
              "train_tokens_per_s_chip": tokens_per_s_chip}
    traced = st["trace"]
    if trace == 2:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    record = {"kind": "train_steps", "window_s": elapsed, "config": conf,
              "trace": traced, "peaks": dev["peaks"], "values": values,
              "counters_before": st["counters_before"],
              "counters_after": st["counters_after"],
              "host_spans": traced.get("host_spans") if traced else None,
              "train": {"tokens_per_s_chip": tokens_per_s_chip,
                        "seq_len": seq, "steps": steps,
                        "median_step_s": float(np.median(step_times))}}
    with open(os.path.join(out_dir, "train.json"), "w") as f:
        json.dump({"marks": marks, "first": first, "numbers": numbers}, f)
    return {"correct": correct, "attempted": steps, "failed": 0,
            "values": values, "record": record,
            "memory_peak_bytes": max(peak, planned), "traced": st["trace"]}
