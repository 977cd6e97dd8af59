"""The trainer loop a JAXJob worker runs, plus its config.

Ties together: mesh (from the worker bootstrap), sharded train state, data
sharding per process, step loop, orbax checkpoint/resume with data
fast-forward, and metric emission. This loop IS the reference's "user
container training script" — but owned by the platform, so checkpointing,
metrics, and elasticity are guaranteed rather than hoped for.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import signal
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from kubeflow_tpu.models.config import DecoderConfig, preset
from kubeflow_tpu.obs import profiler
from kubeflow_tpu.obs.profiler import hot_span
from kubeflow_tpu.obs.trace import get_tracer
from kubeflow_tpu.runtime.bootstrap import (
    EXIT_PREEMPTED, compile_counters, watch_compiles,
)
from kubeflow_tpu.runtime.device_report import (
    lowered_kernel_calls, write_device_report,
)
from kubeflow_tpu.runtime.sanitize import mark_compile_warm, recompile_report
from kubeflow_tpu.runtime.topology import chip_for_device_kind
from kubeflow_tpu.train.checkpoint import CheckpointManager, resume_from_tiers
from kubeflow_tpu.train.data import DataConfig, make_data_source
from kubeflow_tpu.train.metrics import MetricsEmitter, Throughput
from kubeflow_tpu.train.optim import OptimizerConfig
from kubeflow_tpu.train.step import setup_train
from kubeflow_tpu.train.survival import GoodputLedger, StepWatchdog

logger = logging.getLogger("kubeflow_tpu.train")


@dataclasses.dataclass
class TrainerConfig:
    model: str = "tiny"                       # preset name
    model_overrides: dict = dataclasses.field(default_factory=dict)
    optimizer: dict = dataclasses.field(default_factory=dict)
    data: dict = dataclasses.field(default_factory=dict)
    steps: int = 100
    log_every: int = 10
    # Input staging (storage-initializer analog, train/staging.py): staged
    # into the worker dir before the data pipeline constructs; a staged
    # dataset flips the data kind to "text" automatically.
    dataset_uri: Optional[str] = None
    tokenizer_uri: Optional[str] = None
    train_tokenizer_vocab: Optional[int] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    max_checkpoints: int = 3
    # Survivability (ISSUE 9): a preemption (SIGTERM) force-saves to a fast
    # second tier at the next step boundary, so a graceful preemption loses
    # ZERO completed steps instead of up-to-checkpoint_every of them.
    emergency_checkpointing: bool = True
    emergency_checkpoint_dir: Optional[str] = None   # default: <ckpt>-emergency
    # Step-progress watchdog: a wedged step (hung collective, stuck input
    # pipeline) is detected within max(min_seconds, multiplier x observed
    # step time) and exits retryable — faster AND attributed (stack dump),
    # vs. the heartbeat lease, which a wedged-but-alive worker never misses.
    watchdog_enabled: bool = True
    watchdog_multiplier: float = 20.0
    watchdog_min_seconds: float = 60.0
    watchdog_startup_grace_seconds: float = 600.0
    # Chaos-harness hooks (operator/faults.py drives these through job
    # config): {"wedge_at_step": N, "wedge_once_file": path,
    # "save_fail_steps": [N, ...]}. Inert unless set.
    fault_injection: dict = dataclasses.field(default_factory=dict)
    seed: int = 0
    attn_impl: str = "xla"
    # Profiler window (SURVEY.md §5 tracing): trace steps
    # [profile_start_step, profile_start_step + profile_num_steps) into
    # <workdir>/trace, viewable with tensorboard-plugin-profile. A job that
    # is already running is captured with ``Trainer.request_profile``; both
    # go through obs/profiler.py.
    profile_start_step: Optional[int] = None
    profile_num_steps: int = 3
    # Debug mode (SURVEY.md §5 race-detection analogs): trap NaNs at the op
    # that produced them instead of surfacing as a corrupted loss later.
    debug_nans: bool = False

    @classmethod
    def from_dict(cls, d: dict) -> "TrainerConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


class Trainer:
    def __init__(self, cfg: TrainerConfig, mesh, *,
                 process_id: int = 0, num_processes: int = 1,
                 metrics_path: Optional[str] = None,
                 workdir: Optional[str] = None):
        # The start-up clock (obs/profiler.py): this constructor,
        # ``try_resume`` and the first step are its three phases, always-on
        # sums in ``counters()`` and ``train.start.*`` spans under a
        # capture. It brackets nothing: what lies between the phases (a
        # caller's work between the constructor and ``run``) is not its.
        watch_compiles()
        self._start = profiler.PhaseClock(profiler.TRAIN_START_PHASES)
        with self._start.phase(profiler.TRAIN_START_BUILD):
            self._build(cfg, mesh, process_id, num_processes, metrics_path,
                        workdir)

    def _build(self, cfg: TrainerConfig, mesh, process_id: int,
               num_processes: int, metrics_path: Optional[str],
               workdir: Optional[str]) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.process_id = process_id
        self.num_processes = num_processes
        self.workdir = workdir
        # Pallas kernel call sites of the step as lowered for the chip
        # (runtime/device_report.py); stays empty off the TPU, where the
        # kernels run in the interpreter and leave no custom call.
        self.step_kernels: dict[str, int] = {}

        if cfg.debug_nans:
            jax.config.update("jax_debug_nans", True)
        self.model_cfg: DecoderConfig = preset(cfg.model, **cfg.model_overrides)
        opt_cfg = OptimizerConfig.from_dict(
            {"total_steps": cfg.steps, **cfg.optimizer})
        data_overrides = dict(cfg.data)
        if cfg.dataset_uri:
            from kubeflow_tpu.train.staging import stage_inputs

            staged = stage_inputs(
                workdir or cfg.checkpoint_dir or ".",
                dataset_uri=cfg.dataset_uri,
                tokenizer_uri=cfg.tokenizer_uri,
                train_tokenizer_vocab=cfg.train_tokenizer_vocab)
            data_overrides.setdefault("kind", "text")
            data_overrides["path"] = staged["dataset"]
            if staged["tokenizer"]:
                data_overrides["tokenizer_path"] = staged["tokenizer"]
        data_cfg = DataConfig(**{
            "vocab_size": self.model_cfg.vocab_size,
            "seq_len": self.model_cfg.max_seq_len,
            **data_overrides,
        })
        if data_cfg.vocab_size > self.model_cfg.vocab_size:
            raise ValueError("data vocab exceeds model vocab")
        # Elastic shape adaptation: the global batch must divide over BOTH
        # the host shards (loader) and the mesh's batch axes (dcn×data×
        # fsdp sharding of the device batch). An auto-resize can land on a
        # world shape the configured batch doesn't divide (e.g. 8 over 3
        # workers); round UP to the nearest valid multiple — the torchrun-
        # elastic convention of adapting batch to world size, logged so
        # the change is visible in the worker log.
        import math

        dp = 1
        for ax in ("dcn", "data", "fsdp"):
            dp *= int(dict(mesh.shape).get(ax, 1))
        gran = math.lcm(max(num_processes, 1), max(dp, 1))
        if data_cfg.global_batch % gran:
            new_gb = -(-data_cfg.global_batch // gran) * gran
            logger.info(
                "global_batch %d not divisible by lcm(processes=%d, "
                "batch-shards=%d)=%d; adjusted to %d for this world shape",
                data_cfg.global_batch, num_processes, dp, gran, new_gb)
            data_cfg = dataclasses.replace(data_cfg, global_batch=new_gb)
        self.data_cfg = data_cfg
        self.data = make_data_source(data_cfg, shard=process_id,
                                     num_shards=num_processes)

        self.task = setup_train(
            self.model_cfg, opt_cfg, mesh, seed=cfg.seed,
            attn_impl=cfg.attn_impl)

        self.ckpt: Optional[CheckpointManager] = None
        self.ckpt_emergency: Optional[CheckpointManager] = None
        if cfg.checkpoint_dir:
            self.ckpt = CheckpointManager(
                cfg.checkpoint_dir, cfg.max_checkpoints,
                write_manifests=(process_id == 0))
            if cfg.emergency_checkpointing:
                self.ckpt_emergency = CheckpointManager(
                    cfg.emergency_checkpoint_dir
                    or f"{cfg.checkpoint_dir.rstrip(os.sep)}-emergency",
                    max_to_keep=1, write_manifests=(process_id == 0))

        # Goodput ledger: coordinator-owned, lives in the workdir so it
        # survives gang restarts (every attempt shares the workdir).
        ledger_dir = workdir or (os.path.dirname(metrics_path)
                                 if metrics_path else None)
        self.ledger: Optional[GoodputLedger] = (
            GoodputLedger(ledger_dir)
            if ledger_dir and process_id == 0 else None)
        self.save_failures = 0
        self._preempted = threading.Event()
        # Profiler capture (obs/profiler.py): a pending request
        # (num_steps, trace_dir), and the step at which the capture this
        # trainer started ends.
        self._profile_request: Optional[tuple[int, str]] = None
        self._profile_until: Optional[int] = None
        # Running sum behind ``counters()``.
        self._stage_wait_sum_s = 0.0

        self.emitter = MetricsEmitter(jsonl_path=metrics_path)
        self.throughput = Throughput(
            tokens_per_step=data_cfg.global_batch * data_cfg.seq_len,
            num_chips=mesh.devices.size,
            flops_per_token=self.model_cfg.flops_per_token(),
            # The peak comes from the device the mesh is made of, never
            # from a default row (runtime/topology.py CHIPS).
            peak_tflops=chip_for_device_kind(
                mesh.devices.flat[0].device_kind).bf16_tflops,
        )

    # -- checkpoint/resume -----------------------------------------------------

    def try_resume(self) -> int:
        """Restore the newest VALID checkpoint across tiers; returns the
        resume step.

        The emergency tier is preferred when it holds the newest step (a
        graceful preemption resumes with zero completed steps lost). A
        corrupt or torn step is verified against its manifest, quarantined,
        and the walk falls back to the next older valid step — a bad
        checkpoint can never crash the resume or silently poison the
        numerics, and every skip is surfaced as a ``restore_fallbacks``
        metric."""
        with self._start.phase(profiler.TRAIN_START_RESUME):
            if self.ckpt is None:
                return 0
            tiers: list = []
            if self.ckpt_emergency is not None:
                tiers.append(("emergency", self.ckpt_emergency))
            tiers.append(("interval", self.ckpt))
            resumed = resume_from_tiers(
                tiers, self._abstract_state(),
                quarantine=(self.process_id == 0))
            if resumed is None:
                return 0
            state, _, tier, fallbacks = resumed
            self.task.state = state
            step = int(jax.device_get(state["step"]))
            if fallbacks and self.ledger is not None:
                self.ledger.record_fallback(fallbacks)
            logger.info("resumed from checkpoint at step %d (tier=%s, "
                        "fallbacks=%d)", step, tier, fallbacks)
            return step

    def _abstract_state(self):
        from kubeflow_tpu.train.step import make_state_init

        return CheckpointManager.make_abstract_state(
            make_state_init(self.model_cfg, self.task.optimizer),
            self.task.state_shardings)

    def save(self, step: int, *, force: bool = False,
             manager: Optional[CheckpointManager] = None) -> bool:
        """Save through ``manager`` (default: the interval tier). A rejected
        (False return) or FAILED (raising) save is an alarm — logged and
        counted into ``checkpoint_save_failures`` on metrics.jsonl/job
        status — never a crash: training keeps producing steps while the
        checkpoint store misbehaves, and the alarm is what pages someone."""
        mgr = manager if manager is not None else self.ckpt
        if mgr is None:
            return False
        try:
            if step in set(self.cfg.fault_injection.get("save_fail_steps", ())):
                raise OSError(f"injected checkpoint save failure at step {step}")
            accepted = mgr.save(step, self.task.state, force=force)
            if not accepted:
                logger.error("checkpoint save at step %d rejected by the "
                             "manager", step)
        except Exception:
            logger.exception("checkpoint save at step %d failed", step)
            accepted = False
        if not accepted:
            self.save_failures += 1
            if self.ledger is not None:
                self.ledger.record_save_failure()
        return accepted

    # -- the loop --------------------------------------------------------------

    def make_global_batch(self, local_batch: np.ndarray):
        return jax.make_array_from_process_local_data(
            self.task.batch_sharding, local_batch)

    def counters(self) -> dict[str, float]:
        """One total snapshot of the loop's running sums: every key exists
        from construction on and only ever grows. ``stage_wait_sum_s`` is
        the seconds the loop waited for its next batch;
        ``start_<phase>_sum_s`` the seconds of the three start phases
        (``train.start.*``: constants once the first step has synced);
        ``compile_*`` what JAX compiled, loaded from its cache, traced and
        lowered in this PROCESS so far
        (runtime/bootstrap.py::watch_compiles)."""
        return {"stage_wait_sum_s": self._stage_wait_sum_s,
                **{f"start_{phase}_sum_s": seconds
                   for phase, seconds in self.start_phase_seconds().items()},
                **compile_counters()}

    def start_phase_seconds(self) -> dict[str, float]:
        """The seconds of ``build``, ``resume`` and ``first_step`` (the
        run's first step alone, from its lowering to its outputs ready:
        compile or load, and first execution; ``_first_step``)."""
        return {name.rpartition(".")[2]: self._start.total(name)
                for name in profiler.TRAIN_START_PHASES}

    def _first_step(self, step: int, batch):
        """The run's first step, waited for: its lowering, its compile or
        load from the cache, its dispatch and (once, here alone) a wait
        for its outputs, so that ``train.start.first_step`` is exactly this
        one step whatever ``log_every`` is. Returns the step's metrics."""
        with self._start.phase(profiler.TRAIN_START_FIRST_STEP,
                               profiler.active() and {"step": step}):
            if jax.default_backend() == "tpu":
                self.step_kernels = lowered_kernel_calls(
                    self.task.step_fn, self.task.state, batch)
            with hot_span(profiler.TRAIN_DISPATCH, step=step):
                self.task.state, metrics = self.task.step_fn(self.task.state, batch)
            with hot_span(profiler.TRAIN_SYNC, step=step):
                jax.block_until_ready(metrics)
        # Training shapes are fixed: everything compiles on the first
        # executed step, so under KFTPU_SANITIZE=recompile any later compile
        # is a dispatch-signature defect — the runtime half of the F6xx
        # rules. No-op when the sanitizer is off.
        mark_compile_warm()
        return metrics

    def request_profile(self, num_steps: Optional[int] = None,
                        trace_dir: Optional[str] = None) -> None:
        """Begin a profiler capture at the next step boundary of the
        running job (any thread may ask); it ends ``num_steps`` steps
        later. ``cfg.profile_start_step`` is the same request, made before
        the job started."""
        self._profile_request = (
            int(num_steps or self.cfg.profile_num_steps),
            trace_dir or self._trace_dir())

    def _profile_boundary(self, step: int) -> None:
        """At a step boundary: end the capture this trainer started when
        its steps are up, start a requested one."""
        if self._profile_until is not None:
            if step < self._profile_until:
                return
            self._profile_until = None
            profiler.stop()
        if step == self.cfg.profile_start_step:
            self.request_profile()
        req, self._profile_request = self._profile_request, None
        if req is not None and self.process_id == 0 \
                and not profiler.active():
            profiler.start(req[1])
            self._profile_until = step + req[0]

    def run(self, *, on_step=None) -> dict:
        start = self.try_resume()
        if self.ledger is not None:
            lost = self.ledger.record_resume(start)
            if lost:
                logger.warning(
                    "restart lost %d completed step(s): last recorded "
                    "progress outran the resumed checkpoint", lost)
        last_metrics: dict = {}
        last_tick_step = start
        tracer = get_tracer()
        window_start = time.time()
        watchdog: Optional[StepWatchdog] = None
        if self.cfg.watchdog_enabled:
            watchdog = StepWatchdog(
                multiplier=self.cfg.watchdog_multiplier,
                min_seconds=self.cfg.watchdog_min_seconds,
                startup_grace_seconds=self.cfg.watchdog_startup_grace_seconds)
            watchdog.start()
        prev_sigterm = self._install_preemption_handler()
        # Double-buffered host→device staging (train/staging.py): batch
        # N+1 is built and uploaded on a background thread while step N
        # runs, so the device never idles on the host's input work.
        # batch_at is a pure function of the step (the fast-forward
        # contract), which keeps prefetching restart-transparent.
        from kubeflow_tpu.train.staging import DeviceBatchStager

        stager = DeviceBatchStager(
            lambda s: self.make_global_batch(self.data.batch_at(s)),
            start=start, name="train-batch-stager")
        # try/finally so ANY exit from the loop — exception mid-window,
        # preemption SystemExit — still stops the profiler capture it began,
        # drains the async checkpoint managers (an in-flight save must not
        # be abandoned torn), and closes the metrics emitter.
        try:
            for step in range(start, self.cfg.steps):
                self._profile_boundary(step)
                with profiler.hot_step(profiler.TRAIN_STEP, step):
                    t_wait = time.monotonic()
                    with hot_span(profiler.TRAIN_STAGE_WAIT, step=step):
                        batch = stager.get(step)
                    self._stage_wait_sum_s += time.monotonic() - t_wait
                    if step == start:
                        metrics = self._first_step(step, batch)
                    else:
                        with hot_span(profiler.TRAIN_DISPATCH, step=step):
                            self.task.state, metrics = self.task.step_fn(self.task.state, batch)
                    if watchdog is not None:
                        watchdog.step_completed(step + 1)
                    if self._preempted.is_set():
                        self._emergency_exit(step + 1)      # raises SystemExit
                    if (step + 1) % self.cfg.log_every == 0 or step + 1 == self.cfg.steps:
                        with hot_span(profiler.TRAIN_SYNC, step=step):
                            metrics = {k: float(jax.device_get(v)) for k, v in metrics.items()}
                        with hot_span(profiler.TRAIN_LOG, step=step):
                            metrics.update(self.throughput.tick(step + 1 - last_tick_step))
                            # COMMITTED checkpoints only (async saves that a teardown
                            # would abort must not arm the elastic autoscaler): surfaced
                            # through metrics.jsonl onto job status.
                            if self.ckpt is not None:
                                committed = self.ckpt.latest_committed_step()
                                if committed is not None:
                                    metrics["last_checkpoint_step"] = committed
                            # Goodput ledger (train/survival.py): restart/fallback/
                            # emergency accounting riding every window onto job
                            # status; the ledger's cumulative counters supersede the
                            # attempt-local save_failures when present.
                            metrics["checkpoint_save_failures"] = self.save_failures
                            if self.ledger is not None:
                                self.ledger.record_progress(step + 1)
                                metrics.update(self.ledger.metrics(
                                    step + 1, self.throughput.ema_step_time_s))
                            # One completed span per logged window (obs/trace.py): the
                            # train loop's slice of the platform trace surface. Spans
                            # are retrospective (explicit start) so the hot loop pays
                            # nothing between log points; ``profiling=True`` marks
                            # windows that overlapped a profiler capture, tying the
                            # span to the on-device timeline it summarizes.
                            sp = tracer.start_span(
                                "train.window", start=window_start,
                                steps=f"{last_tick_step}-{step + 1}")
                            for k in ("loss", "step_time_ms", "tokens_per_sec", "mfu"):
                                if k in metrics:
                                    sp.set_attrs(**{k: round(float(metrics[k]), 6)})
                            if profiler.active():
                                sp.set_attrs(profiling=True)
                            sp.end()
                            window_start = time.time()
                            last_tick_step = step + 1
                            last_metrics = metrics
                            if self.process_id == 0:
                                self.emitter.emit(step + 1, metrics)
                    if self.cfg.checkpoint_every and (step + 1) % self.cfg.checkpoint_every == 0:
                        with hot_span(profiler.TRAIN_CHECKPOINT, step=step):
                            self.save(step + 1)
                    self._maybe_injected_wedge(step + 1)
                if on_step is not None:
                    on_step(step + 1, last_metrics)
            if self.ckpt is not None and self.ckpt.latest_step() != self.cfg.steps:
                self.save(self.cfg.steps, force=True)
            if self.workdir and self.process_id == 0:
                self._write_device_report()
        finally:
            stager.close()
            if prev_sigterm is not None:
                signal.signal(signal.SIGTERM, prev_sigterm)
            if watchdog is not None:
                watchdog.stop()
            if self._profile_until is not None:
                self._profile_until = None
                try:
                    profiler.stop()
                except Exception:
                    logger.exception("stopping profiler trace failed")
            for mgr in (self.ckpt, self.ckpt_emergency):
                if mgr is None:
                    continue
                try:
                    # blocking-ok: drain the async save at run end — durability outranks prompt exit
                    mgr.wait()
                    mgr.close()
                except Exception:
                    logger.exception("checkpoint manager close failed")
            self.emitter.close()
        rep = recompile_report()
        if rep.get("steady_count"):
            # At 6k-chip scale each of these cost minutes of cluster time
            # per occurrence; name the dispatch sites so the fix is a
            # grep, not a bisect.
            logger.error(
                "recompile sanitizer: %d steady-state recompile(s) after "
                "the first step: %s", rep["steady_count"],
                "; ".join(f"{e['fn']} x{e['count']} at {e['site']}"
                          for e in rep["steady"]))
        return last_metrics

    def _write_device_report(self) -> None:
        """What this run ran on, for a parent that must not touch the chip:
        device, memory, compile cache, the step's kernels, and where the
        largest parameter's shards sit (sharded training that put
        everything on the first device shows here)."""
        big = max(jax.tree.leaves(self.task.state["params"]),
                  key=lambda a: a.size)
        write_device_report(
            self.workdir,
            programs={"train_step": self.step_kernels},
            start={"phases": self.start_phase_seconds()},
            mesh={a: int(n) for a, n in self.mesh.shape.items() if n > 1},
            largest_param={
                "shape": list(big.shape),
                "shard_shape": list(big.addressable_shards[0].data.shape),
                "devices": sorted(s.device.id
                                  for s in big.addressable_shards),
            })

    # -- survivability (preemption / wedge / chaos hooks) ----------------------

    def _install_preemption_handler(self):
        """SIGTERM = preemption notice, not an order to die mid-step: set a
        flag, emergency-save at the NEXT step boundary, then exit retryable.
        (worker_main's default handler exits immediately, losing everything
        since the last interval save.) Main-thread only — the signal module
        contract; in-process harnesses (tests driving Trainer directly from
        worker threads) simply keep the host's handler. Returns the previous
        handler for the finally-restore, or None when not installed."""
        if threading.current_thread() is not threading.main_thread():
            return None
        try:
            return signal.signal(signal.SIGTERM,
                                 lambda *_: self._preempted.set())
        except (ValueError, OSError) as exc:
            logger.warning("preemption handler not installed: %s", exc)
            return None

    def _emergency_exit(self, step: int) -> None:
        """A preemption landed: force-save the just-completed step to the
        emergency tier, make it durable, record the ledger, and exit with
        the retryable code so ``JAXJobController._handle_failures``
        gang-restarts and resume finds this exact step — a graceful
        preemption loses ZERO completed steps."""
        mgr = self.ckpt_emergency or self.ckpt
        saved = False
        if mgr is not None:
            saved = self.save(step, force=True, manager=mgr)
            try:
                mgr.wait()          # blocking-ok: durable before we die, or it never was
            except Exception:
                logger.exception("emergency checkpoint wait failed")
                saved = False
        if self.ledger is not None:
            self.ledger.record_progress(step)
            if saved:
                self.ledger.record_emergency_save(step)
        logger.warning(
            "preemption: emergency checkpoint at step %d (%s); exiting "
            "retryable", step, "saved" if saved else "SAVE FAILED")
        raise SystemExit(EXIT_PREEMPTED)

    def _maybe_injected_wedge(self, step: int) -> None:
        """Chaos hook: hang the loop at a configured step (a hung collective,
        as far as any failure detector can tell) — the step-progress
        watchdog is the component under test. ``wedge_once_file`` makes the
        wedge fire on the first attempt only, so the gang restart that
        follows can prove the resume."""
        fi = self.cfg.fault_injection
        if fi.get("wedge_at_step") != step:
            return
        once = fi.get("wedge_once_file")
        if once:
            if os.path.exists(once):
                return
            with open(once, "w") as f:
                f.write(str(step))
        logger.warning("fault injection: wedging at step %d", step)
        while True:
            time.sleep(0.25)

    def _trace_dir(self) -> str:
        import os

        base = (os.path.dirname(self.emitter.jsonl_path)
                if getattr(self.emitter, "jsonl_path", None) else ".")
        return os.path.join(base, "trace")
