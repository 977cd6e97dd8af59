"""JAXJob controller: reconciles JAXJobs into gang-scheduled Worker objects.

The TPU-native unification of the reference's per-framework job controllers
((U) training-operator pkg/controller.v1/{pytorch,tensorflow,mpi}/*_controller.go
over the shared engine pkg/controller.v1/common/job.go — SURVEY.md §2.2#15-16,
§3.1). What carries over: level-triggered reconcile, per-replica child
creation, status aggregation into conditions, RestartPolicy/backoffLimit/
activeDeadline/ttl/suspend semantics, gang scheduling.

What is deliberately different (TPU-native):

- **Whole-gang restart.** The reference restarts individual pods; an SPMD
  gang cannot absorb that — a dead process wedges every collective and a new
  process cannot rejoin a live `jax.distributed` cluster. Any worker failure
  therefore tears down the whole gang and relaunches it (from the latest
  checkpoint — resume is first-class in RunPolicy, not user code).
- **Placement before pods.** The reference creates pods and lets Volcano hold
  them; here the gang allocator answers *before* any Worker object exists, so
  a queued job is visibly Pending with zero side effects.
- **Coordinator assignment.** Rendezvous env (coordinator address = worker-0,
  process ids) replaces MASTER_ADDR/TF_CONFIG/hostfile injection
  ((U) pytorch/envvar.go SetClusterSpec).
- **Failure detection is leased.** Worker heartbeat staleness (marked by the
  worker runtime) is a retryable failure like a preemption, not a job error.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from kubeflow_tpu.core.events import EventRecorder, default_recorder
from kubeflow_tpu.core.jobs import (
    WORKER, CleanPodPolicy, JAXJob, JobConditionType, ReplicaStatus,
    RestartPolicy, Worker, WorkerPhase, WorkerSpec, WorkerStatus, worker_name,
)
from kubeflow_tpu.core.object import ObjectMeta, utcnow
from kubeflow_tpu.core.store import (
    AlreadyExistsError, ConflictError, NotFoundError, ObjectStore, WatchEvent,
)
from kubeflow_tpu.operator.controller import ReconcileResult
from kubeflow_tpu.runtime.allocator import (
    GangAllocator, GangRequest, InsufficientCapacityError,
)
from kubeflow_tpu.runtime.bootstrap import EXIT_CONFIG_ERROR, free_port

# Labels on Worker objects (≈ training.kubeflow.org/replica-{type,index}).
LABEL_JOB = "training.tpu.kubeflow.dev/job-name"
LABEL_REPLICA_TYPE = "training.tpu.kubeflow.dev/replica-type"
LABEL_REPLICA_INDEX = "training.tpu.kubeflow.dev/replica-index"

_PLACEMENT_POLL = 0.5   # seconds between queue-position re-checks
_FINISHED_PHASES = (WorkerPhase.SUCCEEDED, WorkerPhase.FAILED)


def _is_retryable_exit(code: Optional[int]) -> bool:
    """Exit-code contract: >=128 (signals/preemption/rendezvous) retryable.

    ``None`` (no exit code: heartbeat-stale kill, lost process) is retryable —
    it is the shape of an infrastructure failure, not a program bug."""
    return code is None or code >= 128


class JAXJobController:
    """Reconciler for JAXJob (+ owned Worker) objects."""

    kinds = [JAXJob.KIND, Worker.KIND]

    def __init__(self, store: ObjectStore, allocator: GangAllocator, *,
                 base_dir: str, recorder: Optional[EventRecorder] = None,
                 metrics_sync_interval: Optional[float] = 1.0):
        self.store = store
        self.allocator = allocator
        self.base_dir = base_dir
        self.recorder = recorder or default_recorder
        # Periodic resync while workers run: lifts fresh data-plane metrics
        # onto job status between watch events (None = event-driven only).
        self.metrics_sync_interval = metrics_sync_interval

    # -- event routing ---------------------------------------------------------

    def key_for(self, ev: WatchEvent) -> Optional[str]:
        obj = ev.object
        if obj.kind == JAXJob.KIND:
            return obj.metadata.key
        if obj.kind == Worker.KIND:
            return obj.spec.job  # route child events to the owning job
        return None

    # -- reconcile -------------------------------------------------------------

    def reconcile(self, key: str) -> Optional[ReconcileResult]:
        namespace, name = key.split("/", 1)
        job = self.store.try_get(JAXJob, name, namespace)
        if job is None:
            # Job deleted: tear down whatever it left behind.
            self.allocator.release(key)
            for w in self._workers(key):
                self._delete_worker(w)
            return None

        if job.status.phase in ("Succeeded", "Failed"):
            return self._reconcile_finished(job)

        if job.spec.run_policy.suspend:
            return self._reconcile_suspended(job)

        # Admission bookkeeping.
        if not job.status.has_condition(JobConditionType.CREATED.value):
            job.status.set_condition(JobConditionType.CREATED.value,
                                     reason="JobCreated")
            self.recorder.normal(job, "JobCreated", "job admitted")
        if job.status.start_time is None:
            job.status.start_time = utcnow()
        # Coming back from suspension: clear the marker so phase recomputes.
        if job.status.has_condition(JobConditionType.SUSPENDED.value):
            job.status.set_condition(JobConditionType.SUSPENDED.value,
                                     status=False, reason="Resumed")

        # Active deadline (≈ RunPolicy.activeDeadlineSeconds).
        deadline = job.spec.run_policy.active_deadline_seconds
        if deadline is not None and job.status.start_time is not None:
            elapsed = (utcnow() - job.status.start_time).total_seconds()
            if elapsed >= deadline:
                return self._fail(job, "DeadlineExceeded",
                                  f"active deadline {deadline}s exceeded")
            result_deadline = deadline - elapsed
        else:
            result_deadline = None

        # Elastic autoscaler (the reference's ElasticPolicy→HPA metric half,
        # (U) training-operator pkg/controller.v1/pytorch/hpa.go): may write
        # a new worker count into the spec, which the resize check below
        # then acts on in this same pass.
        if (job.spec.elastic_policy is not None
                and job.spec.elastic_policy.auto_scaling):
            self._maybe_autoscale(job)

        # Elastic / spec resize: desired shape changed under a live gang
        # (worker count, chips per worker, or mesh axes) → tear down and
        # re-gang at the new shape (resharded resume from checkpoint).
        spec = job.spec.worker
        desired_parallelism = (job.spec.parallelism.axis_sizes()
                               if job.spec.parallelism.total > 1 else {})
        alloc = self.allocator.allocation(key)
        if alloc is not None and (
                alloc.request.num_workers != spec.replicas
                or alloc.request.chips_per_worker != spec.resources.tpu_chips
                or any(w.spec.parallelism != desired_parallelism
                       for w in self._workers(key))):
            return self._resize(job, alloc)

        # Gang placement (all-or-nothing; queue = visible Pending).
        if alloc is None:
            try:
                alloc = self.allocator.submit(GangRequest(
                    name=key,
                    num_workers=spec.replicas,
                    chips_per_worker=spec.resources.tpu_chips,
                    priority=job.spec.run_policy.scheduling_policy.priority,
                    queue=job.spec.run_policy.scheduling_policy.queue,
                ))
            except InsufficientCapacityError as exc:
                return self._fail(job, "InsufficientCapacity", str(exc))
            if alloc is None:
                # Timeout counts from entering the queue (this wait), not job
                # admission — a resumed/resized job waits afresh.
                if job.status.pending_since is None:
                    job.status.pending_since = utcnow()
                timeout = job.spec.run_policy.scheduling_policy.timeout_seconds
                if timeout is not None:
                    waited = (utcnow() - job.status.pending_since).total_seconds()
                    if waited >= timeout:
                        self.allocator.release(key)
                        return self._fail(job, "PlacementTimeout",
                                          f"no placement after {waited:.0f}s")
                self.recorder.normal(job, "Pending", "waiting for gang placement")
                self._update_status(job)
                return ReconcileResult(requeue_after=_PLACEMENT_POLL)
            self.recorder.normal(
                job, "GangScheduled",
                f"placed on slice {alloc.slice_name}: {alloc.request.total_chips} chips")
        job.status.pending_since = None

        if job.status.gang_name is None:
            job.status.gang_name = key
        if job.status.coordinator_address is None:
            job.status.coordinator_address = f"127.0.0.1:{free_port()}"

        # Materialize Worker objects for the current attempt.
        workers = self._workers(key)
        current = [w for w in workers if w.spec.attempt == job.status.restart_count]
        stale = [w for w in workers if w.spec.attempt != job.status.restart_count]
        for w in stale:  # leftovers of a torn-down attempt still draining
            self._delete_worker(w)
        have = {w.spec.replica_index for w in current}
        for i in range(spec.replicas):
            if i not in have:
                current.append(self._create_worker(job, alloc, i))

        # Aggregate → ReplicaStatus + conditions (≈ common/status.go).
        rs = ReplicaStatus()
        for w in current:
            if w.status.phase == WorkerPhase.SUCCEEDED:
                rs.succeeded += 1
            elif w.status.phase == WorkerPhase.FAILED:
                rs.failed += 1
            else:
                rs.active += 1
        job.status.replica_statuses = {WORKER: rs}

        self._sync_metrics(job, current)

        failed = [w for w in current if w.status.phase == WorkerPhase.FAILED]
        if failed:
            return self._handle_failures(job, current, failed)

        if rs.succeeded == spec.replicas:
            return self._succeed(job)

        if rs.active == spec.replicas and all(
                w.status.phase == WorkerPhase.RUNNING for w in current):
            if not job.status.has_condition(JobConditionType.RUNNING.value):
                self.recorder.normal(job, "JobRunning", "all workers running")
            job.status.set_condition(JobConditionType.RUNNING.value,
                                     reason="AllWorkersRunning")
            job.status.set_condition(JobConditionType.RESTARTING.value,
                                     status=False, reason="Recovered")

        self._update_status(job)
        # Requeue for whichever comes first: deadline expiry or the periodic
        # metrics resync (worker events also wake us immediately).
        delays = [d for d in (result_deadline, self.metrics_sync_interval)
                  if d is not None]
        return ReconcileResult(requeue_after=min(delays) if delays else None)

    # -- terminal / suspended states -------------------------------------------

    def _reconcile_finished(self, job: JAXJob) -> Optional[ReconcileResult]:
        key = job.metadata.key
        self.allocator.release(key)
        policy = job.spec.run_policy.clean_pod_policy
        for w in self._workers(key):
            if policy == CleanPodPolicy.ALL:
                self._delete_worker(w)
            elif policy == CleanPodPolicy.RUNNING and w.status.phase not in _FINISHED_PHASES:
                self._delete_worker(w)

        ttl = job.spec.run_policy.ttl_seconds_after_finished
        if ttl is not None:
            done_at = job.status.completion_time or utcnow()
            remaining = ttl - (utcnow() - done_at).total_seconds()
            if remaining <= 0:
                # Cascade: children first, then the job itself.
                for w in self._workers(key):
                    self._delete_worker(w)
                try:
                    self.store.delete(JAXJob, job.metadata.name, job.metadata.namespace)
                except NotFoundError:
                    pass
                return None
            return ReconcileResult(requeue_after=remaining)
        return None

    def _reconcile_suspended(self, job: JAXJob) -> Optional[ReconcileResult]:
        key = job.metadata.key
        for w in self._workers(key):
            self._delete_worker(w)
        self.allocator.release(key)
        if not job.status.has_condition(JobConditionType.SUSPENDED.value):
            self.recorder.normal(job, "JobSuspended",
                                 "workers stopped, gang released")
        job.status.pending_since = None   # a resumed job waits afresh
        job.status.set_condition(JobConditionType.SUSPENDED.value,
                                 reason="SuspendRequested")
        job.status.set_condition(JobConditionType.RUNNING.value,
                                 status=False, reason="Suspended")
        job.status.replica_statuses = {WORKER: ReplicaStatus()}
        self._update_status(job)
        return None

    # -- failure / restart machinery -------------------------------------------

    def _handle_failures(self, job: JAXJob, workers: list[Worker],
                         failed: list[Worker]) -> Optional[ReconcileResult]:
        spec = job.spec.worker
        policy = spec.restart_policy
        reached_running = job.status.has_condition(JobConditionType.RUNNING.value)

        def describe(w: Worker) -> str:
            return (f"{w.metadata.name}: exit={w.status.exit_code} "
                    f"{w.status.message}".strip())

        # Root-cause attribution: when one worker dies, its gang peers die
        # too (their collectives lose a participant) with exit codes that
        # say nothing about the real cause. The EARLIEST failure is the
        # root cause; only its exit code decides retryability.
        root = min(failed, key=lambda w: (w.status.finish_time is None,
                                          w.status.finish_time))
        retryable: bool
        if policy == RestartPolicy.NEVER:
            retryable = False
        elif (root.status.exit_code == EXIT_CONFIG_ERROR
              and policy != RestartPolicy.ALWAYS):
            # A config error (bad entrypoint, a tpu worker that found no
            # TPU, a second worker on a host whose chips are held) is
            # deterministic: a restart cannot cure it, so the job fails at
            # once, with the worker's message.
            retryable = False
        elif policy in (RestartPolicy.ALWAYS, RestartPolicy.ON_FAILURE):
            retryable = True
        else:  # EXIT_CODE
            retryable = _is_retryable_exit(root.status.exit_code)
            # A gang that died before ever running is a rendezvous/placement
            # failure — infrastructure, not the program (bootstrap.py notes
            # the coordination client can abort without a clean exit code).
            if not retryable and not reached_running:
                retryable = True

        if not retryable:
            return self._fail(job, "WorkerFailed",
                              "; ".join(describe(w) for w in failed))

        max_restarts = job.spec.run_policy.backoff_limit
        if job.spec.elastic_policy is not None:
            max_restarts = max(max_restarts, job.spec.elastic_policy.max_restarts)
        if job.status.restart_count >= max_restarts:
            return self._fail(
                job, "BackoffLimitExceeded",
                f"restarted {job.status.restart_count}x; last: "
                + "; ".join(describe(w) for w in failed))

        # Whole-gang restart: every worker goes; chips stay allocated.
        self.recorder.warning(
            job, "GangRestart",
            f"attempt {job.status.restart_count + 1}: "
            + "; ".join(describe(w) for w in failed))
        for w in workers:
            self._delete_worker(w)
        job.status.restart_count += 1
        job.status.coordinator_address = f"127.0.0.1:{free_port()}"
        job.status.set_condition(JobConditionType.RESTARTING.value,
                                 reason="GangRestart")
        job.status.set_condition(JobConditionType.RUNNING.value,
                                 status=False, reason="Restarting")
        self._update_status(job)
        # Recreate on the next pass so worker deletion events settle first.
        return ReconcileResult(requeue_after=0.05)

    @staticmethod
    def _elastic_parallelism(job: JAXJob, desired: int, chips: int):
        """ParallelismSpec for ``desired`` workers that PRESERVES the job's
        non-data axes (dcn/pipeline/expert/seq/model) and scales only the
        data×fsdp product — an fsdp×tp job must stay fsdp×tp across an
        auto-resize ((U) hpa.go scales worker counts regardless of the
        inner strategy; forcing pure DP would reject any model that does
        not fit one chip, the actual elastic-training regime).

        Returns None when ``desired`` cannot host the preserved axes
        (their product doesn't divide desired*chips) — the caller must
        pick a different count, not silently change the strategy."""
        from kubeflow_tpu.core.jobs import ParallelismSpec

        old = job.spec.parallelism
        total = desired * chips
        preserved = (old.dcn * old.pipeline * old.expert * old.seq
                     * old.model)
        if total % preserved:
            return None
        product = total // preserved          # new data*fsdp pool
        if product < 1:
            return None
        if old.fsdp > 1 and product % old.fsdp == 0:
            fsdp, data = old.fsdp, product // old.fsdp
        elif old.fsdp > 1:
            # fsdp no longer divides the pool: absorb it all into fsdp
            # (memory per chip only improves; resharded restore handles
            # the layout change) rather than silently unsharding params.
            fsdp, data = product, 1
        else:
            fsdp, data = 1, product
        return ParallelismSpec(dcn=old.dcn, pipeline=old.pipeline,
                               data=data, fsdp=fsdp, expert=old.expert,
                               seq=old.seq, model=old.model)

    def _valid_count_below(self, job: JAXJob, cur: int, chips: int,
                           floor: int) -> Optional[int]:
        """Largest worker count in [floor, cur) whose shape can host the
        preserved parallelism axes."""
        for d in range(cur - 1, floor - 1, -1):
            if self._elastic_parallelism(job, d, chips) is not None:
                return d
        return None

    def _shrink_helps_pending(self, job: JAXJob, alloc, cur: int,
                              chips: int, floor: int) -> bool:
        """Could shrinking EVER make some pending gang placeable?
        Shrinking when the waiter needs a different slice — or more chips
        than this job could yield even at its smallest valid shape — burns
        the shared auto-resize budget without unblocking anyone. Judged
        against the maximum eventual yield (not one step): shrinks go one
        valid count per cooldown, and the gate must not block progressive
        yielding toward a large waiter."""
        min_valid = next(
            (d for d in range(floor, cur)
             if self._elastic_parallelism(job, d, chips) is not None), None)
        if min_valid is None:
            return False
        max_freeable = (cur - min_valid) * chips
        free = self.allocator.free_chips(alloc.slice_name)
        for p in self.allocator.pending():
            if p.slice_name not in (None, alloc.slice_name):
                continue
            if p.total_chips <= free + max_freeable:
                return True
        return False

    def _maybe_autoscale(self, job: JAXJob) -> None:
        """Decide a new worker count from cluster + job metrics and durably
        write it into the spec (the scale-subresource analog). The existing
        resize machinery — re-gang, resharded restore — does the rest.

        Ordering of signals: shrink signals outrank growth (yielding chips
        under pressure beats widening), and every move respects the
        cooldown and the ``max_restarts`` auto-resize budget."""
        pol = job.spec.elastic_policy
        alloc = self.allocator.allocation(job.metadata.key)
        if alloc is None:
            return                       # not placed: nothing to scale yet
        if not job.status.has_condition(JobConditionType.RUNNING.value):
            return                       # mid-restart/startup: let it settle
        ck = job.spec.run_policy.checkpoint
        if ck.enabled and job.status.metrics.last_checkpoint_step is None:
            # A resize before the first checkpoint lands would trade live
            # progress for a from-scratch restart — wait for a resume point.
            return
        if job.status.elastic_resizes >= pol.max_restarts:
            return                       # budget spent: hold shape forever
        last = job.status.last_scale_time
        if isinstance(last, str):
            import datetime

            last = datetime.datetime.fromisoformat(last)
        if last is not None and (
                (utcnow() - last).total_seconds() < pol.scale_cooldown_seconds):
            return
        cur = job.spec.worker.replicas
        chips = job.spec.worker.resources.tpu_chips
        down = self._valid_count_below(job, cur, chips, pol.min_replicas)
        desired, why = cur, ""
        if (pol.yield_to_pending and down is not None
                and self.allocator.pending()
                and self._shrink_helps_pending(job, alloc, cur, chips,
                                               pol.min_replicas)):
            desired, why = down, "pending gangs waiting for chips"
        tput = job.status.metrics.tokens_per_sec_per_chip
        if (desired == cur and pol.min_tokens_per_sec_per_chip is not None
                and tput is not None and down is not None
                and tput < pol.min_tokens_per_sec_per_chip):
            desired, why = down, (
                f"{tput:.0f} tok/s/chip below floor "
                f"{pol.min_tokens_per_sec_per_chip:.0f}")
        if (desired == cur and pol.scale_on_headroom
                and cur < pol.max_replicas
                and not self.allocator.pending()):
            # Growth yields to ANY queued gang (not only under
            # yield_to_pending): growing while something waits would either
            # starve it or — with yield_to_pending set — flap grow/shrink
            # every cooldown until the resize budget is gone.
            free = self.allocator.free_chips(alloc.slice_name)
            # Grow only as far as re-placement is guaranteed to succeed:
            # after release the gang needs desired*chips on this slice, and
            # free + cur*chips is exactly what will be available. Step down
            # to the largest count that can host the preserved axes.
            for grow in range(min(pol.max_replicas, cur + free // chips),
                              cur, -1):
                if self._elastic_parallelism(job, grow, chips) is not None:
                    desired, why = grow, (
                        f"{free} free chips on slice {alloc.slice_name}")
                    break
        if desired == cur:
            return
        new_par = self._elastic_parallelism(job, desired, chips)
        if new_par is None:      # unreachable: counts above were validated
            return
        job.spec.worker.replicas = desired
        # Scale the data/fsdp product; every other axis (tp/ep/sp/pp/dcn)
        # keeps its degree — a multi-worker gang also cannot run on the
        # default total==1 parallelism (each process would build a 1-device
        # mesh under a 2-device jax.distributed world), so the spec is
        # always rewritten to span desired*chips.
        job.spec.parallelism = new_par
        job.status.elastic_resizes += 1
        job.status.last_scale_time = utcnow()
        try:
            job.metadata = self.store.update(job).metadata
        except (ConflictError, NotFoundError):
            # Lost a spec race: drop the local mutation too — acting on an
            # unpersisted spec would resize now and resize BACK next pass.
            fresh = self.store.try_get(JAXJob, job.metadata.name,
                                       job.metadata.namespace)
            if fresh is not None:
                job.spec = fresh.spec
                job.status = fresh.status
                job.metadata = fresh.metadata
            return
        self.recorder.normal(
            job, "ElasticScaleUp" if desired > cur else "ElasticScaleDown",
            f"{cur} -> {desired} workers: {why} "
            f"(auto-resize {job.status.elastic_resizes}/{pol.max_restarts})")

    def _resize(self, job: JAXJob, alloc) -> Optional[ReconcileResult]:
        key = job.metadata.key
        new = job.spec.worker.replicas
        pure_shrink = (new < alloc.request.num_workers
                       and alloc.request.chips_per_worker
                       == job.spec.worker.resources.tpu_chips)
        self.recorder.normal(
            job, "Resizing",
            f"{alloc.request.num_workers} -> {new} workers; "
            + ("shrinking in place" if pure_shrink else "re-ganging"))
        for w in self._workers(key):
            self._delete_worker(w)
        if pure_shrink:
            # Atomic scale-down: trailing workers' chips are freed and
            # waiters scheduled under the allocator lock — no release→
            # re-submit window in which a pending gang could take more
            # than the freed chips and leave this job Pending. The gang
            # keeps its identity; processes restart at the new world size.
            self.allocator.shrink(key, new)
            job.status.coordinator_address = None   # fresh rendezvous
        else:
            self.allocator.release(key)
            job.status.gang_name = None
            job.status.coordinator_address = None
        # Throughput readings from the OLD shape must not drive the next
        # autoscale decision: the re-ganged job takes minutes to produce a
        # fresh line, and a stale below-floor value would shrink again every
        # cooldown down to min_replicas.
        job.status.metrics.tokens_per_sec_per_chip = None
        job.status.metrics.step_time_ms = None
        job.status.metrics.mfu = None
        job.status.set_condition(JobConditionType.RESTARTING.value,
                                 reason="Resized")
        job.status.set_condition(JobConditionType.RUNNING.value,
                                 status=False, reason="Resizing")
        self._update_status(job)
        return ReconcileResult(requeue_after=0.05)

    def _succeed(self, job: JAXJob) -> Optional[ReconcileResult]:
        job.status.set_condition(JobConditionType.SUCCEEDED.value,
                                 reason="AllWorkersSucceeded")
        job.status.set_condition(JobConditionType.RUNNING.value,
                                 status=False, reason="Finished")
        job.status.completion_time = utcnow()
        self.recorder.normal(job, "JobSucceeded", "all workers succeeded")
        self._update_status(job)
        return self._reconcile_finished(job)

    def _fail(self, job: JAXJob, reason: str, message: str) -> Optional[ReconcileResult]:
        job.status.set_condition(JobConditionType.FAILED.value,
                                 reason=reason, message=message)
        job.status.set_condition(JobConditionType.RUNNING.value,
                                 status=False, reason="Failed")
        job.status.completion_time = utcnow()
        self.recorder.warning(job, reason, message)
        self._update_status(job)
        return self._reconcile_finished(job)

    # -- children --------------------------------------------------------------

    def _workers(self, job_key: str) -> list[Worker]:
        namespace, name = job_key.split("/", 1)
        return self.store.list(Worker, namespace=namespace,
                               label_selector={LABEL_JOB: name})

    def job_dir(self, job: JAXJob) -> str:
        return os.path.join(self.base_dir, job.metadata.namespace,
                            job.metadata.name)

    def _create_worker(self, job: JAXJob, alloc, index: int) -> Worker:
        spec = job.spec.worker
        name = worker_name(job.metadata.name, WORKER, index)
        jdir = self.job_dir(job)
        template = spec.template.model_copy(deep=True)
        if template.working_dir is None:
            template.working_dir = os.path.join(jdir, f"worker-{index}")
        # First-class checkpointing: default the trainer's checkpoint dir into
        # the job dir so every attempt resumes from the same place (the
        # reference leaves this to user pods — SURVEY.md §5 checkpoint/resume).
        ckpt = job.spec.run_policy.checkpoint
        if ckpt.enabled and "checkpoint_dir" not in template.config:
            template.config["checkpoint_dir"] = (
                ckpt.directory or os.path.join(jdir, "ckpt"))
            template.config.setdefault("checkpoint_every", ckpt.interval_steps)
            template.config.setdefault("max_checkpoints", ckpt.max_to_keep)
            # Preemption-aware emergency tier (trainer force-saves on
            # SIGTERM at the next step boundary; train/checkpoint.py).
            template.config.setdefault("emergency_checkpointing",
                                       ckpt.save_on_failure)
        parallelism = (job.spec.parallelism.axis_sizes()
                       if job.spec.parallelism.total > 1 else {})
        w = Worker(
            metadata=ObjectMeta(
                name=name, namespace=job.metadata.namespace,
                labels={LABEL_JOB: job.metadata.name,
                        LABEL_REPLICA_TYPE: WORKER,
                        LABEL_REPLICA_INDEX: str(index)},
                owner=job.key,
            ),
            spec=WorkerSpec(
                job=job.metadata.key,
                replica_index=index,
                num_workers=spec.replicas,
                template=template,
                resources=spec.resources,
                coordinator_address=job.status.coordinator_address,
                gang_name=job.status.gang_name,
                restart_policy=spec.restart_policy,
                parallelism=parallelism,
                chip_ids=list(alloc.chip_assignment.get(index, [])),
                slice_name=alloc.slice_name,
                attempt=job.status.restart_count,
            ),
            status=WorkerStatus(phase=WorkerPhase.PENDING),
        )
        try:
            created = self.store.create(w)
        except AlreadyExistsError:
            return self.store.get(Worker, name, job.metadata.namespace)
        self.recorder.normal(job, "CreatedWorker", f"created {name}")
        return created

    def _delete_worker(self, w: Worker) -> None:
        try:
            self.store.delete(Worker, w.metadata.name, w.metadata.namespace)
        except NotFoundError:
            pass

    # -- status plumbing -------------------------------------------------------

    def _sync_metrics(self, job: JAXJob, workers: list[Worker]) -> None:
        """Lift data-plane metrics (worker-0's metrics.jsonl tail) onto the
        job status — the platform-visible analog of tokens/sec the reference
        never surfaces (SURVEY.md §5 observability)."""
        for w in workers:
            if w.spec.replica_index != 0 or not w.spec.template.working_dir:
                continue
            path = os.path.join(w.spec.template.working_dir, "metrics.jsonl")
            line = _tail_line(path)
            if not line:
                return
            try:
                m = json.loads(line)
            except ValueError:
                return
            job.status.metrics.step = int(m.get("step", job.status.metrics.step))
            for field in ("tokens_per_sec_per_chip", "step_time_ms", "mfu",
                          "loss", "goodput"):
                if m.get(field) is not None:
                    setattr(job.status.metrics, field, float(m[field]))
            # Survivability ledger counters (ISSUE 9): restart economics on
            # job status, where the autoscaler/SRE can see them.
            for field in ("last_checkpoint_step", "steps_lost_total",
                          "emergency_saves", "restore_fallbacks",
                          "checkpoint_save_failures"):
                if m.get(field) is not None:
                    setattr(job.status.metrics, field, int(m[field]))
            return

    def _update_status(self, job: JAXJob) -> None:
        try:
            self.store.update_status(job)
        except NotFoundError:
            pass


def _tail_line(path: str, max_bytes: int = 8192) -> Optional[str]:
    """Last complete line of a file, cheaply (no full read)."""
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - max_bytes))
            chunk = f.read().decode("utf-8", "replace")
    except OSError:
        return None
    lines = [ln for ln in chunk.splitlines() if ln.strip()]
    return lines[-1] if lines else None
