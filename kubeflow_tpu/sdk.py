"""Python SDK — the TrainingClient / KatibClient / kfp.Client analog.

((U) training-operator sdk/python kubeflow/training TrainingClient
{create_job,get_job,get_job_logs,wait_for_job_conditions,delete_job, train};
katib KatibClient.tune; kfp.Client.create_run — SURVEY.md §2.2#22, §2.4#36,
§2.5#37.) One client over the in-process control plane: the platform is
single-host, so the SDK talks to the store directly; the HTTP path for
remote callers is the CLI/ApiServer.
"""

from __future__ import annotations

import os
import time
from typing import Any, Optional

from kubeflow_tpu.core.jobs import (
    JAXJob, JAXJobSpec, ParallelismSpec, ReplicaSpec, TPUResourceSpec,
    WorkloadSpec,
)
from kubeflow_tpu.core.object import ApiObject, ObjectMeta
from kubeflow_tpu.core.pipeline_specs import (
    Pipeline, PipelineRun, PipelineRunSpec, PipelineSpecModel,
)


class Client:
    """SDK over a running ControlPlane (start one, or use ``local()``)."""

    def __init__(self, control_plane):
        self.cp = control_plane

    @classmethod
    def local(cls, base_dir: Optional[str] = None, platform: str = "cpu",
              num_chips: Optional[int] = None) -> "Client":
        """Spin up an in-process platform (caller owns .shutdown())."""
        from kubeflow_tpu.operator.control_plane import (
            ControlPlane, ControlPlaneConfig,
        )
        from kubeflow_tpu.runtime.topology import detect_local_cluster

        cluster = (detect_local_cluster(num_chips=num_chips,
                                        platform=platform)
                   if num_chips else None)
        cp = ControlPlane(ControlPlaneConfig(
            base_dir=base_dir, platform=platform, cluster=cluster))
        cp.start()
        return cls(cp)

    def shutdown(self) -> None:
        self.cp.stop()

    # -- training (TrainingClient surface) -------------------------------------

    def create_job(
        self,
        name: str,
        *,
        entrypoint: str = "llm_pretrain",
        config: Optional[dict[str, Any]] = None,
        workers: int = 1,
        chips_per_worker: int = 1,
        parallelism: Optional[dict[str, int]] = None,
        namespace: str = "default",
        submit: bool = True,
        **run_policy,
    ) -> JAXJob:
        job = JAXJob(
            metadata=ObjectMeta(name=name, namespace=namespace),
            spec=JAXJobSpec(
                replica_specs={"worker": ReplicaSpec(
                    replicas=workers,
                    template=WorkloadSpec(entrypoint=entrypoint,
                                          config=config or {}),
                    resources=TPUResourceSpec(tpu_chips=chips_per_worker))},
                parallelism=ParallelismSpec(**(parallelism or {})),
            ))
        for k, v in run_policy.items():
            setattr(job.spec.run_policy, k, v)
        return self.cp.submit(job) if submit else job

    def get_job(self, name: str, namespace: str = "default") -> Optional[JAXJob]:
        return self.cp.store.try_get(JAXJob, name, namespace)

    def get_job_logs(self, name: str, namespace: str = "default",
                     worker: int = 0, max_bytes: int = 65536) -> str:
        path = os.path.join(self.cp.config.base_dir, "logs",
                            f"{namespace}.{name}-worker-{worker}.log")
        try:
            with open(path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - max_bytes))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    def wait_for_job_conditions(
        self, name: str, conditions=("Succeeded",),
        namespace: str = "default", timeout: float = 300.0,
    ) -> JAXJob:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            job = self.get_job(name, namespace)
            if job is not None:
                for c in conditions:
                    if job.status.has_condition(c):
                        return job
                if "Failed" not in conditions \
                        and job.status.has_condition("Failed"):
                    cond = job.status.get_condition("Failed")
                    raise RuntimeError(
                        f"job {name} failed: {cond.reason if cond else ''} "
                        f"{cond.message if cond else ''}")
            time.sleep(0.2)
        raise TimeoutError(f"job {name}: none of {conditions} in {timeout}s")

    def delete_job(self, name: str, namespace: str = "default") -> None:
        self.cp.store.delete(JAXJob, name, namespace)

    def train(
        self,
        name: str,
        *,
        model: str = "llama3-8b",
        model_overrides: Optional[dict] = None,
        steps: int = 100,
        workers: int = 1,
        chips_per_worker: int = 1,
        parallelism: Optional[dict[str, int]] = None,
        optimizer: Optional[dict] = None,
        data: Optional[dict] = None,
        dataset_uri: Optional[str] = None,
        tokenizer_uri: Optional[str] = None,
        train_tokenizer_vocab: Optional[int] = None,
        checkpoint: bool = True,
        namespace: str = "default",
        wait: bool = False,
        timeout: float = 3600.0,
    ) -> JAXJob:
        """High-level LLM training (TrainingClient.train analog — the
        reference downloads HF model+dataset into a PVC via its
        storage-initializer initContainer; ``dataset_uri`` stages the
        dataset into the job dir the same way, tokenizing through a staged
        or freshly-trained BPE artifact)."""
        config = {
            "model": model,
            "model_overrides": model_overrides or {},
            "steps": steps,
            "optimizer": optimizer or {},
            "data": data or {},
        }
        if dataset_uri:
            config["dataset_uri"] = dataset_uri
        if tokenizer_uri:
            config["tokenizer_uri"] = tokenizer_uri
        if train_tokenizer_vocab:
            config["train_tokenizer_vocab"] = train_tokenizer_vocab
        job = self.create_job(
            name,
            entrypoint="llm_pretrain",
            config=config,
            workers=workers, chips_per_worker=chips_per_worker,
            parallelism=parallelism, namespace=namespace,
            submit=False)   # finish the spec BEFORE the controller sees it
        job.spec.run_policy.checkpoint.enabled = checkpoint
        job = self.cp.submit(job)
        if wait:
            return self.wait_for_job_conditions(name, namespace=namespace,
                                                timeout=timeout)
        return job

    # -- HPO (KatibClient surface) ---------------------------------------------

    def tune(self, name: str, *, timeout: float = 600.0, **kwargs):
        from kubeflow_tpu.tune.client import tune as _tune

        return _tune(self.cp, name, timeout=timeout, **kwargs)

    # -- pipelines (kfp.Client surface) ----------------------------------------

    def upload_pipeline(self, pipeline_def, *, name: Optional[str] = None,
                        namespace: str = "default") -> Pipeline:
        from kubeflow_tpu.pipelines.compiler import as_pipeline_object

        return self.cp.apply(as_pipeline_object(
            pipeline_def, namespace=namespace, name=name))

    def create_run(self, pipeline: str, *, run_name: Optional[str] = None,
                   parameters: Optional[dict] = None,
                   namespace: str = "default", wait: bool = False,
                   timeout: float = 600.0) -> PipelineRun:
        run = PipelineRun(
            metadata=ObjectMeta(
                name=run_name or f"{pipeline}-{int(time.time())}",
                namespace=namespace),
            spec=PipelineRunSpec(pipeline=pipeline,
                                 parameters=parameters or {}))
        run = self.cp.submit(run)
        if wait:
            return self.cp.wait_for(run, "Succeeded", timeout=timeout)
        return run

    # -- artifacts (the train→deploy seam) -------------------------------------

    @property
    def artifacts(self):
        """The platform artifact store. ``publish_model(ckpt_dir,
        name=..., store=client.artifacts)`` → an ``artifact://`` uri usable
        as an InferenceService storageUri or a ``train()`` dataset_uri."""
        return self.cp.artifact_store

    def publish_model(self, checkpoint_dir: str, *, name=None,
                      version=None) -> str:
        from kubeflow_tpu.pipelines.artifacts import publish_model

        return publish_model(checkpoint_dir, name=name, version=version,
                             store=self.artifacts)

    def publish_file(self, path: str, *, name=None, version=None,
                     type_name: str = "Dataset") -> str:
        from kubeflow_tpu.pipelines.artifacts import publish_file

        return publish_file(path, name=name, version=version,
                            store=self.artifacts, type_name=type_name)

    # -- generic ---------------------------------------------------------------

    def apply(self, obj: ApiObject) -> ApiObject:
        return self.cp.apply(obj)

    def wait_for(self, obj: ApiObject, condition: str = "Succeeded",
                 timeout: float = 300.0) -> ApiObject:
        return self.cp.wait_for(obj, condition, timeout=timeout)
