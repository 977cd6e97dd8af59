"""Test configuration: force an 8-device virtual CPU platform.

Tests validate multi-chip sharding semantics without TPU hardware by running
JAX on 8 virtual CPU devices (the driver separately dry-runs the multi-chip
path; bench.py runs on the real chip). Must run before jax initializes."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# One persistent XLA compile cache for the whole run — this process and every
# worker it starts. The suite builds the same tiny programs hundreds of times
# (each engine fixture, each worker process), and a hit replaces an XLA
# compile with a file read: a fifth off a cold run here, two thirds off a
# warm one. Entries are keyed by the program's HLO and the compiler's
# version, so a code change misses by itself. Placed as the program places
# its own (runtime/bootstrap.py): where JAX_COMPILATION_CACHE_DIR is set,
# there; else the one fixed <checkout>/.jax_cache (git-ignored).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

# Pin the config too, so tests run on the 8-device virtual CPU mesh whatever
# set the platform list before this file ran. Guarded so the jax-free core
# tests still collect on a box without jax.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def decode_rounds_at_their_caps(request, monkeypatch):
    """A decode round's length is the engine scheduler's choice from what it
    measures of itself (serve/pacing.py), and on this CPU, at tiny sizes and
    under six test workers, what it measures changes from run to run. The
    tests that count dispatches, preemptions or pages were written for
    rounds at the two options' values, which is where the choice stands
    until something is measured: here it stays there, so they see the same
    schedule every time. ``@pytest.mark.paced`` (tests/test_serve_pacing.py)
    takes the choice as it is deployed."""
    if "paced" in request.keywords:
        return
    try:
        from kubeflow_tpu.serve.pacing import RoundPacer
    except ImportError:         # the jax-free core tests on a box without jax
        return
    monkeypatch.setattr(RoundPacer, "choose", lambda self, cap: cap)


@pytest.fixture()
def store():
    from kubeflow_tpu.core.store import ObjectStore

    return ObjectStore()


@pytest.fixture()
def tiny_job():
    """A minimal valid JAXJob for controller tests."""
    from kubeflow_tpu.core.jobs import (
        JAXJob, JAXJobSpec, ReplicaSpec, WorkloadSpec, ParallelismSpec,
        TPUResourceSpec,
    )
    from kubeflow_tpu.core.object import ObjectMeta

    return JAXJob(
        metadata=ObjectMeta(name="tiny", namespace="default"),
        spec=JAXJobSpec(
            replica_specs={
                "worker": ReplicaSpec(
                    replicas=2,
                    template=WorkloadSpec(entrypoint="noop", config={"steps": 2}),
                    resources=TPUResourceSpec(tpu_chips=1),
                )
            },
            parallelism=ParallelismSpec(data=2),
        ),
    )
