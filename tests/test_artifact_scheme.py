"""The artifact:// storage scheme — the train→deploy seam ((U) KFP object
store → kserve storage-initializer; SURVEY.md §2.3#28 + §2.5#44, §3.4→§3.2):
tree artifacts, the name@version register, cross-subsystem resolution, and
the committed e2e — a pipeline trains a model, its artifact uri serves an
InferenceService, and train() consumes a published dataset."""

import json
import os
import time
import urllib.request

import jax
import numpy as np
import pytest

from kubeflow_tpu.pipelines.artifacts import (
    ARTIFACT_SCHEME, ROOT_ENV, SCHEME, ArtifactStore, publish_file,
    publish_model,
)

CORPUS = ("the quick brown fox jumps over the lazy dog. " * 40
          + "pack my box with five dozen liquor jugs. " * 40)


@pytest.fixture()
def store(tmp_path):
    return ArtifactStore(str(tmp_path / "artifacts"))


def _make_tree(root, files):
    for rel, content in files.items():
        p = os.path.join(root, rel)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "wb") as f:
            f.write(content)


class TestTreeArtifacts:
    def test_roundtrip_preserves_contents(self, store, tmp_path):
        src = str(tmp_path / "src")
        files = {"a.bin": b"alpha", "sub/b.bin": b"beta", "sub/deep/c": b"c"}
        _make_tree(src, files)
        uri = store.put_tree(src)
        assert uri.startswith(SCHEME)
        out = store.materialize_tree(uri)
        for rel, content in files.items():
            with open(os.path.join(out, rel), "rb") as f:
                assert f.read() == content

    def test_materialize_idempotent_and_shared(self, store, tmp_path):
        src = str(tmp_path / "src")
        _make_tree(src, {"x": b"1"})
        uri = store.put_tree(src)
        first = store.materialize_tree(uri)
        marker = os.path.join(first, ".complete")
        before = os.path.getmtime(marker)
        assert store.materialize_tree(uri) == first
        assert os.path.getmtime(marker) == before   # no re-write

    def test_trees_dedup_shared_files(self, store, tmp_path):
        big = b"shard-bytes" * 1000
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        _make_tree(a, {"shared.bin": big, "only_a": b"a"})
        _make_tree(b, {"shared.bin": big, "only_b": b"b"})
        ua, ub = store.put_tree(a), store.put_tree(b)
        assert ua != ub
        # 2 manifests + 3 distinct blobs (shared.bin stored once), plus
        # nothing else: count CAS files outside trees/named.
        blobs = sum(
            len(fs) for d, _, fs in os.walk(store.root)
            if not os.path.relpath(d, store.root).startswith(("trees",
                                                              "named")))
        assert blobs == 5

    def test_blob_is_not_a_tree(self, store):
        uri = store.put_bytes(b"raw dataset text")
        with pytest.raises(ValueError, match="not a tree artifact"):
            store.materialize_tree(uri)

    def test_blob_starting_with_T_is_not_a_tree(self, store):
        # Raw blobs are untagged; first-byte sniffing alone would call any
        # capitalized text corpus a tree and crash staging on json.loads.
        for payload in (b"THE SONNETS\nby William Shakespeare",
                        b'T{"not": "a manifest"}',
                        b'T{"kftpu_tree": "wrong shape"}'):
            assert not store.is_tree(store.put_bytes(payload))
        assert open(store.localize(
            "artifact://" + store.put_bytes(b"Titled corpus")[len(SCHEME):]
        ), "rb").read() == b"Titled corpus"

    def test_republish_of_materialized_tree_skips_marker(self, store,
                                                         tmp_path):
        src = str(tmp_path / "src")
        _make_tree(src, {"w": b"weights"})
        out = store.materialize_tree(store.put_tree(src))
        # Re-publishing the materialized dir must not capture .complete —
        # the manifests (and so the digests) of both publishes are equal.
        assert store.put_tree(out) == store.put_tree(src)


class TestRegister:
    def test_register_lookup_latest(self, store):
        u1 = store.put_bytes(b"v1")
        u2 = store.put_bytes(b"v2")
        art1 = store.register("corpus", "1", u1)
        assert art1 == f"{ARTIFACT_SCHEME}corpus@1"
        store.register("corpus", "2", u2)
        assert store.lookup("corpus", "1") == u1
        assert store.lookup("corpus") == u2
        assert store.versions("corpus") == ["1", "2"]

    def test_latest_orders_numerically(self, store):
        # "latest" must be version ORDER, not mtime (racy within a quantum)
        # or lexicographic ("10" < "9").
        u9, u10 = store.put_bytes(b"nine"), store.put_bytes(b"ten")
        store.register("m", "10", u10)      # registered FIRST on purpose
        store.register("m", "9", u9)
        assert store.versions("m") == ["9", "10"]
        assert store.lookup("m") == u10
        ua, ub = store.put_bytes(b"a"), store.put_bytes(b"b")
        store.register("d", "1.9", ua)
        store.register("d", "1.10", ub)
        assert store.lookup("d") == ub

    def test_traversal_names_rejected(self, store):
        # storage_uri / dataset_uri are user-facing: names must never reach
        # os.path.join un-validated.
        for ref in ("../..@x", "/etc@passwd", "..", "a/b@1"):
            with pytest.raises(ValueError):
                store.resolve(ARTIFACT_SCHEME + ref)

    def test_crashed_register_does_not_bind(self, store):
        # A crash mid-register must not leave name@version bound to "".
        # The write-then-link protocol means the entry either has the full
        # uri or does not exist; simulate the old failure by checking a
        # re-register after an interrupted attempt succeeds cleanly.
        u = store.put_bytes(b"x")
        store.register("m2", "1", u)
        assert store.lookup("m2", "1") == u
        store.register("m2", "1", u)        # idempotent re-register

    def test_versions_are_immutable(self, store):
        u1 = store.put_bytes(b"v1")
        u2 = store.put_bytes(b"v2")
        store.register("m", "1", u1)
        store.register("m", "1", u1)             # same content: no-op
        with pytest.raises(ValueError, match="immutable"):
            store.register("m", "1", u2)

    def test_register_requires_stored_content(self, store):
        with pytest.raises(FileNotFoundError):
            store.register("m", "1", SCHEME + "0" * 64)

    def test_bad_names_rejected(self, store):
        u = store.put_bytes(b"x")
        with pytest.raises(ValueError):
            store.register("has/slash", "1", u)
        with pytest.raises(ValueError):
            store.register("0" * 64, "1", u)     # digest-shaped name
        with pytest.raises(ValueError):
            store.register("m", "v@1", u)
        # 64 chars but not hex: a fine name.
        store.register("z" * 64, "1", u)


class TestResolveAndLocalize:
    def test_resolve_digest_form(self, store):
        cas = store.put_bytes(b"data")
        digest = cas[len(SCHEME):]
        assert store.resolve(ARTIFACT_SCHEME + digest) == cas

    def test_resolve_named_forms(self, store):
        cas = store.put_bytes(b"data")
        store.register("m", "7", cas)
        assert store.resolve(f"{ARTIFACT_SCHEME}m@7") == cas
        assert store.resolve(f"{ARTIFACT_SCHEME}m") == cas

    def test_resolve_unknown_name_raises(self, store):
        with pytest.raises(FileNotFoundError, match="no registered"):
            store.resolve(f"{ARTIFACT_SCHEME}ghost")

    def test_resolve_rejects_other_schemes(self, store):
        with pytest.raises(ValueError, match="not an artifact uri"):
            store.resolve("s3://bucket/key")

    def test_resolve_rejects_empty_version(self, store):
        cas = store.put_bytes(b"x")
        store.register("m", "1", cas)
        with pytest.raises(ValueError, match="bad version"):
            store.resolve(f"{ARTIFACT_SCHEME}m@")

    def test_localize_blob_and_tree(self, store, tmp_path):
        blob = store.put_bytes(b"corpus text")
        p = store.localize(blob)
        assert open(p, "rb").read() == b"corpus text"
        src = str(tmp_path / "t")
        _make_tree(src, {"f": b"1"})
        tree = store.put_tree(src)
        assert os.path.isdir(store.localize(tree))


class TestPublishHelpers:
    def test_publish_file_named(self, store, tmp_path):
        p = tmp_path / "data.txt"
        p.write_text(CORPUS)
        uri = publish_file(str(p), name="corpus", store=store)
        assert uri == f"{ARTIFACT_SCHEME}corpus@1"
        assert open(store.localize(uri)).read() == CORPUS

    def test_publish_model_digest_form(self, store, tmp_path):
        src = str(tmp_path / "ckpt")
        _make_tree(src, {"state/params": b"weights"})
        uri = publish_model(src, store=store)
        assert uri.startswith(ARTIFACT_SCHEME)
        out = store.localize(uri)
        assert open(os.path.join(out, "state/params"), "rb").read() == b"weights"

    def test_env_fallback(self, store, tmp_path, monkeypatch):
        monkeypatch.setenv(ROOT_ENV, store.root)
        p = tmp_path / "d.txt"
        p.write_text("x")
        uri = publish_file(str(p), name="envd")
        from kubeflow_tpu.pipelines.artifacts import artifact_store_from_env

        assert artifact_store_from_env().lookup("envd") == store.resolve(uri)

    def test_version_without_name_rejected(self, store, tmp_path):
        p = tmp_path / "d.txt"
        p.write_text("x")
        with pytest.raises(ValueError, match="version requires name"):
            publish_file(str(p), version="2", store=store)

    def test_no_root_is_a_clear_error(self, monkeypatch):
        monkeypatch.delenv(ROOT_ENV, raising=False)
        from kubeflow_tpu.pipelines.artifacts import artifact_store_from_env

        with pytest.raises(RuntimeError, match="KFTPU_ARTIFACT_ROOT"):
            artifact_store_from_env()


class TestStagingArtifactScheme:
    def test_stage_published_dataset(self, store, tmp_path, monkeypatch):
        from kubeflow_tpu.train.staging import stage_inputs

        monkeypatch.setenv(ROOT_ENV, store.root)
        src = tmp_path / "corpus.txt"
        src.write_text(CORPUS)
        uri = publish_file(str(src), name="corpus", store=store)
        out = stage_inputs(str(tmp_path / "job"), dataset_uri=uri,
                           train_tokenizer_vocab=280)
        assert open(out["dataset"]).read() == CORPUS
        assert os.path.exists(out["tokenizer"])

    def test_tree_dataset_rejected(self, store, tmp_path, monkeypatch):
        from kubeflow_tpu.train.staging import stage_inputs

        monkeypatch.setenv(ROOT_ENV, store.root)
        src = str(tmp_path / "t")
        _make_tree(src, {"f": b"1"})
        uri = ARTIFACT_SCHEME + store.put_tree(src)[len(SCHEME):]
        with pytest.raises(ValueError, match="tree artifact"):
            stage_inputs(str(tmp_path / "job"), dataset_uri=uri)


class TestLoadParamsArtifact:
    def test_serving_loads_published_checkpoint(self, store, tmp_path):
        """Train-side orbax save → publish_model → serve-side load_params
        restores the identical param tree through artifact://name@ver."""
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.models.decoder import init_decoder_params
        from kubeflow_tpu.serve.storage import load_params
        from kubeflow_tpu.train.checkpoint import CheckpointManager

        cfg = preset("tiny", vocab_size=512)
        params = init_decoder_params(jax.random.PRNGKey(7), cfg)
        ckpt = str(tmp_path / "ckpt")
        mgr = CheckpointManager(ckpt)
        mgr.save(3, {"params": params}, force=True)
        mgr.wait()
        mgr.close()
        uri = publish_model(ckpt, name="m0", version="1", store=store)
        got = load_params(uri, cfg, artifact_root=store.root)
        jax.tree.map(np.testing.assert_array_equal, params,
                     jax.tree.map(np.asarray, got))


# -- the committed e2e seams --------------------------------------------------


@pytest.fixture()
def live_cp(tmp_path):
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )
    from kubeflow_tpu.runtime.topology import Cluster, SliceTopology

    plane = ControlPlane(ControlPlaneConfig(
        base_dir=str(tmp_path),
        cluster=Cluster(slices=[SliceTopology(name="s0", generation="cpu",
                                              dims=(2, 2))]),
        platform="cpu"))
    plane.start()
    yield plane
    plane.stop()


def _post(url: str, body: dict, timeout=180) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_pipeline_trains_publishes_and_serves(live_cp, tmp_path):
    """VERDICT r3 #1 done-criterion: a pipeline trains a model, publishes
    the orbax checkpoint as a typed Model artifact (with lineage), and the
    artifact uri — no file path — serves an InferenceService."""
    from kubeflow_tpu.core.object import ObjectMeta
    from kubeflow_tpu.core.pipeline_specs import (
        PipelineRun, PipelineRunSpec, RunPhase,
    )
    from kubeflow_tpu.core.serving import (
        BatchingSpec, InferenceService, InferenceServiceSpec, ModelSpec,
        PredictorSpec,
    )
    from kubeflow_tpu.pipelines import dsl
    from kubeflow_tpu.pipelines.compiler import compile_pipeline

    ckpt_dir = str(tmp_path / "pipeckpt")

    @dsl.component
    def train_tiny(steps: int) -> str:
        from kubeflow_tpu.runtime.mesh import build_mesh
        from kubeflow_tpu.train.trainer import Trainer, TrainerConfig

        cfg = TrainerConfig(
            model="tiny", model_overrides={"vocab_size": 512},
            steps=steps, data={"global_batch": 8},
            checkpoint_dir=ckpt_dir, checkpoint_every=steps)
        Trainer(cfg, build_mesh({"data": 8}),
                workdir=str(tmp_path / "pipework")).run()
        return publish_model(ckpt_dir, name="pipe-model", version="1")

    @dsl.pipeline(name="train-and-publish")
    def train_and_publish(steps: int = 2):
        train_tiny(steps=steps)

    run = live_cp.submit(PipelineRun(
        metadata=ObjectMeta(name="tp1"),
        spec=PipelineRunSpec(ir=compile_pipeline(train_and_publish))))
    done = live_cp.wait_for(run, "Succeeded", timeout=300)
    assert done.status.phase is RunPhase.SUCCEEDED
    uri = done.status.tasks["train_tiny"].outputs["output"]
    assert uri == f"{ARTIFACT_SCHEME}pipe-model@1"

    # Lineage: a typed Model artifact exists, carries the register name,
    # was OUTPUT by the training execution, and is attributed to the run.
    from kubeflow_tpu.pipelines import metadata as md

    md_store = live_cp.pipelinerun_reconciler.metadata
    model_aids = md_store.artifacts_of_type("Model")
    assert model_aids, "publish_model recorded no Model artifact"
    art = md_store.get_artifact(model_aids[-1])
    assert art["properties"]["name"] == "pipe-model"
    evs = md_store.events_by_artifact(model_aids[-1])
    assert any(etype == md.EVENT_OUTPUT for _eid, etype in evs)
    train_eid = done.status.tasks["train_tiny"].execution_id
    assert train_eid in [eid for eid, _ in evs]

    # The served seam: the artifact uri IS the storageUri.
    isvc = live_cp.submit(InferenceService(
        metadata=ObjectMeta(name="from-artifact"),
        spec=InferenceServiceSpec(predictor=PredictorSpec(
            model=ModelSpec(
                model_name="from-artifact",
                storage_uri=uri,
                config={"preset": "tiny", "overrides": {"vocab_size": 512}}),
            batching=BatchingSpec(max_batch_size=2, max_seq_len=64,
                                  page_size=16, chunked_prefill_tokens=32)))))
    ready = live_cp.wait_for(isvc, "Ready", timeout=240)
    out = _post(ready.status.url + "/v1/completions",
                {"prompt": "hello", "max_tokens": 4})
    assert out["usage"]["completion_tokens"] >= 1


@pytest.mark.slow
def test_train_consumes_published_dataset(live_cp, tmp_path):
    """The other half of the seam: train() staging a dataset published into
    the platform store, resolved inside a separate worker process through
    the control-plane-injected KFTPU_ARTIFACT_ROOT."""
    from kubeflow_tpu.sdk import Client

    client = Client(live_cp)
    corpus = tmp_path / "corpus.txt"
    corpus.write_text(CORPUS)
    uri = client.publish_file(str(corpus), name="corpus")
    job = client.train(
        "from-published", model="tiny",
        model_overrides={"vocab_size": 512, "max_seq_len": 32},
        steps=4, dataset_uri=uri, train_tokenizer_vocab=280,
        data={"global_batch": 4}, checkpoint=False,
        wait=True, timeout=300)
    assert job.status.metrics.step >= 4
    assert job.status.metrics.loss is not None
