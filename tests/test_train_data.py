"""Real data path: BPE tokenizer, text→grain pipeline, staging
(storage-initializer analog), and mid-epoch resume — the round-1 verdict's
"train from a text file and resume mid-epoch" e2e ((U) training-operator
sdk train(); SURVEY.md §2.2#22)."""

import os

import numpy as np
import jax
import pytest

from kubeflow_tpu.serve.tokenizer import BPETokenizer, ByteTokenizer
from kubeflow_tpu.train.data import DataConfig, make_data_source

CORPUS = ("the tpu runs the model and the model runs on the tpu " * 40
          + "pipelines schedule experiments while experiments tune models " * 30)


class TestBPE:
    def test_roundtrip_exact(self):
        tok = BPETokenizer.train(CORPUS, vocab_size=300)
        for text in ("the tpu runs", "experiments tune models",
                     "unseen words also roundtrip", "ünïcödé too"):
            assert tok.decode(tok.encode(text)) == text

    def test_compresses_vs_bytes(self):
        tok = BPETokenizer.train(CORPUS, vocab_size=340)
        byte = ByteTokenizer()
        text = "the tpu runs the model"
        assert len(tok.encode(text)) < len(byte.encode(text))
        assert tok.vocab_size > byte.vocab_size

    def test_save_load(self, tmp_path):
        tok = BPETokenizer.train(CORPUS, vocab_size=300)
        path = str(tmp_path / "tok.json")
        tok.save(path)
        tok2 = BPETokenizer.load(path)
        assert tok2.merges == tok.merges
        assert tok2.encode("the tpu") == tok.encode("the tpu")


class TestTextSource:
    def _cfg(self, tmp_path, **kw):
        p = tmp_path / "corpus.txt"
        if not p.exists():
            p.write_text(CORPUS)
        return DataConfig(kind="text", path=str(p), vocab_size=512,
                          seq_len=16, global_batch=4, **kw)

    def test_batches_are_deterministic_fast_forward(self, tmp_path):
        cfg = self._cfg(tmp_path)
        a = make_data_source(cfg)
        b = make_data_source(cfg)     # a "restarted worker"
        for step in (0, 3, 17, 100):
            np.testing.assert_array_equal(a.batch_at(step), b.batch_at(step))
        assert a.batch_at(0).shape == (4, 17)
        # Different steps see different data (epoch shuffle, not repetition).
        assert not np.array_equal(a.batch_at(0), a.batch_at(1))

    def test_shards_partition_the_batch(self, tmp_path):
        cfg = self._cfg(tmp_path)
        full = make_data_source(cfg).batch_at(5)
        s0 = make_data_source(cfg, shard=0, num_shards=2).batch_at(5)
        s1 = make_data_source(cfg, shard=1, num_shards=2).batch_at(5)
        np.testing.assert_array_equal(np.concatenate([s0, s1]), full)

    def test_tokenization_cached_once(self, tmp_path):
        cfg = self._cfg(tmp_path)
        make_data_source(cfg)
        caches = [f for f in os.listdir(tmp_path) if f.endswith(".tokens.npy")]
        assert len(caches) == 1
        mtime = os.path.getmtime(tmp_path / caches[0])
        make_data_source(cfg)         # second construction reuses the cache
        assert os.path.getmtime(tmp_path / caches[0]) == mtime

    def test_bpe_tokenizer_path(self, tmp_path):
        tok = BPETokenizer.train(CORPUS, vocab_size=300)
        tok_path = str(tmp_path / "tok.json")
        tok.save(tok_path)
        cfg = self._cfg(tmp_path, tokenizer_path=tok_path)
        src = make_data_source(cfg)
        batch = src.batch_at(0)
        assert batch.max() >= 259   # merged ids beyond the byte range occur


class TestStaging:
    def test_stage_dataset_and_train_tokenizer(self, tmp_path):
        from kubeflow_tpu.train.staging import stage_inputs

        src = tmp_path / "data.txt"
        src.write_text(CORPUS)
        work = tmp_path / "job"
        out = stage_inputs(str(work), dataset_uri=f"file://{src}",
                           train_tokenizer_vocab=300)
        assert os.path.exists(out["dataset"])
        assert os.path.exists(out["tokenizer"])
        tok = BPETokenizer.load(out["tokenizer"])
        assert tok.vocab_size == 300
        # Idempotent (restart path).
        again = stage_inputs(str(work), dataset_uri=f"file://{src}",
                             train_tokenizer_vocab=300)
        assert again == out

    def test_unsupported_scheme_rejected(self, tmp_path):
        from kubeflow_tpu.train.staging import stage_inputs

        with pytest.raises(ValueError, match="scheme"):
            stage_inputs(str(tmp_path), dataset_uri="s3://bucket/x")


@pytest.mark.slow
def test_text_training_resumes_mid_epoch(tmp_path):
    """The committed e2e: train from a raw text file (staged, BPE-tokenized)
    with checkpoints, kill, resume mid-epoch — the resumed run must consume
    EXACTLY the batches an uninterrupted run would and end bitwise-equal."""
    from kubeflow_tpu.train.trainer import Trainer, TrainerConfig

    src = tmp_path / "corpus.txt"
    src.write_text(CORPUS)

    def make(steps):
        cfg = TrainerConfig(
            model="tiny", model_overrides={"vocab_size": 512,
                                           "max_seq_len": 32},
            dataset_uri=f"file://{src}",
            train_tokenizer_vocab=300,
            data={"global_batch": 8},
            steps=steps, log_every=5,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=5,
        )
        from kubeflow_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": 8})
        return Trainer(cfg, mesh, workdir=str(tmp_path / "job"))

    tr1 = make(steps=5)
    tr1.run()
    # A finished run says what it ran on (runtime/device_report.py).
    from kubeflow_tpu.runtime.device_report import read_device_report

    assert read_device_report(str(tmp_path / "job"))["platform"] == "cpu"

    # Resume: picks up the step-5 checkpoint mid-epoch and continues.
    tr2 = make(steps=10)
    assert tr2.try_resume() == 5
    # Fast-forward proof: the resumed source serves the same step-5.. batches
    # a fresh source would.
    fresh = make(steps=10)
    np.testing.assert_array_equal(tr2.data.batch_at(5), fresh.data.batch_at(5))
    np.testing.assert_array_equal(tr2.data.batch_at(9), fresh.data.batch_at(9))
    m2 = tr2.run()
    assert int(jax.device_get(tr2.task.state["step"])) == 10
    assert np.isfinite(m2["loss"])

    # Uninterrupted oracle: same 10 steps in one run, bitwise-equal params.
    import shutil

    shutil.rmtree(tmp_path / "ckpt")
    tr3 = make(steps=10)
    tr3.run()
    a = jax.device_get(tr2.task.state["params"]["embed"])
    b = jax.device_get(tr3.task.state["params"]["embed"])
    np.testing.assert_array_equal(a, b)


class TestReviewRegressions:
    def test_too_short_corpus_clear_error(self, tmp_path):
        p = tmp_path / "tiny.txt"
        p.write_text("short")
        cfg = DataConfig(kind="text", path=str(p), vocab_size=512,
                         seq_len=128, global_batch=4)
        with pytest.raises(ValueError, match="seq_len"):
            make_data_source(cfg)

    def test_cached_tokens_validated_against_vocab(self, tmp_path):
        p = tmp_path / "corpus.txt"
        p.write_text(CORPUS)
        big = DataConfig(kind="text", path=str(p), vocab_size=512,
                         seq_len=16, global_batch=4)
        make_data_source(big)         # writes the cache
        small = DataConfig(kind="text", path=str(p), vocab_size=50,
                           seq_len=16, global_batch=4)
        with pytest.raises(ValueError, match="vocab"):
            make_data_source(small)   # cache hit must still validate

    def test_bpe_trailing_space_roundtrip(self):
        tok = BPETokenizer.train(CORPUS, vocab_size=300)
        for text in ("a ", "", "  ", "the tpu ", " leading"):
            assert tok.decode(tok.encode(text)) == text, repr(text)

    def test_staged_tokenizer_refreshes_on_change(self, tmp_path):
        import time as _t

        from kubeflow_tpu.train.staging import stage_inputs

        art = tmp_path / "tok.json"
        BPETokenizer.train(CORPUS, 280).save(str(art))
        work = str(tmp_path / "job")
        out = stage_inputs(work, tokenizer_uri=str(art))
        v1 = BPETokenizer.load(out["tokenizer"]).vocab_size
        _t.sleep(0.05)
        BPETokenizer.train(CORPUS, 320).save(str(art))
        out = stage_inputs(work, tokenizer_uri=str(art))
        assert BPETokenizer.load(out["tokenizer"]).vocab_size != v1
