"""The page pool is the engine's only KV cache (ISSUE 30): the ``paged`` key
is kept for the files that carry it and refuses ``false``; the draft model
of speculative decoding runs over a page pool of its own under an identity
table; and the names the benchmark holds the engine by exist."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import decoder_forward, init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name)) as f:
        return json.load(f)


SERVING_TRAFFIC = sorted(
    name for name in map(os.path.basename, glob.glob(
        os.path.join(REPO, "benchmark", "traffic", "*.json")))
    if "engine" in traffic(name))

DRAFT = SpeculativeSpec(mode="draft_model", k=3,
                        draft={"preset": "tiny",
                               "overrides": {"n_layers": 1,
                                             "dtype": "float32"}})


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def make_engine(cfg, params, **kw):
    return LLMEngine(cfg, BatchingSpec(
        max_batch_size=2, max_seq_len=64, page_size=8,
        chunked_prefill_tokens=16, decode_steps=4, **kw), params=params)


def generate(eng, prompts, max_new=10):
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    reqs = [eng.submit(list(p), sp) for p in prompts]
    for _ in range(400):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return [list(r.output_tokens) for r in reqs]
    raise AssertionError("requests did not finish")


def test_paged_false_is_refused_by_name():
    with pytest.raises(ValueError, match="contiguous slot cache is gone"):
        BatchingSpec(paged=False)
    assert BatchingSpec().paged is True
    assert len(BatchingSpec.model_fields) == 30


def test_the_benchmark_has_eighteen_serving_mixes():
    assert len(SERVING_TRAFFIC) == 18, SERVING_TRAFFIC


@pytest.mark.parametrize("name", SERVING_TRAFFIC)
def test_traffic_file_engine_block_builds_a_spec(name):
    """``benchmark/serving.py`` builds ``BatchingSpec(**traffic["engine"])``
    and the files carry ``"paged": true``: the key has to parse until a
    ``benchmark`` PR drops it from them."""
    engine = traffic(name)["engine"]
    assert engine["paged"] is True
    spec = BatchingSpec(**engine)
    assert spec.max_seq_len % spec.page_size == 0
    assert spec.chunked_prefill_tokens % spec.page_size == 0


def test_serverless_paged_example_still_validates():
    from kubeflow_tpu.core import load_manifests

    (isvc,) = load_manifests(
        os.path.join(REPO, "examples", "serverless_paged_isvc.yaml"))
    assert isvc.spec.predictor.batching.paged is True


def test_frozen_names_exist_on_a_built_engine(cfg, params):
    """What ``benchmark/`` and ``scripts/chunk_rows_chip.py`` read."""
    from kubeflow_tpu.serve import paged

    eng = make_engine(cfg, params)
    for name in ("cache", "params", "num_slots", "page_size", "chunk_size",
                 "paged_attn_impl", "program_kernels", "metrics",
                 "decode_rounds", "counters", "submit", "_num_pages", "_mpp",
                 "_paged_chunk", "_cfg_decode", "_sampler", "_next_key",
                 "_pin"):
        assert hasattr(eng, name), name
    assert set(eng.cache) == {"k", "v"}
    assert eng.cache["k"].shape[:3] == (cfg.n_layers, eng._num_pages, 8)
    assert callable(paged._paged_decode_step)
    assert callable(paged.context_bucket)
    assert not hasattr(eng, "paged")


def test_draft_pool_under_identity_table_gives_forward_logits(cfg, params):
    """The draft's cache is ``pool_planes`` at slots x mpp pages with the
    constant table ``arange``: a context caught up through the chunk
    program into slot 1, then one decode step of the pool's own program,
    reads what a full recompute of the draft model reads."""
    from kubeflow_tpu.serve.paged import _paged_decode_step

    eng = make_engine(cfg, params, speculative=DRAFT)
    dcfg, dparams = eng._draft_cfg, eng._draft_params
    cache = eng._draft_cache
    table = np.asarray(cache["table"])
    assert table.tolist() == np.arange(
        eng.num_slots * eng._mpp).reshape(eng.num_slots, eng._mpp).tolist()
    assert cache["k"].shape == (dcfg.n_layers, eng.num_slots * eng._mpp,
                                eng.page_size, dcfg.n_kv_heads,
                                dcfg.head_dim)
    ctx = [(7 * i + 3) % cfg.vocab_size for i in range(21)]
    C, pos = eng.chunk_size, 0
    while pos < len(ctx) - 1:
        real = min(C, len(ctx) - 1 - pos)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :real] = ctx[pos:pos + real]
        cache = eng._draft_chunkfn(dparams, cache, jnp.asarray(chunk),
                                   jnp.int32(1), jnp.int32(pos),
                                   jnp.int32(real))
        pos += real
    tokens = jnp.asarray([0, ctx[-1]], jnp.int32)
    lengths = jnp.asarray([0, len(ctx) - 1], jnp.int32)
    live = jnp.asarray([False, True])
    logits, cache = _paged_decode_step(dparams, cache, tokens, lengths, live,
                                       dcfg)
    want = decoder_forward(dparams, jnp.asarray([ctx], jnp.int32), dcfg)[0]
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(want[0, -1]), atol=2e-4)
    # slot 0 is dead and owns other pages: nothing of it was written
    assert not np.asarray(cache["k"][:, :eng._mpp]).any()


def test_draft_model_greedy_identity(cfg, params):
    prompts = [[5, 17, 3, 99, 42], list(range(1, 30)), [7] * 12]
    want = generate(make_engine(cfg, params), prompts)
    eng = make_engine(cfg, params, speculative=DRAFT)
    assert generate(eng, prompts) == want
    assert eng.metrics.snapshot()["spec_rounds"] > 0
    assert eng.kv_pages_in_use() == 0
    eng._allocator.assert_quiescent()
