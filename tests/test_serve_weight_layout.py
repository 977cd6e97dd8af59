"""The per-head projections lie as the serving programs read them (ISSUE 39),
on the CPU: which leaves ``serve/weight_layout.py`` lays out heads-major and
which it leaves alone, read off the leaf and never off a model's name; that
``relay`` changes where the bytes lie and nothing a reader of the tree sees;
that the engine's load path ends in it and counts what it did.

What the layout is FOR (no parameter-sized copy left in either program) is
the chip's compiler's to say: ``tests/test_chip_compile.py``. The CPU backend
takes a ``Layout`` too, so the mechanism itself (a plain ``jax.jit`` reads a
committed array in the layout it lies in, ``device_get`` hands back the
logical array) runs here; an engine on the CPU relays nothing by itself, so
the tests that want a relaid tree force the rule's one switch. The tests'
persistent compile cache (tests/conftest.py) stays ON: ``relay`` has to be
right with it, on the first run of this file and on the second.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve import engine as engine_mod
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.weight_layout import (
    HEADS_MAJOR, relaid_bytes, relay, weight_formats,
)

BF16 = dict(dtype="bfloat16", param_dtype="bfloat16")


def _shapes(cfg, quantize=False):
    def tree():
        p = init_decoder_params(jax.random.PRNGKey(0), cfg)
        if quantize:
            from kubeflow_tpu.ops.quantization import quantize_params_int8

            p = quantize_params_int8(p, cfg)
        return p

    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
        jax.eval_shape(tree))


def _relaid(cfg, one_chip_pallas=True, quantize=False):
    """{leaf path: major_to_minor} of the leaves the rule lays out anew."""
    formats = weight_formats(_shapes(cfg, quantize), cfg,
                             one_chip_pallas=one_chip_pallas)
    return {jax.tree_util.keystr(path): tuple(f.layout.major_to_minor)
            for path, f in jax.tree_util.tree_leaves_with_path(formats)}


QKV = {f"['layers']['attn']['{n}']": HEADS_MAJOR for n in ("wq", "wk", "wv")}

# case: (preset, overrides, the rule's switch, int8 weights, leaves relaid)
RULE = {
    # heads of 128, 32 query heads over 8 KV heads: Mistral, Llama
    "heads-of-128": ("llama3-8b", {"n_layers": 2}, True, False, QKV),
    # the same widths with experts: Mixtral; no expert stack moves
    "heads-of-128-experts": ("mixtral-8x7b", {"n_layers": 2}, True, False,
                             QKV),
    # heads of 256 over a LONE KV head (Gemma-2B): whole tiles, so the rule
    # holds (the compile shows wq's copy gone; wk / wv of one head lie the
    # same either way)
    "heads-of-256-lone-kv": ("gemma-2b", {"n_layers": 2}, True, False, QKV),
    # heads of 64 (LFM2): two heads share a tile, the default layout is what
    # the matrix unit reads, heads-major would ADD copies; conv `win` stays
    "heads-of-64": ("lfm2-24b-a2b", {}, True, False, {}),
    # latent attention (GLM): wqa / wqb / wkva / wkvb are no per-head
    # projection of the hidden state
    "latent": ("glm-4.7-flash", {"n_layers": 3}, True, False, {}),
    # int8 leaves are dequantized in the operand read
    "int8": ("llama3-8b", {"n_layers": 2}, True, True, {}),
    # an engine under a mesh, on a CPU, or off the Pallas path
    "not-one-chip-pallas": ("llama3-8b", {"n_layers": 2}, False, False, {}),
    # the layers as a list (no scan): no stacked leaf, nothing sized
    "unscanned": ("llama3-8b", {"n_layers": 2, "scan_layers": False}, True,
                  False, {}),
    # a tiny preset's heads of 16
    "tiny": ("tiny", {}, True, False, {}),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_reads_the_leaf(case):
    name, over, switch, int8, want = RULE[case]
    cfg = preset(name, **BF16, **over)
    assert _relaid(cfg, switch, int8) == want


def test_a_leaf_of_the_name_and_another_shape_stays():
    """``wq`` of a shape that is not [L, hidden, heads, head_dim] of the
    config (a LoRA factor, a fused projection) is not the operand the rule
    was sized for."""
    cfg = preset("llama3-8b", **BF16, n_layers=2)
    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    tree = {"layers": {"attn": {
        "wq": sds(2, 4096, 16, 128),            # half the heads
        "wk": sds(2, 2048, 8, 128),             # another hidden
        "wv": sds(2, 4096, 8, 128, dt=jnp.int8),
        "wo": sds(2, 32, 128, 4096)}}}
    formats = weight_formats(tree, cfg, one_chip_pallas=True)
    assert jax.tree.leaves(formats) == []


@functools.lru_cache(maxsize=None)
def _wide(kind="float32"):
    """The tiny preset with heads of 128: the smallest tree the rule takes."""
    cfg = preset("tiny", head_dim=128, dtype=kind, param_dtype=kind,
                 max_seq_len=256)
    return cfg, init_decoder_params(jax.random.PRNGKey(3), cfg)


def test_relay_moves_bytes_and_nothing_a_reader_sees():
    cfg, params = _wide()
    formats = weight_formats(params, cfg, one_chip_pallas=True)
    out = relay(params, formats)
    moved = 0
    for (path, x), y, f in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(out),
            jax.tree.leaves(formats, is_leaf=lambda f: f is None)):
        assert y.shape == x.shape and y.dtype == x.dtype, path
        # device_get hands back the logical array, wherever the bytes lay
        np.testing.assert_array_equal(np.asarray(jax.device_get(y)),
                                      np.asarray(x), err_msg=str(path))
        if f is None:
            assert y is x, path         # untouched, not even re-put
        else:
            assert tuple(y.format.layout.major_to_minor) == HEADS_MAJOR
            moved += x.nbytes
    a = params["layers"]["attn"]
    assert moved == a["wq"].nbytes + a["wk"].nbytes + a["wv"].nbytes > 0
    assert relaid_bytes(out, formats) == moved
    assert relaid_bytes(params, formats) == 0     # asked for, not held


# One process of the scenario below: relay a tiny tree and project through its
# wq, over the persistent cache the environment names. ``unguarded``: without
# relay's guard, as any other program is compiled (and written to the cache).
_ONE_RUN = """
import contextlib, sys
import jax, jax.numpy as jnp, numpy as np
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve import weight_layout
if sys.argv[1] == "unguarded":
    weight_layout._compiled_afresh = contextlib.nullcontext
cfg = preset("tiny", head_dim=128, dtype="float32", param_dtype="float32")
params = init_decoder_params(jax.random.PRNGKey(3), cfg)
out = weight_layout.relay(params, weight_layout.weight_formats(
    params, cfg, one_chip_pallas=True))
h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.hidden))
project = jax.jit(lambda p, h: jnp.einsum(
    "bsd,dhk->bshk", h, p["layers"]["attn"]["wq"][0]))
wq = out["layers"]["attn"]["wq"]
print("RESULT", list(wq.format.layout.major_to_minor),
      bool(np.array_equal(project(out, h), project(params, h))),
      jax.config.jax_enable_compilation_cache)
"""


def test_relay_is_right_where_the_persistent_cache_holds_its_program(
        tmp_path):
    """JAX 0.9.0 hands a program READ BACK from the persistent cache over
    with its result marked row-major while the bytes lie as compiled (the
    chat cell's second run on the chip read logit errors of 0.9). ``relay``
    compiles its program afresh, whatever the cache holds; a program that
    takes the relaid array as a parameter may come from the cache. Three
    processes over one cache of their own (this worker's caches are left
    alone): one that writes relay's program to it unguarded, then two as
    the engine runs it, the second with every other program cached too."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}

    def run(mode):
        out = subprocess.run([sys.executable, "-c", _ONE_RUN, mode], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return [ln for ln in out.stdout.splitlines()
                if ln.startswith("RESULT")][-1]

    run("unguarded")
    assert os.listdir(tmp_path)
    for _ in range(2):
        assert run("guarded") == f"RESULT {list(HEADS_MAJOR)} True True"


def test_a_plain_jit_reads_a_relaid_leaf_where_it_lies():
    """No ``in_shardings``: the program takes the committed array's layout
    (what the engine's programs and ``benchmark/correctness.py`` lean on),
    and computes what it computed."""
    cfg, params = _wide()
    out = relay(params, weight_formats(params, cfg, one_chip_pallas=True))
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.hidden))
    fn = jax.jit(lambda p, h: jnp.einsum(
        "bsd,dhk->bshk", h, p["layers"]["attn"]["wq"][0]))
    np.testing.assert_array_equal(np.asarray(fn(out, h)),
                                  np.asarray(fn(params, h)))
    taken = fn.lower(out, h).compile().input_formats[0][0]
    assert tuple(taken["layers"]["attn"]["wq"].layout.major_to_minor) \
        == HEADS_MAJOR


def _engine(cfg, params, **kw):
    spec = dict(max_batch_size=4, max_seq_len=256, paged=True, page_size=16,
                chunked_prefill_tokens=32, enable_prefix_caching=False)
    spec.update(kw)
    return LLMEngine(cfg, BatchingSpec(**spec), params=params)


def _greedy(eng, prompts, n=6):
    sp = SamplingParams(max_new_tokens=n, temperature=0.0)
    reqs = [eng.submit(list(map(int, p)), sp) for p in prompts]
    for _ in range(800):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return [list(r.output_tokens) for r in reqs]
    raise AssertionError("requests did not finish")


def _switch_the_rule_on(monkeypatch):
    """The engine's own call, with the rule's switch held on: what an engine
    on one TPU chip does at load, on this CPU."""
    monkeypatch.setattr(
        engine_mod, "weight_formats",
        lambda params, cfg, one_chip_pallas: weight_formats(
            params, cfg, one_chip_pallas=True))


@pytest.fixture
def rule_switched_on(monkeypatch):
    _switch_the_rule_on(monkeypatch)


PROMPTS = [list(range(3, 50)), list(range(7, 27)), [5, 9, 2]]


@pytest.mark.parametrize("weights_dtype", [None, "bfloat16"])
def test_the_load_path_ends_in_the_layout(rule_switched_on, weights_dtype):
    """Behind the cast (which would drop a layout put on before it) the
    engine's tree is the input tree leaf for leaf, three leaves lie
    heads-major, the counter says how many bytes, and the tokens are those
    of an engine that relaid nothing."""
    cfg, params = _wide()
    kw = {} if weights_dtype is None else {"weights_dtype": weights_dtype}
    eng = _engine(cfg, params, **kw)
    want = params if weights_dtype is None else jax.tree.map(
        lambda x: x.astype(weights_dtype), params)
    assert jax.tree.structure(eng.params) == jax.tree.structure(params)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(eng.params)):
        assert y.shape == x.shape and y.dtype == x.dtype, path
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x),
                                      err_msg=str(path))
    a = eng.params["layers"]["attn"]
    for name in ("wq", "wk", "wv"):
        assert tuple(a[name].format.layout.major_to_minor) == HEADS_MAJOR
    assert tuple(a["wo"].format.layout.major_to_minor) == (0, 1, 2, 3)
    assert eng.counters()["weights_relaid_bytes"] == sum(
        a[n].nbytes for n in ("wq", "wk", "wv")) > 0
    _greedy(eng, PROMPTS)
    assert eng.counters()["weights_relaid_bytes"] == sum(
        a[n].nbytes for n in ("wq", "wk", "wv"))    # a constant


def test_greedy_tokens_are_the_same_with_and_without(monkeypatch):
    cfg, params = _wide()
    plain = _engine(cfg, params)
    assert plain.counters()["weights_relaid_bytes"] == 0   # a CPU engine
    want = _greedy(plain, PROMPTS)
    _switch_the_rule_on(monkeypatch)
    relaid = _engine(cfg, params)
    assert relaid.counters()["weights_relaid_bytes"] > 0
    assert _greedy(relaid, PROMPTS) == want


def _compiles_during(fn) -> list:
    """Names of the programs the backend compiled (or read back from the
    persistent cache) while ``fn`` ran: what the benchmark counts inside a
    measured window."""
    seen = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return seen


def test_what_a_relaid_engine_allocates_is_committed_like_its_weights(
        rule_switched_on):
    """A relaid leaf is a committed array and what a program returns is
    committed where an argument is, so a pool or a decode state that began
    uncommitted would meet every program that takes it a second time, in
    another form, in the middle of traffic (on the chip: the COW copy,
    compiled again inside a measured window). They begin committed; a plain
    engine's begin as they always did."""
    cfg, params = _wide()
    eng = _engine(cfg, params, enable_prefix_caching=True)
    own = jax.tree.leaves((eng.cache, eng._dstate.arrays, eng._dstate.table))
    assert all(x._committed for x in own)
    plain_cfg = preset("tiny", dtype="float32", param_dtype="float32",
                       max_seq_len=256)     # heads of 16: nothing relaid
    plain = _engine(plain_cfg, init_decoder_params(jax.random.PRNGKey(3),
                                                   plain_cfg))
    assert not any(x._committed for x in jax.tree.leaves(
        (plain.cache, plain._dstate.arrays, plain._dstate.table)))
    # Warm traffic, then the COW copy the engine warmed at construction, on
    # the pool as traffic left it: no program is met in a new form.
    _greedy(eng, PROMPTS)
    assert _compiles_during(lambda: eng._kv_copy_pages([0], [-1])) == []


def test_a_relaid_engine_warms_the_first_token_widths_itself(
        rule_switched_on):
    """Rows of a chunk's logits are committed in a relaid engine, so the
    sampler a caller warmed over rows of its own making is another program
    than the one traffic calls. The engine warms every width when it is
    built, over rows as its programs return them."""
    cfg, params = _wide()
    eng = _engine(cfg, params)
    row = jax.device_put(jnp.zeros((cfg.vocab_size,), jnp.float32),
                         jax.devices()[0])              # committed
    greedy = SamplingParams(temperature=0.0)

    def every_width():
        for n in range(1, eng.num_slots + 1):
            eng._sample_first([row] * n, [greedy] * n, eng._rng)

    assert _compiles_during(every_width) == []


def test_an_engine_under_a_mesh_keeps_the_default_layouts():
    """On the CPU nothing is relaid anyway; what this pins is that the load
    path's last step hands a sharded tree through as it came."""
    from kubeflow_tpu.runtime.mesh import build_mesh

    cfg, params = _wide()
    mesh = build_mesh({"model": 2}, jax.devices()[:2])
    eng = LLMEngine(cfg, BatchingSpec(
        max_batch_size=2, max_seq_len=64, paged=True, page_size=16,
        chunked_prefill_tokens=16), params=params, mesh=mesh)
    assert eng.counters()["weights_relaid_bytes"] == 0
    wq = eng.params["layers"]["attn"]["wq"]
    assert tuple(wq.format.layout.major_to_minor) == (0, 1, 2, 3)
    assert len(wq.sharding.device_set) == 2


def test_no_option_decides_a_layout():
    assert len(BatchingSpec.model_fields) == 30
