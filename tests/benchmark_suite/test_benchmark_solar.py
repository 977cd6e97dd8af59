"""The ``solar-open2`` architecture and its cell
(``solar-open2-250b.batch-longdoc``): the cell's path rehearsed on the CPU at
tiny widths and judged ``correct`` against its own plain reference, which
walks the KDA layers TOKEN BY TOKEN (through ``engine_logits``' calls as they
stand: ONE page-table row of ``arange`` and no slot, from which a linear layer
finds its sequence's state at ``row[0]``), the float8 control over its limit,
a reference of other equations far over it, ``counts.py`` against the numbers
reckoned by hand in ISSUE 43, the configuration file against the published
config, and each of the cell's twelve readers on a recorded run and on a run
without samples.

The literal tables of the older files of this suite get this cell's entries
from ``tests/conftest.py`` (outside the benchmark's paths)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture, control, correctness
from benchmark import manifest as mf
from benchmark.run import run_cell
from benchmark.weights import make_params, param_shapes
from test_benchmark_program_readers import quiet_run
from test_benchmark_rehearsal_cpu import check_line, rehearsal_manifest

MANIFEST = mf.load_manifest()
CELL = "solar-open2-250b.batch-longdoc"
REHEARSAL = "tiny-solar.rehearsal-closed-state"
CONF = mf.load_config(MANIFEST, "solar-open2-250b")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-solar.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
GQA_CALL = "kernel.paged_decode_attention_bw_share.longdoc"
CHUNK_CALLS = "kernel.paged_chunk_attention_mfu.longdoc"
KDA_CHUNK = "kernel.kda_chunk_roofline_share.longdoc"
KDA_STEP = "kernel.kda_step_bw_share.longdoc"
KDA_MIXER = "step.kda_mixer_mfu.longdoc"
COUNTER_READERS = ["kv.state_share_of_pool.longdoc",
                   "moe.held_row_share.longdoc",
                   "engine.decode_occupancy.longdoc",
                   "kv.preemptions.longdoc",
                   "engine.sched_busy_share_window.longdoc"]
READERS = ["step.prefill_mfu.longdoc", "step.decode_weight_bw_share.longdoc",
           KDA_CHUNK, KDA_STEP, GQA_CALL, CHUNK_CALLS] + COUNTER_READERS \
    + [KDA_MIXER]
# config.json of upstage/Solar-Open2-250B, as the catalog beside the
# model-configs guide gives it
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 47, seconds=2.0,
                    trace=trace, allow_cpu=True)
    # what the CPU's trace can feed: the counters (no device plane)
    counters = set(COUNTER_READERS)
    if trace == 2:
        assert line["correct"] is True and line["failed"] == 0
        assert set(line["metrics"]) == {"serve_tokens_per_s",
                                        "setup_s"} | counters
    else:
        check_line(line, manifest, REHEARSAL, trace=bool(trace))
        if trace:
            assert set(line["metrics"]) == counters
    if trace:
        value = {n: m["value"] for n, m in line["metrics"].items()}
        assert 0.0 < value["engine.decode_occupancy.longdoc"] <= 100.0
        assert value["kv.preemptions.longdoc"] >= 0.0
        # six linear layers' entries (2 slots x 5248 B) beside 16 pages of
        # 16 tokens x 256 B over the two attention layers
        state = 6 * 2 * (4 * 16 * 16 * 4 + 9 * 64 * 2)
        assert value["kv.state_share_of_pool.longdoc"] == pytest.approx(
            100 * state / (state + 16 * 16 * 256))
        # 4 of 16 experts held: a quarter of the routed rows, more or less
        assert 10.0 < value["moe.held_row_share.longdoc"] < 45.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (linear layers refuse int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-state")
    sound, low = [], []
    for seed in (5, 2**31 + 6):
        sides = control.serving_sides(TINY, traffic, seed,
                                      ["program", "reference_fp8"])
        assert correctness.judge(sides["program"], limits)[0], sides
        for name in limits:
            assert sides["reference_fp8"][name] > limits[name], (seed, name)
        sound += [sides["program"][n] for n in limits]
        low += [sides["reference_fp8"][n] for n in limits]
    assert min(low) > 3 * max(sound)
    with pytest.raises(ValueError, match="int8 KV"):
        control.serving_sides(TINY, traffic, 5, ["program_int8"])


@pytest.mark.parametrize("what", ["no decay", "beta in (0, 1)",
                                  "no convolution", "gate ignored",
                                  "share ignored"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference whose state never decays, whose beta
    stops at 1, whose convolutions see the current position alone, whose GQA
    gate is dropped, or which sums another four experts than the ones held:
    not the model, and the comparison says so."""
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 100, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    other_conf, layers = TINY, dict(params["layers"])
    lin = dict(layers["linear"])
    if what == "no decay":
        lin["a_log"] = jnp.full_like(lin["a_log"], -30.0)
    elif what == "beta in (0, 1)":      # 2 sigmoid(z - ln 3) stays under 1
        lin["wb"] = jnp.zeros_like(lin["wb"])
        own_zero = correctness.reference_logits(
            {**params, "layers": {**layers, "linear": lin}}, tokens, TINY,
            last=64)
        err = float(jnp.median(correctness.position_errors(own_zero, own)))
        assert err > 1.5 * limit, (what, err)
        return
    elif what == "no convolution":
        for n in "qkv":
            taps = lin["conv_" + n]
            lin["conv_" + n] = taps.at[:, :-1].set(0)
    elif what == "gate ignored":
        layers["attn"] = {**layers["attn"], "wgate": jnp.zeros_like(
            layers["attn"]["wgate"])}
    else:
        other_conf = {**TINY, "expert_offset": 8}
    got = correctness.reference_logits(
        {**params, "layers": {**layers, "linear": lin}}, tokens, other_conf,
        last=64)
    err = float(jnp.median(correctness.position_errors(got, own)))
    assert err > 1.5 * limit, (what, err)
    assert callable(architecture.part(TINY, "reference").sequence_nll)


def test_the_loss_is_the_logits_next_token_likelihood():
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 9, "float32")
    tokens = jnp.asarray(correctness.check_tokens(9, 0, 33,
                                                  TINY["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        logits = ref.logits(params, tokens[:-1], TINY)
        nll = ref.sequence_nll(params, tokens, TINY)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
    assert float(nll) == pytest.approx(float(want), rel=1e-5)


def test_the_reference_walks_the_sequence_token_by_token_in_blocks():
    """One block or many, the walk is the same recurrence: the state and the
    convolutions' last inputs are what a block hands the next."""
    ref = architecture.part(TINY, "reference")
    assert ref.kda_block_for(17408) == 1024 and ref.kda_block_for(1024) == 1024
    assert ref.kda_block_for(100) == 100 and ref.kda_block_for(2051) == 293
    params = make_params(TINY, 4, "float32")
    p = jax.tree.map(lambda a: a[0], params["layers"]["linear"])
    y = jax.random.normal(jax.random.PRNGKey(0), (60, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.kda_operator(p, y, TINY, lambda x: x)
        ref.kda_block_for = lambda s: 12        # five blocks of twelve
        try:
            blocks = ref.kda_operator(p, y, TINY, lambda x: x)
        finally:
            architecture._load.cache_clear()
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-6)
    # one token of the recurrence, by hand
    state = jnp.ones((1, 2, 2))
    k = jnp.asarray([[1.0, 0.0]])
    new, o = ref.kda_token(state, (jnp.asarray([[0.0, 1.0]]), k,
                                   jnp.asarray([[3.0, 5.0]]),
                                   jnp.log(jnp.asarray([[0.5, 0.25]])),
                                   jnp.asarray([2.0])))
    # Diag(a) S = [[.5, .5], [.25, .25]]; k sees row 0; the update puts
    # 2 (v - row 0) onto row 0
    np.testing.assert_allclose(new[0], [[0.5 + 2 * 2.5, 0.5 + 2 * 4.5],
                                        [0.25, 0.25]])
    np.testing.assert_allclose(o[0], [0.25, 0.25])


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, v, n = 4096, 24576, 8192
    kda_matmuls = 4 * d * n + 2 * (d * 128 + 128 * n) + d * 64
    assert kda_matmuls == COUNTS.kda_matmul_params(CONF) == 137_625_600
    kda = kda_matmuls + 3 * 4 * n + 64 + n + 128
    assert kda == COUNTS.kda_params(CONF) == 137_732_288       # 137.7 M
    gqa = 3 * d * n + 2 * d * 1024
    assert gqa == COUNTS.gqa_matmul_params(CONF) == 109_051_904  # 109.1 M
    expert = 3 * d * 1280
    assert expert == COUNTS.expert_params_one(CONF) == 15_728_640
    held = d * 320 + 320 + 41 * expert                  # 40 held + shared
    assert held == COUNTS.expert_layer_params(CONF) == 646_185_280
    whole_layer = kda + held + 280 * expert + 2 * d
    assert round(whole_layer * 2 / 1e9, 1) == 10.4      # no chip holds one
    assert kda + held + 2 * d == 783_925_760            # a KDA layer as held
    assert gqa + held + 2 * d == 755_245_376            # the GQA layer
    assert 2 * v * d == 201_326_592                     # untied, an eighth
    total = 3 * (kda + held + 2 * d) + gqa + held + 2 * d + 2 * v * d + d
    assert total == COUNTS.params_total(CONF) == 3_308_353_344  # 3.31 B
    assert round(total * 2 / 1e9, 2) == 6.62
    # the GQA layer holds 2 x 8 x 128 values a token; the three KDA layers a
    # [64, 128, 128] float32 matrix and nine rows of 8192 a sequence
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 4096
    assert COUNTS.state_bytes_per_sequence(CONF, 2) == 3 * (
        64 * 128 * 128 * 4 + 9 * 8192 * 2) == 13_025_280
    # the cell's pool: 4352 pages of 128 in the one GQA layer, 32 entries in
    # each of three KDA layers; four full-attention layers would hold 9.1 GB
    assert 4352 * 128 * 4096 == 2_281_701_376
    assert 32 * 13_025_280 == 416_808_960
    assert round(4 * 4352 * 128 * 4096 / 1e9, 1) == 9.1
    # the program counts the same parameters
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs_here():
    d, v = 4096, 24576
    kda, gqa, expert = 137_625_600, 109_051_904, 15_728_640
    assert COUNTS.experts_met(CONF) == 1.0              # 8 x 40 / 320
    matmuls = 3 * kda + gqa + 4 * (d * 320 + 2 * expert)
    assert COUNTS.layers_matmul_params_active(CONF) == matmuls \
        == 653_000_704
    assert COUNTS.kda_token_flops(CONF) == 7.0 * 64 * 128 * 128 == 7_340_032
    assert COUNTS.causal_pairs(512, 4096) == 512 * 4096 + 512 * 513 / 2
    n = 10240
    pairs = n * (n + 1) / 2
    assert COUNTS.attention_flops(CONF, n) == 4.0 * 128 * 64 * pairs
    want = 2.0 * matmuls * n + 3 * 7_340_032 * n \
        + 4.0 * 128 * 64 * pairs + 2.0 * d * v
    assert COUNTS.prefill_flops(CONF, n) == want       # the head ONCE
    assert 1.45e9 < want / n < 1.55e9
    # by operations a token at a mean context of 5k: the KDA mixers 0.85 G,
    # the experts 0.25 G, the GQA projections 0.22 G, its attention 0.16 G
    assert round((2 * 3 * kda + 3 * 7_340_032) / 1e9, 2) == 0.85
    assert round(2 * 4 * 2 * expert / 1e9, 2) == 0.25
    assert round(2 * gqa / 1e9, 2) == 0.22
    assert round(4 * 128 * 64 * 5000 / 1e9, 2) == 0.16
    assert COUNTS.chunk_attention_flops(CONF, n) \
        == COUNTS.attention_flops(CONF, n)
    assert COUNTS.train_flops_per_token(CONF, 4096) == (
        6.0 * (matmuls + d * v)
        + 3.0 * (COUNTS.attention_flops(CONF, 4096) / 4096 + 3 * 7_340_032))
    # a step's weights: everything held but the embedding, the held experts
    # by the share of them that some live stream chose
    fixed = 3_308_353_344 - 4 * 40 * expert - v * d
    at32 = COUNTS.decode_weight_bytes(CONF, 2, 32)
    assert at32 == pytest.approx(
        2.0 * (fixed + 4 * 40 * expert * (1 - (312 / 320) ** 32)))
    assert 4.1e9 < at32 < 4.3e9
    assert COUNTS.resident_weight_bytes(CONF, 2) == 2.0 * 3_308_353_344
    assert COUNTS.decode_attention_bytes(CONF, 1000, 2) == 1000 * 4096
    # the two KDA kernels: a step's call moves a live stream's state in and
    # out (8.4 MB) and 0.2 MB of operands; a chunk call a token a head five
    # rows of 128 and one of 64 float32 values, a row a head the state twice
    assert COUNTS.kda_step_bytes(CONF, 32) == 32 * (
        2 * 64 * 128 * 128 * 4 + 6 * 8192 * 4) == 274_726_912
    assert round(3 * COUNTS.kda_step_bytes(CONF, 32) / 1e9, 2) == 0.82
    assert COUNTS.kda_chunk_flops(CONF, 1024) == 1024 * 7_340_032
    assert COUNTS.kda_chunk_bytes(CONF, 1024, 2) == 1024 * 64 * 4 * (
        5 * 128 + 64) + 2 * 64 * 2 * 128 * 128 * 4
    per_byte = COUNTS.kda_chunk_flops(CONF, 1024) \
        / COUNTS.kda_chunk_bytes(CONF, 1024, 2)
    assert 35 < per_byte < 45           # under the chip's ridge of 240


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "solar-open2-250b")
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == CONF["source"]
    for key, value in PUBLISHED.items():
        if key in CONF["reduced"]:
            assert CONF["reduced"][key]["from"] == value
            assert CONF["reduced"][key]["to"] == CONF[key] != value
        else:
            assert CONF[key] == value, key
    # the router keeps every published output; the chip holds 40 experts
    assert CONF["n_routed_experts_routed"] == PUBLISHED["n_routed_experts"]
    assert (CONF["n_routed_experts"], CONF["expert_offset"]) == (40, 0)
    assert CONF["vocab_size_published"] == PUBLISHED["vocab_size"]
    assert CONF["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the layers held are published layers 0-3: ONE whole period
    assert CONF["gqa_layers_held"] == [0]
    assert CONF["layer_types_held"] == ["full_attention"] \
        + ["linear_attention"] * 3
    assert CONF["num_hidden_layers"] == 4 and CONF["n_routed_experts"] >= 8
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("scoring_func", "router_bias", "gqa_gate",
                 "conv_activation", "qk_l2_norm", "kda_gate_rank",
                 "output_gate", "decay", "beta", "state_dtype",
                 "norm_placement", "shared_expert_width",
                 "intermediate_size"):
        assert item in CONF["assumed"]
    assert "8" in CONF["deployment"] and "40 of the 320" in CONF["deployment"]
    assert "0.8 rows" in CONF["deployment"] and "6.4" in CONF["deployment"]
    assert CONF["architecture"] == "solar-open2" and CONF["chips"] == 1
    assert any(plen + n == 17408 for plen, n
               in CONF["correctness"]["sequences"])
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    """What test_benchmark_manifest.py asks of every configuration, of this
    one (that test takes every key ending in ``_size`` for a width;
    ``vocab_size`` is rows held, tests/conftest.py)."""
    import re

    entry = mf.config_entry(MANIFEST, "solar-open2-250b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    conf = mf.load_json(entry["file"])
    assert conf["source"] == entry["source"]
    width = re.compile(
        r"(_dim|_rank)$|^(hidden|intermediate|moe_intermediate)_size$"
        r"|^num_(attention|key_value)_heads$|^num_experts_per_tok$"
        r"|^linear_attn_config$")
    for key in entry["reduced"]:
        assert not width.search(key), key
        assert conf["reduced"][key]["to"] == conf[key]
    cell = mf.cell(MANIFEST, CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == conf["chips"] == 1 and len(cell["why"]) <= 200
    assert mf.load_traffic(cell["traffic"])["kind"] == "closed_loop"
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "linear_attn_config"):
        assert conf[key] == PUBLISHED[key]


def test_the_programs_config_is_held_against_the_file():
    program = architecture.part(CONF, "program")
    cfg = program.program_config(CONF)
    assert (cfg.n_layers, cfg.leading_dense_layers, cfg.num_experts,
            cfg.experts_held, cfg.shared_experts, cfg.experts_per_token) \
        == (4, 0, 320, 40, 1, 8)
    assert cfg.kinds == ("attention", "linear", "linear", "linear")
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_gate_rank,
            cfg.conv_taps) == (64, 128, 128, 4)
    assert cfg.attn_output_gate and cfg.rope_window_only
    assert (cfg.expert_mlp_dim, cfg.router_scale, cfg.vocab_size) \
        == (1280, 1.0, 24576)
    assert not cfg.tie_embeddings and not cfg.qk_norm
    for key, other in (("n_routed_experts", 80),
                       ("n_routed_experts_routed", 160),
                       ("expert_offset", 40), ("first_k_dense_replace", 1),
                       ("num_hidden_layers", 8),
                       ("routed_scaling_factor", 2.5),
                       ("norm_topk_prob", False),
                       ("num_key_value_heads", 4), ("head_dim", 64),
                       ("n_shared_experts", 0), ("use_gqa_gate", False),
                       ("tie_word_embeddings", True), ("use_rope", True),
                       ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False),
                       ("kda_gate_rank", 64), ("vocab_size", 196608),
                       ("gqa_layers_held", [0, 2])):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="linear_attn_config"):
        program.program_config({**CONF, "linear_attn_config": {
            **CONF["linear_attn_config"], "short_conv_kernel_size": 3}})
    with pytest.raises(mf.ManifestError, match="gqa_layers_held is not"):
        program.program_config({**CONF, "gqa_layers": [1, 5]})
    # a config object that disagrees with the file is refused as well
    with pytest.raises(mf.ManifestError, match="use_gqa_gate"):
        program.program_config(CONF, attn_output_gate=False)
    with pytest.raises(mf.ManifestError, match="solar-open2 is"):
        program.program_config(CONF, rope_window_only=False)


def got_bias(conf, seed):
    weights = architecture.part(conf, "weights")
    return weights.balanced_bias(jax.random.PRNGKey(seed), 4,
                                 conf["n_routed_experts_routed"],
                                 conf["n_routed_experts"])


def test_the_seeded_tree_is_the_programs_at_the_published_widths():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert got["lm_head"].shape == (4096, 24576)
    assert got["layers"]["mlp"]["gate"].shape == (4, 40, 4096, 1280)
    assert got["layers"]["mlp"]["router"].shape == (4, 4096, 320)
    assert got["layers"]["linear"]["wq"].shape == (3, 4096, 64, 128)
    assert got["layers"]["attn"]["wgate"].shape == (1, 4096, 64, 128)
    assert got["layers"]["mlp"]["router_bias"].dtype == "float32"
    # the decay's draw: A in [1, 16] a head, the step in [1e-3, 1e-1]
    tiny = make_params(TINY, 3, "float32")
    a = np.exp(np.asarray(tiny["layers"]["linear"]["a_log"]))
    step = np.asarray(jax.nn.softplus(tiny["layers"]["linear"]["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert 0.99e-3 <= step.min() and step.max() <= 1.01e-1
    assert np.exp(-a.min() * step.min()) > 0.99
    assert 0.15 < np.exp(-16 * 0.1) < 0.25     # the strongest decay at rest
    # the bias is the same multiset in every layer and for every seed, and
    # every chip's block of 40 held experts has one value of each stratum
    bias = np.asarray(got_bias(CONF, 7))
    other = np.asarray(got_bias(CONF, 8))
    assert bias.shape == (4, 320) and 0.045 < bias.std() < 0.055
    assert (bias != other).mean() > 0.9
    np.testing.assert_array_equal(np.sort(bias, axis=1), np.sort(other, 1))
    ranks = np.argsort(np.argsort(bias, axis=1), axis=1) // 8   # stratum
    for block in ranks.reshape(4, 8, 40):
        assert all(sorted(chip) == list(range(40)) for chip in block)


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 32
    assert e["enable_prefix_caching"] is False      # the cell shares nothing
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    assert traffic["prompt_len"]["dist"] == traffic["output_len"]["dist"] \
        == "uniform"
    # ISSUE 43's two sanctioned fallbacks, both taken (PERF.md section 2),
    # over the engine the issue names; the pool is what a 51 s window
    # serves once (98-103 requests get their first token in it, which is
    # when a prompt counts), so every seed serves the same multiset of
    # sizes in another order
    assert traffic["pool"] == 100
    assert (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]) \
        == (6144, 12288)
    assert (traffic["output_len"]["min"], traffic["output_len"]["max"]) \
        == (384, 768)
    mpp = e["max_seq_len"] // e["page_size"]
    assert mpp == 136 and e["max_pages"] == 32 * mpp        # no preemption
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= e["max_seq_len"]
    need = required_programs(traffic, BatchingSpec(**e))
    # The warm-up's first prompt walks every chunk start of the longest
    # context alone (the one-row program at every bucket), its second group
    # sends two prompts at once (the two-row program)
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {f"paged_decode[{k},greedy]" for k in (1,)} <= need


# -- the twelve readers -------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 3000 decode steps over 28 live streams, 900 chunk
    programs that carried 1700 chunks of 860k tokens, 1.6 M expert rows
    routed and 0.2 M held; 3 traced seconds holding two chunk programs (40
    and 60 ms), a cache copy, two decode programs of one step each (12 ms)
    over 28 streams at 8000 and 12000 context rows a stream, in each one call
    of the GQA layer's kernel (2 ms) and three of ``kda_step`` (0.4 ms), and
    in each chunk program one chunk attention call (4 ms) and three of
    ``kda_chunk`` (1.5 ms), each between the expert kernels (``gmm``) of the
    layer before it and of its own: stretches of 1.9, 1.7 and 2.1 ms."""
    run = quiet_run("any.longdoc")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(slots=32, kv_sequence_pool_bytes=416_808_960,
                              kv_token_pool_bytes=2_281_701_376,
                              kv_pool_bytes=2_698_510_336)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=3000, decode_tokens_emitted=84_000,
        prefill_programs_dispatched=900, prefill_chunks_dispatched=1700,
        prefill_tokens_dispatched=860_000, preemptions=2,
        expert_rows_routed=1_600_000, expert_rows_held=200_000,
        sched_host_busy_sum_s=10.0)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 28, "context": 28 * 8000}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 28, "context": 28 * 12000}]])
    ops = []
    for step in (0.2, 0.25):
        ops.append(["%paged_decode_attention.3 = custom-call", step, 0.002])
        ops += [[f"%kda_step.{i} = custom-call", step + 0.003 + 0.001 * i,
                 0.0004] for i in range(3)]
        # the op that takes a kernel's result names it too, and is no call
        ops.append(["%multiply.7 = f32[32,64,128] multiply(f32[32,64,128] "
                    "%kda_step.1, %broadcast.3)", step + 0.0071, 1e-7])
    for chunk in (0.0, 0.1):
        ops.append(["%paged_chunk_attention.9 = custom-call", chunk, 0.004])
        ops += [[f"%kda_chunk.{i} = custom-call", chunk + 0.005 + 0.002 * i,
                 0.0015] for i in range(3)]
        ops += [[f"%gmm.{i} = custom-call", chunk + at, dur]
                for i, (at, dur) in enumerate((
                    (0.0040, 0.0002), (0.0042, 0.0005), (0.0066, 0.0003),
                    (0.0086, 0.0003), (0.0110, 0.0004), (0.0115, 0.0004)))]
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.040],
                    ["jit__lambda(7)", 0.1, 0.060],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.012],
                    ["jit__paged_decode_fn(3)", 0.25, 0.012]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.03]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0,
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [8192, 4096, 16384]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 0.8e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # a step over 28 live streams reads 4.0 GB of held weights; 12 ms
    assert read["step.decode_weight_bw_share.longdoc"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2, 28) / 819e9 / 0.012)
    assert 35 < read["step.decode_weight_bw_share.longdoc"] < 50
    # two programs of 1.89 chunks of 0.8 TFLOP needed over 100 ms
    assert read["step.prefill_mfu.longdoc"] == pytest.approx(
        100 * 2 * (1700 / 900) * 0.8e12 / (0.100 * 197e12))
    # the GQA call: 280k context rows a step x 4096 B in 2 ms
    assert read[GQA_CALL] == pytest.approx(
        100 * 280_000 * 4096 / 819e9 / 0.002)
    # the step kernel: 28 streams' states in and out in 0.4 ms
    assert read[KDA_STEP] == pytest.approx(
        100 * COUNTS.kda_step_bytes(CONF, 28) / 819e9 / 0.0004)
    assert 0 < read[KDA_STEP] <= 100
    # the chunk kernel: 955.6 tokens in 1.89 rows a call, its bytes at the
    # bus's speed (the nearer roof) over 1.5 ms
    tokens, rows = 860_000 / 900, 1700 / 900
    assert COUNTS.kda_chunk_bytes(CONF, tokens, rows) / 819e9 \
        > COUNTS.kda_chunk_flops(CONF, tokens) / 197e12
    assert read[KDA_CHUNK] == pytest.approx(
        100 * COUNTS.kda_chunk_bytes(CONF, tokens, rows) / 819e9 / 0.0015)
    assert 0 < read[KDA_CHUNK] <= 100
    # the chunk attention calls: the three prompts' needed attention over
    # their 56 chunks, x 3.78 chunks traced, over 8 ms of calls
    need = sum(COUNTS.chunk_attention_flops(CONF, n)
               for n in (8192, 4096, 16384)) / 56 * 2 * 1700 / 900
    assert read[CHUNK_CALLS] == pytest.approx(100 * need / (0.008 * 197e12))
    # the KDA mixers: six stretches between expert kernels, 11.4 ms, each
    # needing one layer's projections and recurrence for 955.6 tokens
    assert COUNTS.kda_mixer_flops(CONF, 1) == 2 * 137_625_600 + 7_340_032
    assert read[KDA_MIXER] == pytest.approx(
        100 * 6 * COUNTS.kda_mixer_flops(CONF, tokens)
        / (2 * (0.0019 + 0.0017 + 0.0021) * 197e12))
    assert 60 < read[KDA_MIXER] < 100
    assert read["kv.state_share_of_pool.longdoc"] == pytest.approx(
        100 * 416_808_960 / 2_698_510_336)
    assert 15.0 < read["kv.state_share_of_pool.longdoc"] < 16.0
    assert read["moe.held_row_share.longdoc"] == 12.5
    assert read["engine.decode_occupancy.longdoc"] == pytest.approx(
        100 * 84_000 / (3000 * 32))
    assert read["kv.preemptions.longdoc"] == 2.0
    assert read["engine.sched_busy_share_window.longdoc"] == 25.0


@pytest.mark.parametrize("name", READERS)
def test_reader_on_runs_without_samples_and_without_a_source(name):
    read = mf.load_layer_metric(name).read
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert {k: entry[k] for k in mf.load_layer_metric(name).DECLARATION} \
        == mf.load_layer_metric(name).DECLARATION
    # counters at rest, a trace that holds none of the programs: the
    # stated number (the pool's share is a constant of the engine)
    quiet = {**recorded_run(), **quiet_run("any.longdoc")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = 12.5 if name.startswith("kv.state_share") else 0.0
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # a program from before this PR with these files dropped in: its engine
    # has no sequence planes and does not count the tokens it prefilled
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        for key in ("kv_sequence_pool_bytes", "kv_token_pool_bytes"):
            part["engine"].pop(key, None)
    if name.startswith("kv.state_share"):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: a step that took exactly its
    weights' time on the bus, a call exactly its bytes' time."""
    run = recorded_run()
    least = COUNTS.decode_weight_bytes(CONF, 2, 28) / 819e9
    floor = {"%paged_decode_attention": 280_000 * 4096 / 819e9,
             "%kda_step": COUNTS.kda_step_bytes(CONF, 28) / 819e9,
             "%kda_chunk": COUNTS.kda_chunk_bytes(
                 CONF, 860_000 / 900, 1700 / 900) / 819e9}
    device = run["trace"]["devices"][0]
    device["modules"] = [m[:2] + [least] if "decode" in m[0] else m
                         for m in device["modules"]]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in ("step.decode_weight_bw_share.longdoc", GQA_CALL, KDA_STEP,
                 KDA_CHUNK):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)


def test_the_mixers_stretch_is_found_by_the_kernels_names_alone():
    """A stretch that took exactly its needed operations' time at the peak
    reads 100; a ``kda_chunk`` call whose program the trace cut (no expert
    kernel in front of it) and one in another program's expert kernels'
    reach are left out; moving work across the kernel's boundary inside the
    stretch changes nothing."""
    run = recorded_run()
    least = COUNTS.kda_mixer_flops(CONF, 860_000 / 900) / 197e12
    device = run["trace"]["devices"][0]
    device["modules"] = [["jit__lambda(7)", 0.0, 0.040],
                         ["jit__lambda(7)", 0.1, 0.060]]

    def program(at, scan_s):
        return [["%gmm.2 = custom-call", at, 0.001],
                ["%kda_chunk.1 = custom-call", at + 0.002, scan_s],
                ["%gmm.3 = custom-call", at + 0.001 + least, 0.001]]

    cut = [["%kda_chunk.1 = custom-call", 0.1, 0.001],
           ["%gmm.3 = custom-call", 0.102, 0.001]]
    reader = mf.load_layer_metric(KDA_MIXER)
    device["ops"] = program(0.0, 0.0006) + cut
    assert reader.stretches(run["trace"]) == [
        (pytest.approx(0.001), pytest.approx(0.001 + least))]
    assert reader.read(run) == pytest.approx(100.0)
    device["ops"] = program(0.0, least - 0.003) + cut
    assert reader.read(run) == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-state")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"kv_sequence_pool_bytes", "kv_token_pool_bytes", "kv_pool_bytes",
            "expert_rows_routed", "expert_rows_held", "kv_bytes_per_token",
            "prefill_chunks_dispatched", "prefill_programs_dispatched",
            "prefill_tokens_dispatched", "decode_steps_dispatched",
            "decode_tokens_emitted", "preemptions", "slots",
            "sched_host_busy_sum_s", "state_sequences_started"} \
        <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2)
    assert counters["kv_sequence_pool_bytes"] == engine.num_slots \
        * counts.state_bytes_per_sequence(TINY, 2)
    assert counters["kv_sequence_pool_bytes"] \
        + counters["kv_token_pool_bytes"] == counters["kv_pool_bytes"]


def test_what_pr_43_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/solar-open2-250b.json",
                 "benchmark/configs/rehearsal-tiny-solar.json",
                 "benchmark/traffic/batch-longdoc.json",
                 "benchmark/traffic/rehearsal-closed-state.json"]
                + [f"benchmark/architectures/solar-open2/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(READERS):] == READERS          # appended, in order
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == "solar-open2-250b"
    assert mf.cell(MANIFEST, CELL)["config"] == "solar-open2-250b"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
