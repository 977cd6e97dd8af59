"""The ``nemotron-h`` architecture and its cell
(``nemotron-3-super-120b-a12b.batch-agentturns``): the cell's path rehearsed
on the CPU at tiny widths and judged ``correct`` against its own plain
reference, the float8 control and the six controls of the stack (no Mamba
layers, no attention layer, no routed experts, the experts fed the hidden in
place of the latent, plain ReLU for its square, one group in the gated norm)
over the limit, ``counts.py`` against the numbers reckoned by hand in ISSUE
61, the configuration file against the published config, ``program.py``'s
table refusing a drifted key, and each of the cell's nine readers on a
recorded run and on a run without samples (ISSUE 61 named seventeen; the
benchmark's list of per-layer metrics holds 128 and had 119).

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
CELL = "nemotron-3-super-120b-a12b.batch-agentturns"
REHEARSAL = "tiny-nemotronh.rehearsal-closed-ssd"
CONF = mf.load_config(MANIFEST, "nemotron-3-super-120b-a12b")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-nemotronh.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
STATE = "step.state_bytes_share.agentturns"
SSD = "step.ssd_share.agentturns"
PRODUCTS = "step.expert_matmul_share.agentturns"
CHUNK_KERNEL = "kernel.ssd_chunk_roofline_share.agentturns"
STEP_KERNEL = "kernel.ssd_step_bw_share.agentturns"
COUNTER_READERS = [STATE, "moe.held_row_share.agentturns",
                   "kv.state_share_of_pool.agentturns"]
READERS = [STATE, SSD, CHUNK_KERNEL, STEP_KERNEL, PRODUCTS,
           "step.decode_weight_bw_share.agentturns",
           "step.prefill_mfu.agentturns"] + COUNTER_READERS[1:]
with open("/opt/skills/guides/model-configs/architectures.jsonl") as _f:
    # config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, as the
    # catalog beside the model-configs guide gives it
    PUBLISHED = next(json.loads(line) for line in _f
                     if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 61, seconds=2.0,
                    trace=trace, allow_cpu=True)
    if trace == 2:
        assert line["correct"] is True and line["failed"] == 0
        assert set(line["metrics"]) >= {"serve_tokens_per_s",
                                        "setup_s"} | set(COUNTER_READERS)
        value = {n: m["value"] for n, m in line["metrics"].items()}
        # 4 of the tiny router's 16 experts are held
        assert 10.0 < value["moe.held_row_share.agentturns"] < 40.0
        # three mixers' entries a slot beside one attention layer's pages
        assert 0.0 < value["kv.state_share_of_pool.agentturns"] < 100.0
        assert 0.0 < value[STATE] < 100.0
    else:
        check_line(line, manifest, REHEARSAL, trace=False)
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_controls_are_over_the_limit_and_the_program_under():
    """One precision step down fails by each number, and so does a reference
    with any one part of the stack got wrong (what the comparison reads
    beside a program that lacks the mechanism); the program's own int8 path
    cannot be a control (a state a sequence refuses int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-ssd")
    ref = architecture.part(TINY, "reference")
    spec = {"sequences": [[100, 4]]}
    seed = 2**31 + 6
    sides = control.serving_sides(TINY, traffic, seed,
                                  ["program", "reference_fp8"])
    assert correctness.judge(sides["program"], limits)[0], sides
    for name in limits:
        assert sides["reference_fp8"][name] > limits[name], name
    params = make_params(TINY, seed, "bfloat16")
    toks = correctness.check_tokens(seed, 0, 104, TINY["vocab_size"])
    want = [correctness.reference_logits(params, toks, TINY, last=8)]
    for variant in ref.VARIANTS[1:]:
        fn = jax.jit(lambda p, t, v=variant: ref.logits(
            p, t, TINY, last=8, variant=v))
        with jax.default_matmul_precision("highest"):
            got = [fn(params, jnp.asarray(toks))]
        numbers = correctness.compare_sides(got, want, spec, 32)
        assert any(numbers[name] > 2 * limits[name] for name in limits), \
            (variant, numbers)
    with pytest.raises(ValueError, match="int8 KV"):
        control.serving_sides(TINY, traffic, 5, ["program_int8"])


def test_the_loss_is_the_logits_next_token_likelihood():
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 9, "float32")
    toks = jnp.asarray(correctness.check_tokens(9, 0, 25, 256))
    with jax.default_matmul_precision("highest"):
        lg = ref.logits(params, toks[:-1], TINY)
        nll = ref.sequence_nll(params, toks, TINY)
    logp = jax.nn.log_softmax(lg, axis=-1)
    want = -jnp.sum(jnp.take_along_axis(logp, toks[1:, None], axis=-1))
    assert float(nll) == pytest.approx(float(want), rel=1e-5)


def test_the_reference_is_plain():
    for part in ("reference", "counts", "weights"):
        path = os.path.join(mf.ROOT, "benchmark/architectures/nemotron-h",
                            part + ".py")
        with open(path) as f:
            text = f.read()
        assert "import kubeflow_tpu" not in text \
            and "from kubeflow_tpu" not in text, part
        assert "pallas" not in text
    with open(os.path.join(mf.ROOT, "benchmark/architectures/nemotron-h",
                           "reference.py")) as f:
        text = f.read()
    assert "lax.scan" in text and "ssd_token" in text   # token by token


# -- counts.py against ISSUE 61's arithmetic ----------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d = 4096
    assert d * 18560 == 76_021_760 and 8192 + 10240 + 128 == 18560
    assert COUNTS.mamba_layer_params(CONF) == (
        76_021_760 + (10240 * 4 + 10240) + 3 * 128 + 8192 + 8192 * d + d) \
        == 109_640_064
    assert COUNTS.attention_layer_params(CONF) == (
        2 * d * d + 2 * d * 256 + d) == 35_655_680
    assert COUNTS.expert_params_one(CONF) == 2 * 1024 * 2688 == 5_505_024
    assert COUNTS.shared_expert_params(CONF) == 2 * d * 5376 == 44_040_192
    assert COUNTS.expert_layer_params_outside_experts(CONF) == (
        d * 512 + 512 + 2 * d * 1024 + 44_040_192 + d) == 54_530_560
    assert COUNTS.expert_layer_params_published(CONF) == 2_873_102_848
    assert COUNTS.expert_layer_params_total(CONF) == 759_173_632
    assert 2 * 32768 * d == 268_435_456
    total = COUNTS.params_total(CONF)
    assert total == (5 * 109_640_064 + 35_655_680 + 5 * 759_173_632
                     + 268_435_456 + d) == 4_648_163_712
    assert round(total * 2 / 1e9, 2) == 9.30
    # the whole published model: 40 Mamba, 8 attention and 40 expert layers
    whole = COUNTS.params_total({
        **CONF, "layers_held": PUBLISHED["config"]["hybrid_override_pattern"],
        "n_routed_experts": 512, "vocab_size": 131072})
    assert whole == (40 * 109_640_064 + 8 * 35_655_680 + 40 * 2_873_102_848
                     + 1_073_741_824 + d) == 120_668_707_840
    # thirteen layers, which the issue does not take
    assert round(COUNTS.params_total({**CONF, "layers_held": "MEMEMEM*EMEME"})
                 * 2 / 1e9, 1) == 11.0
    # the cache: 1024 B a token in the ONE attention layer, 21,278,720 B a
    # sequence over the five Mamba layers
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 1024
    assert COUNTS.state_bytes_per_sequence(CONF, 2) == 5 * (
        4_194_304 + 61_440) == 21_278_720
    pages, states = 2944 * 128 * 1024, 128 * 21_278_720
    assert round(pages / 1e9, 3) == 0.386 and round(states / 1e9, 3) == 2.724
    assert round(100 * states / (pages + states), 1) == 87.6
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    assert sum(s.size for s in jax.tree.leaves(
        param_shapes(CONF, "bfloat16"))) == total


def test_operations_are_what_the_model_needs_here():
    d = 4096
    assert COUNTS.experts_met(CONF) == 5.5      # 22 choices, a quarter held
    layer = d * 512 + 2 * d * 1024 + 44_040_192 + 5.5 * 5_505_024
    assert COUNTS.expert_layer_matmul_params_active(CONF) == layer
    mamba = 76_021_760 + 8192 * d
    per_token = COUNTS.layers_matmul_params_active(CONF)
    assert per_token == 5 * mamba + 35_651_584 + 5 * layer
    assert round(2 * per_token / 1e9, 2) == 2.02
    assert round(5 * mamba / per_token, 2) == 0.54      # the projections
    assert round(5 * layer / per_token, 2) == 0.42      # the expert layers
    assert COUNTS.ssd_chunk_flops(CONF, 1) == 5 * 128 * 64 * 128
    n = 1024
    want = (2.0 * per_token * n + 4 * 128 * 32 * n * (n + 1) / 2
            + 5 * 5 * 128 * 64 * 128 * n + 2.0 * d * 32768)
    assert COUNTS.prefill_flops(CONF, n) == pytest.approx(want, rel=1e-12)
    # the kernels' bytes at THIS shape
    assert COUNTS.ssd_step_bytes(CONF, 128) == 128 * (
        2 * 4_194_304 + 2 * 3 * 10240 * 2)
    assert COUNTS.ssd_chunk_bytes(CONF, 1024, 2) == 1024 * (
        8192 * 6 + 2 * 1024 * 2 + 4 * 128) + 2 * 2 * 4_194_304
    assert COUNTS.decode_attention_bytes(CONF, 128 * 1500, 2) \
        == 128 * 1500 * 1024
    assert COUNTS.chunk_attention_flops(CONF, 512) \
        == 4 * 128 * 32 * 512 * 513 / 2
    # a step's weights: everything but the embedding, of the 640 held
    # experts those that some live stream is expected to choose
    stack = 5 * 128 * 5_505_024
    fixed = 4_648_163_712 - stack - 32768 * d
    assert round(2 * stack / 1e9, 2) == 7.05
    assert round(2 * fixed / 1e9, 2) == 1.98
    assert COUNTS.decode_weight_bytes(CONF, 2, 0) == 2.0 * fixed
    touched = 1 - (1 - 22 / 512) ** 128
    assert round(touched, 3) == 0.996
    assert COUNTS.decode_weight_bytes(CONF, 2, 128) == pytest.approx(
        2.0 * (fixed + touched * stack))
    # ISSUE 61's "state is 37% of a step's bytes" at 128 streams
    state = 2 * 128 * 21_278_720
    assert round(state / 1e9, 2) == 5.45
    share = state / (state + COUNTS.decode_weight_bytes(CONF, 2, 128))
    assert 0.36 < share < 0.39
    assert COUNTS.train_flops_per_token(CONF, 1024) > 6 * per_token


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "nemotron-3-super-120b-a12b")
    reduced = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 128),
               "vocab_size": (131072, 32768),
               "num_nextn_predict_layers": (1, 0)}
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) \
        == sorted(reduced)
    assert entry["source"] == CONF["source"] == PUBLISHED["source_url"]
    for key, value in PUBLISHED["config"].items():
        if key in reduced:
            assert (CONF["reduced"][key]["from"], CONF["reduced"][key]["to"],
                    CONF[key]) == (value, reduced[key][1], reduced[key][1])
            assert value == reduced[key][0] and CONF["reduced"][key]["why"]
        else:
            assert key in CONF and CONF[key] == value, key
    assert (CONF["n_routed_experts_published"], CONF["expert_offset"],
            CONF["vocab_size_published"], CONF["layers_held"]) \
        == (512, 0, 131072, "MEMEMEM*EME")
    assert PUBLISHED["config"]["hybrid_override_pattern"].startswith(
        CONF["layers_held"])
    assert CONF["layer_types_held"].count("full_attention") == 1
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("position", "layers", "latent_experts", "router",
                 "shared_expert", "activation", "in_projection",
                 "gated_norm", "ssm_init", "unread_keys",
                 "prediction_module", "correction_bias", "weights"):
        assert item in CONF["assumed"]
    assert "NO rotation" in CONF["assumed"]["position"]
    assert "NOT built" in CONF["assumed"]["prediction_module"]
    for key in ("expand", "chunk_size", "moe_shared_expert_overlap",
                "rescale_prenorm_residual", "residual_in_fp32",
                "use_mamba_kernels", "num_logits_to_keep"):
        assert key in json.dumps(CONF["assumed"]), key
    assert "one chip of the 4 that share EACH LAYER" in CONF["deployment"]
    assert "87.6%" in CONF["cache"]
    assert CONF["architecture"] == "nemotron-h" and CONF["chips"] == 1
    longest = max(plen + n for plen, n in CONF["correctness"]["sequences"])
    assert longest == CONF["program"]["overrides"]["max_seq_len"] == 2944
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")
    # no width is reduced
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key in (
            "vocab_size",)


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    entry = mf.config_entry(MANIFEST, "nemotron-3-super-120b-a12b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    conf = mf.load_json(entry["file"])
    assert conf["source"] == entry["source"]
    cell = mf.cell(MANIFEST, CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == conf["chips"] == 1 and len(cell["why"]) <= 200
    assert mf.load_traffic(cell["traffic"])["kind"] == "closed_loop"


def test_the_programs_config_is_held_against_the_file():
    program = architecture.part(CONF, "program")
    cfg = program.program_config(CONF)
    assert (cfg.n_layers, cfg.hidden, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (6, 4096, 32, 2, 128, 32768)
    assert cfg.kinds == ("ssd",) * 4 + ("attention", "ssd")
    assert cfg.ffn_free == (3,) and cfg.fed.count(True) == 5
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_groups,
            cfg.ssd_chunk, cfg.conv_taps) == (128, 64, 128, 8, 128, 4)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.expert_mlp_dim, cfg.moe_latent_dim, cfg.shared_experts,
            cfg.router_scale) == (512, 128, 22, 2688, 1024, 2, 5.0)
    assert cfg.router_score == "sigmoid" and cfg.router_norm_topk
    assert cfg.hidden_act == "relu2" and cfg.mlp_matrices == 2
    assert not cfg.use_rope and not cfg.tie_embeddings
    for key, other in (("hidden_size", 2048), ("num_hidden_layers", 12),
                       ("num_attention_heads", 16),
                       ("num_key_value_heads", 8), ("head_dim", 64),
                       ("intermediate_size", 1024),
                       ("moe_intermediate_size", 1536),
                       ("moe_latent_size", 512),
                       ("moe_shared_expert_intermediate_size", 2688),
                       ("n_shared_experts", 2), ("n_routed_experts", 64),
                       ("n_routed_experts_published", 256),
                       ("expert_offset", 128), ("num_experts_per_tok", 8),
                       ("routed_scaling_factor", 2.5),
                       ("norm_topk_prob", False), ("n_group", 8),
                       ("topk_group", 4), ("mlp_hidden_act", "silu"),
                       ("mamba_hidden_act", "gelu"),
                       ("layer_norm_epsilon", 1e-6), ("norm_eps", 1e-6),
                       ("tie_word_embeddings", True),
                       ("attention_bias", True), ("mlp_bias", True),
                       ("use_bias", True), ("mamba_proj_bias", True),
                       ("use_conv_bias", False), ("conv_kernel", 3),
                       ("mamba_head_dim", 128), ("mamba_num_heads", 64),
                       ("ssm_state_size", 256), ("n_groups", 2),
                       ("chunk_size", 256), ("vocab_size", 131072),
                       ("num_nextn_predict_layers", 1)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="layers_held"):
        program.program_config({**CONF, "layers_held": "MEMEMEMEM*E"})
    with pytest.raises(mf.ManifestError, match="nemotron-h is"):
        program.program_config(CONF, use_rope=True)


def test_the_seeded_tree_is_the_programs():
    from kubeflow_tpu.models.decoder import init_decoder_params

    for conf in (CONF, TINY):
        cfg = architecture.part(conf, "program").program_config(conf)
        want = jax.eval_shape(
            lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
        got = param_shapes(conf, cfg.param_dtype)
        assert jax.tree.map(lambda a: (a.shape, a.dtype), got) \
            == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert got["layers"]["ssd"]["w_z"].shape == (2, 64, 64)
    got = param_shapes(CONF, "bfloat16")
    assert got["embed"].shape == (32768, 4096)
    assert set(got) == {"embed", "layers", "layers_rest", "final_norm",
                        "lm_head"}
    first, rest = got["layers"], got["layers_rest"]
    assert first["ln1"].shape == (5, 4096) and first["ln2"].shape == (4, 4096)
    assert first["ssd"]["w_xbc"].shape == (4, 4096, 10240)
    assert first["attn"]["wk"].shape == (1, 4096, 2, 128)
    assert first["mlp"]["up"].shape == (4, 128, 1024, 2688)
    assert first["mlp"]["router"].shape == (4, 4096, 512)
    assert first["mlp"]["shared"]["down"].shape == (4, 5376, 4096)
    assert rest["mlp"]["latent_up"].shape == (1, 1024, 4096)
    # the stratified bias: every seed the same multiset, each block of the
    # held width one value of each stratum
    tiny = [make_params(TINY, s, "float32") for s in (3, 4)]
    a, b = (np.asarray(t["layers"]["mlp"]["router_bias"]) for t in tiny)
    assert a.shape == (2, 16)
    assert np.array_equal(np.sort(a, axis=1), np.sort(b, axis=1))
    assert not np.array_equal(a, b)
    ranks = np.argsort(np.argsort(a, axis=1), axis=1) // 4    # 4 strata of 4
    assert all(sorted(block) == [0, 1, 2, 3]
               for layer in ranks for block in layer.reshape(4, 4))
    # Mamba-2's initialisation, from the row's own keys
    ssd = tiny[0]["layers"]["ssd"]
    step = np.asarray(jax.nn.softplus(ssd["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert 0.0 <= float(ssd["a_log"].min()) \
        and float(ssd["a_log"].max()) <= np.log(16.0)
    for leaf, fan in ((ssd["w_z"], 64), (ssd["w_out"], 64),
                      (tiny[0]["layers"]["mlp"]["up"], 32),
                      (tiny[0]["layers"]["mlp"]["latent_up"], 32),
                      (tiny[0]["layers"]["mlp"]["shared"]["down"], 64)):
        std = float(np.std(np.asarray(leaf))) * fan ** 0.5
        assert 0.85 < std < 1.15, (fan, std)


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    # the named sizes or ISSUE 61's first fallback (96 on 96)
    slots = e["max_batch_size"]
    assert traffic["clients"] == slots and slots in (128, 96)
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    assert set(e) == {"paged", "max_batch_size", "max_seq_len", "page_size",
                      "max_pages", "chunked_prefill_tokens", "decode_steps",
                      "prefill_interleave_steps", "enable_prefix_caching"}
    assert e["enable_prefix_caching"] is False
    assert BatchingSpec(**e).max_concurrent_prefills \
        == BatchingSpec().max_concurrent_prefills == 2
    assert traffic["shared_prefix_tokens"] == 0
    prompts = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"])
    answers = (traffic["output_len"]["min"], traffic["output_len"]["max"])
    # the named sizes or the second fallback: the same means
    assert (prompts, answers) in (((768, 2304), (384, 640)),
                                  ((1152, 1920), (448, 576)))
    mpp = e["max_seq_len"] // e["page_size"]
    longest = prompts[1] + answers[1]
    assert mpp == 23 and longest <= e["max_seq_len"] == 2944
    assert e["max_pages"] == slots * mpp            # whole contexts
    need = required_programs(traffic, BatchingSpec(**e))
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {"paged_decode[1,greedy]"} <= need
    assert {f"paged_chunk_prefill[1x512,{b}]" for b in (4, 8, 16, 23)} \
        == {n for n in need if n.startswith("paged_chunk_prefill")}
    # the comparison's long sequence reaches the longest context
    assert max(p + n for p, n in CONF["correctness"]["sequences"]) == 2944


# -- the nine readers -----------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 1500 decode steps over 120 live streams and 500 chunk
    programs (900 chunks, 430,000 tokens; 400 carried the step); 3 traced
    seconds holding two chunk programs (60 and 64 ms) of two rows each with
    a step of 120 streams riding, and one decode-only step (30 ms): in each
    chunk program five ``ssd_chunk`` calls (1.5 ms), five ``ssd_step`` (1.6
    ms), two chunk-attention calls (0.8 ms), one decode-attention call (0.5
    ms) and ten grouped matmuls (2 ms); in the decode-only step five
    ``ssd_step``, one decode-attention call and ten ragged products (0.3
    ms)."""
    run = quiet_run("any.agentturns")
    state = 2 * 21_278_720
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(slots=128, kv_pool_bytes=3_109_551_104,
                              kv_sequence_pool_bytes=2_723_676_160)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=1500, decode_tokens_emitted=180_000,
        state_bytes_stepped=180_000 * state,
        prefill_programs_dispatched=500, prefill_chunks_dispatched=900,
        prefill_tokens_dispatched=430_000, mixed_programs_dispatched=400,
        expert_rows_routed=22_000_000, expert_rows_held=5_500_000,
        decode_rounds=1500, sched_host_busy_sum_s=4.0,
        sched_sync_state_sum_s=0.75)
    ops = []
    for start, with_chunk in ((0.0, True), (0.1, True), (0.2, False)):
        t = start
        for name, n, dur in (
                ("ssd_chunk", 5 * with_chunk, 0.0015), ("ssd_step", 5, 0.0016),
                ("paged_chunk_attention", 2 * with_chunk, 0.0008),
                ("paged_decode_attention", 1, 0.0005),
                ("gmm", 10 * with_chunk, 0.002),
                ("ragged-dot", 10 * (not with_chunk), 0.0003)):
            for i in range(n):
                ops.append([f"%{name}.{i} = custom-call", t, dur])
                t += dur
    spans = [["engine.decode_dispatch", 0.0 + 0.1 * i, 0.001,
              {"round": i, "k_steps": 1, "live": 120,
               "context": 120 * 1800, "live_rows": 120,
               "state_bytes": 120 * state}] for i in range(3)]
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.060],
                    ["jit__lambda(7)", 0.1, 0.064],
                    ["jit__paged_decode_fn(3)", 0.2, 0.030]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.060],
                      ["%fusion.12 = fusion", 0.1, 0.064],
                      ["%fusion.13 = fusion", 0.2, 0.030]]}]}
    run["host_spans"].append(spans)
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 51.0, "values": {"setup_s": 100.0},
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [1024, 800, 2000]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 1.0e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    state = 180_000 * 2 * 21_278_720
    weights = 1500 * COUNTS.decode_weight_bytes(CONF, 2, 120.0)
    assert read[STATE] == pytest.approx(100 * state / (state + weights))
    assert 33 < read[STATE] < 40
    busy = 0.060 + 0.064 + 0.030
    assert read[SSD] == pytest.approx(
        100 * (10 * 0.0015 + 15 * 0.0016) / busy)
    assert read[PRODUCTS] == pytest.approx(
        100 * (20 * 0.002 + 10 * 0.0003) / busy)
    # a chunk kernel's call: 860 tokens in 1.8 rows, the larger of its
    # operations at the peak and its bytes at the bus, over 1.5 ms
    floor = max(COUNTS.ssd_chunk_flops(CONF, 860) / 197e12,
                COUNTS.ssd_chunk_bytes(CONF, 860, 1.8, 2) / 819e9)
    assert read[CHUNK_KERNEL] == pytest.approx(100 * floor / 0.0015)
    assert 0 < read[CHUNK_KERNEL] < 100
    assert read[STEP_KERNEL] == pytest.approx(
        100 * COUNTS.ssd_step_bytes(CONF, 120, 2) / 819e9 / 0.0016)
    assert 0 < read[STEP_KERNEL] < 100
    # two programs of 1.8 chunks of 1 TFLOP needed over 124 ms
    assert read["step.prefill_mfu.agentturns"] == pytest.approx(
        100 * 2 * 1.8 * 1.0e12 / (0.124 * 197e12))
    # the one decode-ONLY step (one call of the decode kernel inside a
    # decode program): the weights 120 streams are expected to touch / 30 ms
    assert read["step.decode_weight_bw_share.agentturns"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2, 120.0) / 819e9 / 0.030)
    assert 30 < read["step.decode_weight_bw_share.agentturns"] < 100
    assert read["moe.held_row_share.agentturns"] == 25.0
    assert read["kv.state_share_of_pool.agentturns"] == pytest.approx(
        100 * 2_723_676_160 / 3_109_551_104)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_runs_without_samples_and_without_a_source(name):
    read = mf.load_layer_metric(name).read
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert {k: entry[k] for k in mf.load_layer_metric(name).DECLARATION} \
        == mf.load_layer_metric(name).DECLARATION
    # counters at rest, a trace that holds none of the programs: the stated
    # number
    quiet = {**recorded_run(), **quiet_run("any.agentturns")}
    quiet["values"] = {"setup_s": 30.0}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = {"kv.state_share_of_pool.agentturns": 12.5}.get(name, 0.0)
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the PARENT's program with these files dropped in (it cannot build this
    # model; an engine without the new counter): nothing or a number, never
    # an exception
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        part["engine"].pop("state_bytes_stepped", None)
    if name == STATE:
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: calls that took exactly their
    needed work's time."""
    run = recorded_run()
    floor = {"%ssd_chunk": max(
        COUNTS.ssd_chunk_flops(CONF, 860) / 197e12,
        COUNTS.ssd_chunk_bytes(CONF, 860, 1.8, 2) / 819e9),
        "%ssd_step": COUNTS.ssd_step_bytes(CONF, 120, 2) / 819e9}
    device = run["trace"]["devices"][0]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in (CHUNK_KERNEL, STEP_KERNEL):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)
    for name in (SSD, PRODUCTS, STATE):     # shares of a whole
        assert 0 < mf.load_layer_metric(name).read(run) < 100


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-ssd")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"state_bytes_stepped", "expert_rows_routed", "expert_rows_held",
            "kv_pool_bytes", "kv_sequence_pool_bytes", "kv_bytes_per_token",
            "prefill_chunks_dispatched", "prefill_programs_dispatched",
            "prefill_tokens_dispatched", "mixed_programs_dispatched",
            "decode_steps_dispatched", "decode_tokens_emitted",
            "preemptions", "slots", "decode_rounds",
            "sched_host_busy_sum_s", "sched_sync_state_sum_s"} \
        <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2) == 2 * 2 * 16 * 2
    # the program's account of a sequence's state and the model's need
    assert counters["kv_sequence_pool_bytes"] == engine.num_slots \
        * counts.state_bytes_per_sequence(TINY, 2)
    assert engine._state_bytes_a_row \
        == 2 * counts.state_bytes_per_sequence(TINY, 2)


def test_what_pr_61_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/nemotron-3-super-120b-a12b.json",
                 "benchmark/configs/rehearsal-tiny-nemotronh.json",
                 "benchmark/traffic/batch-agentturns.json"]
                + [f"benchmark/architectures/nemotron-h/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(READERS[0])
    assert sorted(names[at:at + len(READERS)]) == sorted(READERS)
    assert len(READERS) == 9 and len(names) == 128    # the list is full
    assert all(n.split(".")[-1] != "agentturns" for n in names[:at])
    assert mf.cell(MANIFEST, CELL)["config"] == "nemotron-3-super-120b-a12b"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert len(MANIFEST["workloads"]) >= 12 <= len(MANIFEST["configs"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
