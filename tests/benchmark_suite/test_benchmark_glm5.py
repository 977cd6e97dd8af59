"""The ``glm-moe-dsa`` architecture and its cell
(``glm-5.batch-agentcontext``): the cell's path rehearsed on the CPU at tiny
widths (``index_topk`` 24 under contexts of 20-100, so that the indexer
selects) and judged ``correct`` against its own plain reference (the whole
``[S, S]`` index scores, a sort a query, one softmax over the selected set),
the float8 control and BOTH selection controls over the limit, ``counts.py``
against the numbers reckoned by hand in ISSUE 55, the configuration file
against the published config, ``program.py``'s table refusing a drifted
key, and each of the cell's fourteen readers on a recorded run and on a run
without samples.

The literal tables of the older files of this suite get this cell's entries
from ``tests/conftest.py`` (outside the benchmark's paths)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architecture, control, correctness, reference
from benchmark import manifest as mf
from benchmark.run import run_cell
from benchmark.weights import make_params, param_shapes
from test_benchmark_program_readers import quiet_run
from test_benchmark_rehearsal_cpu import check_line, rehearsal_manifest

MANIFEST = mf.load_manifest()
CELL = "glm-5.batch-agentcontext"
REHEARSAL = "tiny-glm5.rehearsal-closed-dsa"
CONF = mf.load_config(MANIFEST, "glm-5")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-glm5.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
INDEX = "kernel.index_scores_roofline_share.agentcontext"
CHUNK_CALLS = "kernel.latent_chunk_attention_mfu.agentcontext"
DECODE_CALL = "kernel.latent_decode_bw_share.agentcontext"
SELECT = "step.select_share.agentcontext"
COUNTER_READERS = ["dsa.selected_share.agentcontext",
                   "kv.index_share_of_pool.agentcontext",
                   "moe.held_row_share.agentcontext",
                   "engine.decode_occupancy.agentcontext",
                   "kv.preemptions.agentcontext",
                   "engine.sched_busy_share_window.agentcontext",
                   "engine.sync_state_ms_per_round.agentcontext",
                   "start.unattributed_s.agentcontext"]
READERS = [INDEX, CHUNK_CALLS, DECODE_CALL, SELECT,
           "step.prefill_mfu.agentcontext",
           "step.decode_weight_bw_share.agentcontext"] + COUNTER_READERS
with open("/opt/skills/guides/model-configs/architectures.jsonl") as _f:
    # config.json of zai-org/GLM-5, as the catalog beside the model-configs
    # guide gives it
    PUBLISHED = next(json.loads(line) for line in _f if '"GLM-5"' in line)


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 55, seconds=2.0,
                    trace=trace, allow_cpu=True)
    counters = set(COUNTER_READERS)   # what the CPU's trace can feed
    if trace == 2:
        assert line["correct"] is True and line["failed"] == 0
        assert set(line["metrics"]) >= {"serve_tokens_per_s",
                                        "setup_s"} | counters
    else:
        check_line(line, manifest, REHEARSAL, trace=bool(trace))
    if trace:
        value = {n: m["value"] for n, m in line["metrics"].items()}
        assert 0.0 < value["engine.decode_occupancy.agentcontext"] <= 100.0
        # prompts of 20-90 against 24 selected: a real selection
        assert 25.0 < value["dsa.selected_share.agentcontext"] < 95.0
        # 16 index values beside a 128-value row
        assert value["kv.index_share_of_pool.agentcontext"] \
            == pytest.approx(100 * 16 / 144)
        assert 0.0 < value["moe.held_row_share.agentcontext"] < 60.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_controls_are_over_the_limit_and_the_program_under():
    """One precision step down fails by each number, and so does a reference
    that selects the most recent keys or scores against index keys of zeros
    (what the comparison reads beside a program whose selection is wrong);
    the program's own int8 path cannot be a control (a latent pool refuses
    int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-dsa")
    ref = architecture.part(TINY, "reference")
    spec = {"sequences": [[100, 4]]}
    for seed in (5, 2**31 + 6):
        sides = control.serving_sides(TINY, traffic, seed,
                                      ["program", "reference_fp8"])
        assert correctness.judge(sides["program"], limits)[0], sides
        for name in limits:
            assert sides["reference_fp8"][name] > limits[name], (seed, name)
        params = make_params(TINY, seed, "bfloat16")
        toks = correctness.check_tokens(seed, 0, 104, TINY["vocab_size"])
        want = [correctness.reference_logits(params, toks, TINY, last=8)]
        for selection in ("recent", "keys_zeroed"):
            fn = jax.jit(lambda p, t, s=selection: ref.logits(
                p, t, TINY, last=8, selection=s))
            with jax.default_matmul_precision("highest"):
                got = [fn(params, jnp.asarray(toks))]
            numbers = correctness.compare_sides(got, want, spec, 32)
            for name in limits:
                assert numbers[name] > 2 * limits[name], (selection, numbers)
    with pytest.raises(ValueError, match="int8 KV"):
        control.serving_sides(TINY, traffic, 5, ["program_int8"])


@pytest.mark.parametrize("what", [
    "no indexer bias", "no index rope", "unweighted heads", "no relu",
    "no shared expert", "bias in the weight"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference whose index key has no bias, whose
    index heads are not rotated or not weighted, whose index products keep
    their sign, or whose experts lack the shared one: not the model, and the
    comparison says so."""
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 100, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    tree, conf = jax.tree.map(lambda a: a, params), TINY
    groups = [tree["dense_layers"], tree["layers"]]
    if what == "no indexer bias":
        for g in groups:
            g["attn"]["k_idx_bias"] = 20.0 * jnp.ones_like(
                g["attn"]["k_idx_bias"])
    elif what == "no index rope":
        conf = {**TINY, "rope_parameters": {"rope_theta": 1.0,
                                            "rope_type": "default"}}
    elif what == "unweighted heads":
        for g in groups:
            g["attn"]["w_idx"] = jnp.abs(g["attn"]["w_idx"])
    elif what == "no relu":
        for g in groups:
            g["attn"]["wq_idx"] = -g["attn"]["wq_idx"]
    elif what == "no shared expert":
        tree["layers"]["mlp"]["shared"] = jax.tree.map(
            jnp.zeros_like, tree["layers"]["mlp"]["shared"])
    else:
        tree["layers"]["mlp"]["router_bias"] = 40.0 * tree["layers"]["mlp"][
            "router_bias"]
    fn = jax.jit(lambda p, t: ref.logits(p, t, conf, last=64))
    with jax.default_matmul_precision("highest"):
        got = fn(tree, jnp.asarray(tokens))
    err = float(jnp.median(correctness.position_errors(got, own)))
    assert err > 1.5 * limit, (what, err)


def test_the_loss_is_the_logits_next_token_likelihood():
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 9, "float32")
    tokens = jnp.asarray(correctness.check_tokens(9, 0, 41,
                                                  TINY["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        logits = ref.logits(params, tokens[:-1], TINY)
        nll = ref.sequence_nll(params, tokens, TINY)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
    assert float(nll) == pytest.approx(float(want), rel=1e-5)


def test_the_reference_is_plain():
    """The selection by hand on a few scores; the reference imports nothing
    of the program, sorts, and absorbs nothing."""
    ref = architecture.part(TINY, "reference")
    scores = jnp.asarray([[3.0, -jnp.inf, -jnp.inf, -jnp.inf],
                          [1.0, 1.0, 1.0, -jnp.inf],
                          [0.5, 2.0, 0.5, 2.0]])
    assert np.asarray(ref.selected_keys(scores, 2)).tolist() == [
        [True, False, False, False], [True, True, False, False],
        [False, True, False, True]]
    q = jnp.asarray([[[1.0, 0.0], [0.0, 2.0]]])              # [Q=1, Hi=2, 2]
    w = jnp.asarray([[0.5, -1.0]])
    k = jnp.asarray([[1.0, 1.0], [-1.0, 3.0]])
    got = ref.index_scores(q, w, k, jnp.asarray([1]), reference.same)
    # key 0: 0.5 * relu(1) - relu(2) = -1.5; key 1: 0.5 * relu(-1) - relu(6)
    np.testing.assert_allclose(np.asarray(got), [[-1.5, -6.0]])
    with open(ref.__file__) as f:
        src = f.read().split('"""', 2)[2]
    assert "kubeflow_tpu" not in src and "pallas" not in src
    assert "argsort" in src and "approx_max_k" not in src
    assert "wkvb" in src and "latent_query" not in src


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d = 6144
    assert COUNTS.attention_params(CONF) == (
        d * 2048 + 2048 * 64 * 256 + d * 576 + 512 * 64 * 448
        + 64 * 256 * d + 2048 + 512) == 165_022_208
    assert COUNTS.indexer_params(CONF) == (
        2048 * 4096 + d * 128 + 256 + d * 32) == 9_371_904
    assert COUNTS.expert_params_one(CONF) == 3 * d * 2048 == 37_748_736
    assert COUNTS.router_params(CONF) == d * 256 + 256 == 1_573_120
    assert COUNTS.dense_mlp_params(CONF) == 3 * d * 12288 == 226_492_416
    assert COUNTS.dense_layer_params_total(CONF) == 400_898_816
    assert COUNTS.expert_layer_params_total(CONF) == 817_708_032
    assert COUNTS.expert_layer_params_published(CONF) == 9_877_404_672
    assert 2 * 19360 * d == 237_895_680
    total = COUNTS.params_total(CONF)
    assert total == 400_898_816 + 4 * 817_708_032 + 237_895_680 + d \
        == 3_909_632_768
    assert round(total * 2 / 1e9, 2) == 7.82
    # six layers, which the driver's count allows and the issue does not take
    assert round(COUNTS.params_total({**CONF, "num_hidden_layers": 6})
                 * 2 / 1e9, 2) == 9.45
    # the whole published model: 744 B
    whole = COUNTS.params_total({
        **CONF, "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880})
    assert round(whole / 1e9, 1) == 743.9
    # a token: 1280 + 256 bytes a layer; the cell's 1184 pages of 128 (1568
    # at the sizes ISSUE 55 named first: 1.54 GB)
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 5 * 1536 == 7680
    assert round(1184 * 128 * 7680 / 1e9, 2) == 1.16
    assert round(1568 * 128 * 7680 / 1e9, 2) == 1.54
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs_here():
    d = 6144
    # half a held expert a token beside the shared one
    expert_layer = 165_022_208 - 2560 + 9_371_904 - 256 + d * 256 \
        + 1.5 * 37_748_736
    assert COUNTS.expert_layer_matmul_params_active(CONF) == expert_layer
    dense = 165_022_208 - 2560 + 9_371_904 - 256 + 226_492_416
    assert COUNTS.dense_layer_matmul_params(CONF) == dense
    per_token = COUNTS.layers_matmul_params_active(CONF)
    assert per_token == dense + 4 * expert_layer
    assert round(2 * per_token / 1e9, 2) == 2.66
    # the indexer: 8192 a pair a query can see; attention: a pair SELECTED
    assert COUNTS.index_scores_flops(CONF, 1) == 2 * 32 * 128 == 8192
    assert COUNTS.attention_flops_selected(CONF, 1) == 2 * 64 * 512
    assert COUNTS.latent_chunk_attention_flops(CONF, 1) \
        == 2 * 64 * (576 + 512) == 139_264
    assert COUNTS.visible_pairs(512, 1024) == 512 * 1024 + 512 * 513 / 2
    for n, start in ((512, 0), (512, 1800), (512, 4096), (100, 2000)):
        assert COUNTS.selected_pairs(CONF, n, start) == sum(
            min(2048, start + i + 1) for i in range(n)), (n, start)
    n = 6000
    want = (2.0 * per_token * n
            + 5 * (8192 * n * (n + 1) / 2
                   + 2 * 64 * 512 * COUNTS.selected_pairs(CONF, n))
            + 2.0 * d * 19360)
    assert COUNTS.prefill_flops(CONF, n) == pytest.approx(want, rel=1e-12)
    # a step's weights: everything but the embedding, of the 64 held
    # experts those that some live stream chose
    fixed = 3_909_632_768 - 4 * 16 * 37_748_736 - 19360 * d
    assert COUNTS.decode_weight_bytes(CONF, 2, 0) == 2.0 * fixed
    touched = 1 - (1 - 8 / 256) ** 16
    assert COUNTS.decode_weight_bytes(CONF, 2, 16) == pytest.approx(
        2.0 * (fixed + touched * 4 * 16 * 37_748_736))
    # the kernels' needed bytes: the SELECTED rows as held; the index keys
    assert COUNTS.latent_decode_bytes(CONF, 2048, 2) == 2048 * 1280
    assert COUNTS.index_scores_bytes(CONF, 12288, 2) == 12288 * 256


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "glm-5")
    reduced = {"num_hidden_layers": (78, 5), "first_k_dense_replace": (3, 1),
               "n_routed_experts": (256, 16), "vocab_size": (154880, 19360),
               "num_nextn_predict_layers": (1, 0)}
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) \
        == sorted(reduced)
    assert entry["source"] == CONF["source"] == PUBLISHED["source_url"]
    for key, value in PUBLISHED["config"].items():
        if key in reduced:
            assert (CONF["reduced"][key]["from"], CONF["reduced"][key]["to"],
                    CONF[key]) == (value, reduced[key][1], reduced[key][1])
            assert value == reduced[key][0]
        else:
            assert key in CONF and CONF[key] == value, key
    assert (CONF["n_routed_experts_published"], CONF["expert_offset"],
            CONF["vocab_size_published"]) == (256, 0, 154880)
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("indexer_key_norm", "indexer_queries", "indexer_rope",
                 "indexer_weights", "no_hadamard_no_fp8", "selection",
                 "router_bias", "head_dim", "weights"):
        assert item in CONF["assumed"]
    assert "BFLOAT16" in CONF["assumed"]["no_hadamard_no_fp8"]
    assert "one chip of the 16 that share EACH LAYER" in CONF["deployment"]
    assert "ckv" in CONF["cache"] and "idx" in CONF["cache"]
    assert CONF["architecture"] == "glm-moe-dsa" and CONF["chips"] == 1
    assert CONF["correctness"]["sequences"][1][0] < CONF["index_topk"]
    longest = max(plen + n for plen, n in CONF["correctness"]["sequences"])
    assert longest <= CONF["program"]["overrides"]["max_seq_len"] == 9472
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")
    # no width is reduced
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key in (
            "vocab_size",)


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    entry = mf.config_entry(MANIFEST, "glm-5")
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
    assert (cfg.n_layers, cfg.hidden, cfg.n_heads, cfg.mlp_dim,
            cfg.vocab_size, cfg.leading_dense_layers) \
        == (5, 6144, 64, 12288, 19360, 1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (2048, 512, 192, 64, 256)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) \
        == (32, 128, 2048)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.shared_experts, cfg.expert_mlp_dim) == (256, 16, 8, 1, 2048)
    assert cfg.kinds == ("attention",) * 5 and not cfg.tie_embeddings
    for key, other in (("index_topk", 1024), ("index_n_heads", 16),
                       ("index_head_dim", 64), ("hidden_size", 4096),
                       ("num_hidden_layers", 4),
                       ("first_k_dense_replace", 3),
                       ("num_attention_heads", 32), ("q_lora_rank", 1536),
                       ("kv_lora_rank", 256), ("qk_nope_head_dim", 128),
                       ("qk_rope_head_dim", 32), ("v_head_dim", 128),
                       ("qk_head_dim", 192), ("head_dim", 128),
                       ("intermediate_size", 10240),
                       ("moe_intermediate_size", 1536),
                       ("n_routed_experts", 32),
                       ("n_routed_experts_published", 128),
                       ("expert_offset", 16), ("n_shared_experts", 2),
                       ("num_experts_per_tok", 4), ("norm_topk_prob", False),
                       ("routed_scaling_factor", 1.0),
                       ("scoring_func", "softmax"), ("topk_method", "greedy"),
                       ("n_group", 8), ("topk_group", 4),
                       ("moe_layer_freq", 2), ("vocab_size", 154880),
                       ("attention_bias", True), ("rms_norm_eps", 1e-6),
                       ("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="rope_parameters"):
        program.program_config({**CONF, "rope_parameters": {
            "rope_theta": 10000, "rope_type": "default"}})
    with pytest.raises(mf.ManifestError, match="glm-moe-dsa is"):
        program.program_config(CONF, moe_impl="dense")


def test_the_seeded_tree_is_the_programs():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert got["embed"].shape == (19360, 6144)
    assert got["lm_head"].shape == (6144, 19360)
    attn = got["layers"]["attn"]
    assert attn["wq_idx"].shape == (4, 32 * 128, 2048)
    assert attn["wk_idx"].shape == (4, 6144, 128)
    assert attn["w_idx"].shape == (4, 6144, 32)
    assert got["layers"]["mlp"]["router"].shape == (4, 6144, 256)
    assert got["layers"]["mlp"]["gate"].shape == (4, 16, 6144, 2048)
    # the stratified bias: every seed the same multiset, each block of the
    # held width one value of each stratum
    a, b = (np.asarray(make_params(TINY, s, "float32")["layers"]["mlp"][
        "router_bias"]) for s in (3, 4))
    assert np.array_equal(np.sort(a, axis=1), np.sort(b, axis=1))
    assert not np.array_equal(a, b)
    tiny = make_params(TINY, 3, "float32")
    for leaf, fan in ((tiny["layers"]["attn"]["wq_idx"], 24),
                      (tiny["layers"]["attn"]["wk_idx"], 64),
                      (tiny["layers"]["attn"]["w_idx"], 64),
                      (tiny["layers"]["attn"]["wqb"], 24)):
        std = float(np.std(np.asarray(leaf))) * fan ** 0.5
        assert 0.85 < std < 1.15, (fan, std)


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 16
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    # the engine's own prefill concurrency: the block names what ISSUE 55
    # names and no more
    assert set(e) == {"paged", "max_batch_size", "max_seq_len", "page_size",
                      "max_pages", "chunked_prefill_tokens", "decode_steps",
                      "prefill_interleave_steps"}
    assert BatchingSpec(**e).max_concurrent_prefills \
        == BatchingSpec().max_concurrent_prefills == 2
    assert traffic["pool"] == 76
    assert traffic["shared_prefix_tokens"] == 0
    # ISSUE 55's one sanctioned fallback (named: 4096-12288)
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 3072,
                                     "max": 9216}
    assert traffic["output_len"] == {"dist": "uniform", "min": 128,
                                     "max": 256}
    mpp = e["max_seq_len"] // e["page_size"]
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert mpp == 74 and longest <= e["max_seq_len"] == 9472
    assert e["max_pages"] == 16 * mpp == 1184       # 16 whole contexts
    # two queries in three of a prompt's see more keys than are selected
    mean = (traffic["prompt_len"]["min"] + traffic["prompt_len"]["max"]) / 2
    assert 1 - CONF["index_topk"] / mean == pytest.approx(2 / 3)
    need = required_programs(traffic, BatchingSpec(**e))
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {f"paged_decode[{k},greedy]" for k in (1,)} <= need
    assert {f"paged_chunk_prefill[1x512,{b}]"
            for b in (4, 8, 16, 32, 64, 74)} \
        == {n for n in need if n.startswith("paged_chunk_prefill")}


# -- the fourteen readers -------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 1500 decode steps over 9 live streams and 1400 chunk
    programs; 3 traced seconds holding two chunk programs (40 and 44 ms) that
    carried a chunk at 4096 and one at 8192 with a step of 9 streams each,
    and one decode-only step (12 ms): in each chunk program five calls of
    each kernel for the chunk (index 0.3 ms, select 0.3, attention 4) and
    five of each for the riding step (0.1, 0.05, 0.3)."""
    run = quiet_run("any.agentcontext")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(
            slots=16, kv_pool_bytes=1_541_406_720,
            index_pool_bytes=256_901_120)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=1500, decode_tokens_emitted=13_500,
        prefill_programs_dispatched=1400, prefill_chunks_dispatched=1400,
        prefill_tokens_dispatched=700_000, preemptions=0,
        sched_host_busy_sum_s=4.0, dsa_keys_visible=5_000_000,
        dsa_keys_selected=2_000_000, expert_rows_routed=64_000,
        expert_rows_held=4_000, decode_rounds=1500,
        sched_sync_state_sum_s=0.75)
    ctx = [COUNTS.visible_pairs(512, s) for s in (4096, 8192)]
    spans = []
    for i, (at, pairs) in enumerate(zip((0.0, 0.1), ctx)):
        spans += [
            ["engine.prefill_dispatch", at, 0.002,
             {"slot": i, "pos": 4096 * (i + 1), "chunks": 1,
              "context": int(pairs), "selected": 512 * 2048}],
            ["engine.decode_dispatch", at + 0.0005, 0.001,
             {"round": i, "k_steps": 1, "live": 9, "context": 9 * 6000,
              "selected": 9 * 2048}]]
    spans.append(["engine.decode_dispatch", 0.2, 0.001,
                  {"round": 2, "k_steps": 1, "live": 9, "context": 9 * 6000,
                   "selected": 9 * 2048}])
    run["host_spans"].append(spans)
    ops = []
    for at in (0.0, 0.1):
        for i in range(5):
            t = at + 0.008 * i
            ops += [[f"%paged_index_scores.{i} = custom-call", t, 0.0003],
                    [f"%dsa_select.{i} = custom-call", t + 0.0004, 0.0003],
                    [f"%paged_latent_chunk_attention.{i} = custom-call",
                     t + 0.0008, 0.004],
                    [f"%paged_index_scores.{5 + i} = custom-call",
                     t + 0.005, 0.0001],
                    [f"%dsa_select.{5 + i} = custom-call", t + 0.0052,
                     0.00005],
                    [f"%paged_latent_decode_attention.{i} = custom-call",
                     t + 0.0053, 0.0003]]
    for i in range(5):
        t = 0.2 + 0.001 * i
        ops += [[f"%paged_index_scores.{i} = custom-call", t, 0.0001],
                [f"%dsa_select.{i} = custom-call", t + 0.0002, 0.00005],
                [f"%paged_latent_decode_attention.{i} = custom-call",
                 t + 0.0003, 0.0003]]
    # the op that takes a kernel's result names it too, and is no call
    ops.append(["%slice.7 = s32[98,16,128] slice(s32[1,98,16,128] "
                "%dsa_select.1)", 0.21, 1e-7])
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.040],
                    ["jit__lambda(7)", 0.1, 0.044],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.012]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.03],
                      ["%fusion.12 = fusion", 0.1, 0.03]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0, "values": {"setup_s": 200.0},
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [8192, 6000, 12000]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 2.5e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # the indexer: two chunks' visible pairs on the matrix unit, three steps'
    # keys on the bus, a layer's worth x 5, over 25 calls' time
    pairs = sum(COUNTS.visible_pairs(512, s) for s in (4096, 8192))
    floor = 5 * (pairs * 8192 / 197e12 + 3 * 9 * 6000 * 256 / 819e9)
    assert read[INDEX] == pytest.approx(
        100 * floor / (10 * 0.0003 + 15 * 0.0001))
    assert 0 < read[INDEX] < 100
    # chunk attention: the SELECTED pairs, absorbed, over ten calls of 4 ms
    assert read[CHUNK_CALLS] == pytest.approx(
        100 * 5 * 2 * 512 * 2048 * 139_264 / (10 * 0.004 * 197e12))
    assert 15 < read[CHUNK_CALLS] < 25
    # a decode call: 9 streams' 2048 selected rows of 1280 B in 0.3 ms
    assert read[DECODE_CALL] == pytest.approx(
        100 * 9 * 2048 * 1280 / 819e9 / 0.0003)
    # the selection: 25 calls of the kernel over the busy time
    busy = 2 * 0.03 + 0.012 - 0.002        # the fusions, the step's ops
    assert read[SELECT] == pytest.approx(
        100 * (10 * 0.0003 + 15 * 0.00005) / read_busy(run), rel=1e-9)
    assert 0 < read[SELECT] < 100 and busy > 0
    # two programs of one chunk of 2.5 TFLOP needed over 84 ms
    assert read["step.prefill_mfu.agentcontext"] == pytest.approx(
        100 * 2 * 2.5e12 / (0.084 * 197e12))
    # the one decode-ONLY step (five calls of the latent decode kernel inside
    # a decode program; the steps inside ``jit__lambda`` ride a chunk): the
    # weights nine live streams touch over 12 ms of the bus
    assert read["step.decode_weight_bw_share.agentcontext"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2, 9.0) / 819e9 / 0.012)
    assert 30 < read["step.decode_weight_bw_share.agentcontext"] < 100
    assert read["dsa.selected_share.agentcontext"] == 40.0
    assert read["kv.index_share_of_pool.agentcontext"] == pytest.approx(
        100 / 6)
    assert read["moe.held_row_share.agentcontext"] == 6.25
    assert read["engine.decode_occupancy.agentcontext"] == pytest.approx(
        100 * 13_500 / (1500 * 16))
    assert read["kv.preemptions.agentcontext"] == 0.0
    assert read["engine.sched_busy_share_window.agentcontext"] == 10.0
    assert read["engine.sync_state_ms_per_round.agentcontext"] == 0.5
    assert read["start.unattributed_s.agentcontext"] == 200.0 - 6.0


def read_busy(run: dict) -> float:
    from benchmark import tracing

    return tracing.busy_s(run["trace"])


@pytest.mark.parametrize("name", READERS)
def test_reader_on_runs_without_samples_and_without_a_source(name):
    read = mf.load_layer_metric(name).read
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == ("setup_s" if name.startswith("start.")
                              else "serve_tokens_per_s")
    assert {k: entry[k] for k in mf.load_layer_metric(name).DECLARATION} \
        == mf.load_layer_metric(name).DECLARATION
    # counters at rest, a trace that holds none of the programs: the stated
    # number (the pool's share is a constant of the engine)
    quiet = {**recorded_run(), **quiet_run("any.agentcontext")}
    quiet["values"] = {"setup_s": 30.0}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = {"kv.index_share_of_pool.agentcontext": 12.5,
              "start.unattributed_s.agentcontext": 24.0}.get(name, 0.0)
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the PARENT's program with these files dropped in (it cannot build this
    # model; an engine without the counters, spans that say nothing of a
    # selection): nothing or a number, never an exception
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        for key in ("dsa_keys_visible", "dsa_keys_selected",
                    "index_pool_bytes"):
            part["engine"].pop(key, None)
    for span in parent["host_spans"][-1]:
        span[3].pop("selected", None)
        if span[0] == "engine.prefill_dispatch":
            span[3].pop("context", None)
    if name in (INDEX, CHUNK_CALLS, DECODE_CALL,
                "dsa.selected_share.agentcontext",
                "kv.index_share_of_pool.agentcontext"):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: calls that took exactly their
    needed work's time."""
    run = recorded_run()
    pairs = sum(COUNTS.visible_pairs(512, s) for s in (4096, 8192))
    total_index = 5 * (pairs * 8192 / 197e12 + 3 * 9 * 6000 * 256 / 819e9)
    floor = {"%paged_index_scores": total_index / 25,
             "%paged_latent_chunk_attention":
                 512 * 2048 * 139_264 / 197e12,
             "%paged_latent_decode_attention": 9 * 2048 * 1280 / 819e9}
    device = run["trace"]["devices"][0]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in (INDEX, CHUNK_CALLS, DECODE_CALL):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-dsa")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"dsa_keys_visible", "dsa_keys_selected", "index_pool_bytes",
            "kv_pool_bytes", "kv_bytes_per_token", "expert_rows_routed",
            "expert_rows_held", "prefill_chunks_dispatched",
            "prefill_programs_dispatched", "decode_steps_dispatched",
            "decode_tokens_emitted", "preemptions", "slots", "decode_rounds",
            "sched_host_busy_sum_s", "sched_sync_state_sum_s"} \
        <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2)
    assert counters["kv_pool_bytes"] == engine._num_pages \
        * engine.page_size * counters["kv_bytes_per_token"]
    assert counters["index_pool_bytes"] * 9 == counters["kv_pool_bytes"]


def test_what_pr_55_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/glm-5.json",
                 "benchmark/configs/rehearsal-tiny-glm5.json",
                 "benchmark/traffic/batch-agentcontext.json",
                 "benchmark/traffic/rehearsal-closed-dsa.json"]
                + [f"benchmark/architectures/glm-moe-dsa/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(READERS[0])
    assert sorted(names[at:at + len(READERS)]) == sorted(READERS)
    assert all(n.split(".")[-1] != "agentcontext" for n in names[:at])
    assert mf.cell(MANIFEST, CELL)["config"] == "glm-5"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert len(MANIFEST["workloads"]) >= 10 <= len(MANIFEST["configs"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
