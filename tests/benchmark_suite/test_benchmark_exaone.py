"""The ``exaone-moe`` architecture and its cell
(``k-exaone-236b-a23b.batch-mixedlength``): the cell's path rehearsed on the
CPU at tiny widths and judged ``correct`` against its own plain reference
(through ``engine_logits``' calls as they stand: ONE page-table row of
``arange`` and no slot, from which a window layer finds its ring), the float8
control over its limit, a reference of other equations far over it (the
window ignored, the global layer rotated, the share ignored), ``counts.py``
against the numbers reckoned by hand in ISSUE 40, the configuration file
against the published config, and each of the cell's ten readers on a
recorded run and on a run without samples.

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
CELL = "k-exaone-236b-a23b.batch-mixedlength"
REHEARSAL = "tiny-exaone.rehearsal-closed-ring"
CONF = mf.load_config(MANIFEST, "k-exaone-236b-a23b")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-exaone.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
GLOBAL_CALL = "kernel.paged_decode_attention_bw_share.mixedlength"
WINDOW_CALL = "kernel.paged_window_decode_attention_bw_share.mixedlength"
CHUNK_CALLS = "kernel.paged_chunk_attention_mfu.mixedlength"
COUNTER_READERS = ["kv.window_share_of_pool.mixedlength",
                   "moe.held_row_share.mixedlength",
                   "engine.decode_occupancy.mixedlength",
                   "kv.preemptions.mixedlength",
                   "engine.sched_busy_share_window.mixedlength"]
READERS = ["step.prefill_mfu.mixedlength",
           "step.decode_weight_bw_share.mixedlength", GLOBAL_CALL,
           WINDOW_CALL, CHUNK_CALLS] + COUNTER_READERS
# config.json of LGAI-EXAONE/K-EXAONE-236B-A23B, as the catalog beside the
# model-configs guide gives it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": ["full_attention" if i % 4 == 3 else "sliding_attention"
                    for i in range(48)],
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [0 if i % 4 == 3 else 128 for i in range(48)],
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}


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
        assert 0.0 < value["engine.decode_occupancy.mixedlength"] <= 100.0
        assert value["kv.preemptions.mixedlength"] >= 0.0
        # four window layers' rings (2 slots x 5 pages) beside one global
        # layer's 16 pages
        assert value["kv.window_share_of_pool.mixedlength"] == pytest.approx(
            100 * 4 * 10 / (4 * 10 + 16))
        # 4 of 16 experts held: a quarter of the routed rows, more or less
        assert 10.0 < value["moe.held_row_share.mixedlength"] < 45.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (window layers refuse int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-ring")
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


@pytest.mark.parametrize("what", ["window ignored", "another rope base",
                                  "no expert bias", "share ignored"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference whose window layers see every key,
    whose window layers rotate at another base, whose choice drops the bias,
    or which sums another four experts than the ones held: not the model,
    and the comparison says so (a context of 100 tokens against a window of
    24)."""
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 100, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    other_conf, other = TINY, params
    if what == "window ignored":
        other_conf = {**TINY, "sliding_window": 4096}
    elif what == "another rope base":
        other_conf = {**TINY, "rope_parameters": {
            "rope_theta": 100, "rope_type": "default"}}
    elif what == "no expert bias":
        other = {**params, "layers": {**params["layers"], "mlp": {
            **params["layers"]["mlp"], "router_bias": jnp.zeros_like(
                params["layers"]["mlp"]["router_bias"])}}}
    else:
        other_conf = {**TINY, "expert_offset": 8}
    got = correctness.reference_logits(other, tokens, other_conf, last=64)
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


def test_the_references_window_is_the_plain_mask():
    """Blocks of queries against the keys a block can see are the whole
    masked score matrix."""
    ref = architecture.part(TINY, "reference")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (64, 4, 16))
    k = jax.random.normal(ks[1], (64, 2, 16))
    v = jax.random.normal(ks[2], (64, 2, 16))
    got = ref.window_attention(q, k, v, 16, 24)
    scores = jnp.einsum("qngd,knd->ngqk", q.reshape(64, 2, 2, 16), k) / 4.0
    i, j = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
    scores = jnp.where((j <= i) & (j > i - 24), scores, -jnp.inf)
    want = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, -1), v)
    assert float(jnp.abs(got - want.reshape(64, 4, 16)).max()) < 1e-5


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, v = 6144, 19200
    attention = d * (8192 + 1024 + 1024) + 8192 * d
    assert attention == COUNTS.attention_matmul_params(CONF) == 113_246_208
    assert COUNTS.attention_params(CONF) == attention + 256
    expert = 3 * d * 2048
    assert expert == COUNTS.expert_params_one(CONF) == 37_748_736
    router = d * 128
    dense_layer = attention + 256 + 3 * d * 18432 + 2 * d
    assert dense_layer == 452_997_376                   # 452.98 M + norms
    expert_layer = attention + 256 + router + 128 + 17 * expert + 2 * d
    assert expert_layer == 755_773_824                  # 16 held + shared
    whole_layer = expert_layer + 112 * expert
    assert round(whole_layer * 2 / 1e9, 1) == 10.0      # no chip holds one
    assert 2 * v * d == 235_929_600                     # untied, an eighth
    total = dense_layer + 4 * expert_layer + 2 * v * d + d
    assert total == COUNTS.params_total(CONF) == 3_712_028_416  # 3.712 B
    assert round(total * 2 / 2**30, 2) == 6.91
    # the global layer holds 2 x 8 x 128 values a token; a window layer a
    # ring of 6 pages a sequence
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 4096
    assert COUNTS.window_ring_bytes_per_sequence(CONF, 2, 6, 128) \
        == 4 * 6 * 128 * 4096 == 12_582_912
    # the cell's pool: 2304 pages in the global layer, 192 in each of four
    # window layers; held alike by all five layers it would be 6.0 GB
    assert 2304 * 128 * 4096 == 1_207_959_552
    assert 32 * 12_582_912 == 402_653_184
    assert round(5 * 2304 * 128 * 4096 / 1e9, 1) == 6.0
    # the program counts the same parameters
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs_here():
    d, v = 6144, 19200
    attention, expert = 113_246_208, 37_748_736
    assert COUNTS.experts_met(CONF) == 1.0              # 8 x 16 / 128
    matmuls = 5 * attention + 3 * d * 18432 + 4 * (d * 128 + 2 * expert)
    assert COUNTS.layers_matmul_params_active(CONF) == matmuls \
        == 1_211_105_280
    # a window layer's query scores at most 128 keys, a global layer's all
    assert COUNTS.window_pairs(512, 0, 128) == sum(
        min(t + 1, 128) for t in range(512))
    assert COUNTS.window_pairs(100, 50, 128) == sum(
        min(t + 1, 128) for t in range(50, 150))
    assert COUNTS.window_pairs(512, 4096, 128) == 512 * 128
    assert COUNTS.causal_pairs(512, 4096) == 512 * 4096 + 512 * 513 / 2
    n = 2048
    pairs = n * (n + 1) / 2 + 4 * COUNTS.window_pairs(n, 0, 128)
    assert COUNTS.attention_flops(CONF, n) == 4.0 * 128 * 64 * pairs
    want = 2.0 * matmuls * n + 4.0 * 128 * 64 * pairs + 2.0 * d * v
    assert COUNTS.prefill_flops(CONF, n) == want       # the head ONCE
    assert 2.4e9 < want / n < 2.6e9
    assert COUNTS.chunk_attention_flops(CONF, n) \
        == COUNTS.attention_flops(CONF, n)
    assert COUNTS.train_flops_per_token(CONF, 4096) == (
        6.0 * (matmuls + d * v)
        + 3.0 * COUNTS.attention_flops(CONF, 4096) / 4096)
    # a step's weights: everything held but the embedding, the held experts
    # by the share of them that some live stream chose
    fixed = 3_712_028_416 - 4 * 16 * expert - v * d
    assert COUNTS.decode_weight_bytes(CONF, 2) == pytest.approx(
        2.0 * (fixed + 4 * 16 * expert * 8 / 128))
    at32 = COUNTS.decode_weight_bytes(CONF, 2, 32)
    assert at32 == pytest.approx(
        2.0 * (fixed + 4 * 16 * expert * (1 - (120 / 128) ** 32)))
    assert 6.5e9 < at32 < COUNTS.resident_weight_bytes(CONF, 2) - 2 * v * d
    assert COUNTS.resident_weight_bytes(CONF, 2) == 2.0 * 3_712_028_416
    assert COUNTS.decode_attention_bytes(CONF, 1000, 2) == 1000 * 4096


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "k-exaone-236b-a23b")
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert entry["source"] == CONF["source"]
    for key, value in PUBLISHED.items():
        if key in CONF["reduced"]:
            assert CONF["reduced"][key]["from"] == value
            assert CONF["reduced"][key]["to"] == CONF[key] != value
        else:
            assert CONF[key] == value, key
    # the router keeps every published output; the chip holds 16 experts
    assert CONF["num_experts_routed"] == PUBLISHED["num_experts"] == 128
    assert (CONF["num_experts"], CONF["expert_offset"]) == (16, 0)
    assert CONF["vocab_size_published"] == PUBLISHED["vocab_size"]
    # the layers held are published layers 0-4: the dense layer and one
    # whole period of the pattern over four expert layers
    assert CONF["layer_types_held"] == PUBLISHED["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert len(CONF["layer_types_held"]) == CONF["num_hidden_layers"]
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] >= 4
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("qk_norm", "rope_on_window_layers_only", "norm_placement",
                 "router_bias"):
        assert item in CONF["assumed"]
    assert "8" in CONF["deployment"] and "16 of the 128" in CONF["deployment"]
    assert CONF["architecture"] == "exaone-moe" and CONF["chips"] == 1
    assert any(plen + n == 9216 for plen, n
               in CONF["correctness"]["sequences"])
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    """What test_benchmark_manifest.py asks of every configuration, of this
    one (that test is handed the manifest without it, tests/conftest.py:
    its pattern for a width takes every key that ends in ``_size``, and the
    vocabulary is no width: not a hidden, intermediate, latent, state or
    projection size, a head size, an expansion factor or the experts a
    token; ISSUE 40 names ``vocab_size`` among the keys reduced)."""
    import re

    entry = mf.config_entry(MANIFEST, "k-exaone-236b-a23b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["reduced"]) <= 16
    conf = mf.load_json(entry["file"])
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    width = re.compile(
        r"(_dim|_rank)$|^(hidden|intermediate|moe_intermediate)_size$"
        r"|^num_(attention|key_value)_heads$|^num_experts_per_tok$"
        r"|^sliding_window$")
    for key in entry["reduced"]:
        assert not width.search(key), key
        assert conf["reduced"][key]["to"] == conf[key]
    cell = mf.cell(MANIFEST, CELL)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == conf["chips"] == 1 and len(cell["why"]) <= 200
    assert mf.load_traffic(cell["traffic"])["kind"] == "closed_loop"
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window"):
        assert conf[key] == PUBLISHED[key]


def test_the_programs_config_is_held_against_the_file():
    program = architecture.part(CONF, "program")
    cfg = program.program_config(CONF)
    assert (cfg.n_layers, cfg.leading_dense_layers, cfg.num_experts,
            cfg.experts_held, cfg.shared_experts, cfg.experts_per_token) \
        == (5, 1, 128, 16, 1, 8)
    assert cfg.kinds == ("window",) * 3 + ("attention", "window")
    assert cfg.qk_norm and cfg.rope_window_only and cfg.head_dim == 128
    assert (cfg.attn_window, cfg.router_scale, cfg.vocab_size) \
        == (128, 2.5, 19200)
    assert not cfg.tie_embeddings and not cfg.kv_heads_packed
    for key, other in (("num_experts", 32), ("num_experts_routed", 64),
                       ("expert_offset", 16), ("first_k_dense_replace", 2),
                       ("num_hidden_layers", 6), ("sliding_window", 256),
                       ("routed_scaling_factor", 1.0),
                       ("scoring_func", "softmax"),
                       ("norm_topk_prob", False),
                       ("num_key_value_heads", 4), ("head_dim", 64),
                       ("num_shared_experts", 0),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1), ("n_group", 8),
                       ("vocab_size", 153600),
                       ("layer_types_held", ["full_attention"] * 5)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="rope_parameters"):
        program.program_config({**CONF, "rope_parameters": {
            "rope_theta": 10000, "rope_type": "default"}})
    # a config object that disagrees with the file is refused as well
    with pytest.raises(mf.ManifestError, match="sliding_window"):
        program.program_config(CONF, attn_window=64)
    with pytest.raises(mf.ManifestError, match="exaone-moe is"):
        program.program_config(CONF, rope_window_only=False)


def test_the_seeded_tree_is_the_programs_at_the_published_widths():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert got["lm_head"].shape == (6144, 19200)
    assert got["layers"]["mlp"]["gate"].shape == (3, 16, 6144, 2048)
    assert got["layers"]["mlp"]["router"].shape == (3, 6144, 128)
    assert got["layers_rest"]["mlp"]["router_bias"].shape == (1, 128)
    assert got["layers"]["mlp"]["router_bias"].dtype == "float32"
    tiny = make_params(TINY, 3, "bfloat16")
    assert float(abs(tiny["layers"]["mlp"]["router_bias"]).min()) > 0
    # the bias is the same multiset in every layer and for every seed, and
    # every chip's block of held experts has one value of each stratum
    bias = np.asarray(got_bias(CONF, 7))
    other = np.asarray(got_bias(CONF, 8))
    assert bias.shape == (4, 128) and 0.045 < bias.std() < 0.055
    assert (bias != other).mean() > 0.9
    np.testing.assert_array_equal(np.sort(bias, axis=1), np.sort(other, 1))
    ranks = np.argsort(np.argsort(bias, axis=1), axis=1) // 8   # stratum
    for block in ranks.reshape(4, 8, 16):
        assert all(sorted(chip) == list(range(16)) for chip in block)


def got_bias(conf, seed):
    weights = architecture.part(conf, "weights")
    return weights.balanced_bias(jax.random.PRNGKey(seed), 4,
                                 conf["num_experts_routed"],
                                 conf["num_experts"])


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.paged import context_bucket

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 32
    assert e["enable_prefix_caching"] is False      # the cell shares nothing
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    # a window completes about ``pool`` requests, so every seed serves the
    # same multiset of sizes in another order (PERF.md, PR 32's refusal)
    assert 150 <= traffic["pool"] <= 300
    mpp = e["max_seq_len"] // e["page_size"]
    assert mpp == 72 and e["max_pages"] == 32 * mpp        # no preemption
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest < e["max_seq_len"]
    need = required_programs(traffic, BatchingSpec(**e))
    first = traffic["warmup"][0][0][0]
    steps = {e["decode_steps"],
             min(e["decode_steps"], e["prefill_interleave_steps"])}
    reached = {f"paged_chunk_prefill[1x512,"
               f"{context_bucket(pos, 512, 128, mpp)}]"
               for pos in range(0, first, 512)} \
        | {f"paged_decode[{k},greedy]" for k in steps}
    # The warm-up reaches every program the traffic can and nothing else but,
    # at most, the whole table, the engine's longest context, which
    # ``correct`` drives once (9208 tokens; prompts that stop at 6144 end in
    # the bucket of 64 pages, prompts up to 8192 reach the table's end).
    assert reached - need <= {f"paged_chunk_prefill[1x512,{mpp}]"}
    assert need <= reached


# -- the ten readers ---------------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 4000 decode steps over 30 live streams, 500 chunk
    programs that carried 900 chunks, 1.6 M expert rows routed and 0.2 M
    held; 3 traced seconds holding two chunk programs (40 and 60 ms), a
    cache copy, two decode programs of one step each (10 ms) over 30 streams
    at 2000 and 3000 context rows a stream, in each one call of the global
    layer's kernel (1 ms) and four of the window layers' (0.1 ms), and in
    each chunk program one global chunk call (4 ms) and four window calls
    (1 ms)."""
    run = quiet_run("any.mixedlength")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(slots=32, kv_window_pool_bytes=402_653_184,
                              kv_pool_bytes=1_610_612_736)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=4000, decode_tokens_emitted=120_000,
        prefill_programs_dispatched=500, prefill_chunks_dispatched=900,
        preemptions=2, expert_rows_routed=1_600_000,
        expert_rows_held=200_000, sched_host_busy_sum_s=10.0)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 30, "context": 30 * 2000,
          "window_context": 30 * 128}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 30, "context": 30 * 3000,
          "window_context": 30 * 128}]])
    ops = []
    for step in (0.2, 0.25):
        ops.append(["%paged_decode_attention.3 = custom-call", step, 0.001])
        ops += [[f"%paged_window_decode_attention.{i} = custom-call",
                 step + 0.002 + 0.0002 * i, 0.0001] for i in range(4)]
        # the op that takes a kernel's result names it too, and is no call
        ops.append(["%multiply.7 = bf16[32,64,128] multiply(bf16[32,64,128] "
                    "%paged_decode_attention.3, %broadcast.3)", step + 0.0011,
                    1e-7])
    for chunk in (0.0, 0.1):
        ops.append(["%paged_chunk_attention.9 = custom-call", chunk, 0.004])
        ops += [[f"%paged_window_chunk_attention.{i} = custom-call",
                 chunk + 0.005 + 0.001 * i, 0.001] for i in range(4)]
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.040],
                    ["jit__lambda(7)", 0.1, 0.060],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.010],
                    ["jit__paged_decode_fn(3)", 0.25, 0.010]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.03]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0,
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [2048, 512, 4096]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 1.2e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # a step over 30 live streams reads 6.56 GB of held weights; 10 ms
    assert read["step.decode_weight_bw_share.mixedlength"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2, 30) / 819e9 / 0.010)
    assert 75 < read["step.decode_weight_bw_share.mixedlength"] < 85
    # two programs of 1.8 chunks of 1.2 TFLOP needed over 100 ms
    assert read["step.prefill_mfu.mixedlength"] == pytest.approx(
        100 * 2 * 1.8 * 1.2e12 / (0.100 * 197e12))
    # the global call: 75k context rows a step x 4096 B in 1 ms; the window
    # calls: 3840 rows a step in 0.1 ms
    assert read[GLOBAL_CALL] == pytest.approx(
        100 * 75_000 * 4096 / 819e9 / 0.001)
    assert read[WINDOW_CALL] == pytest.approx(
        100 * 3840 * 4096 / 819e9 / 0.0001)
    assert 0 < read[WINDOW_CALL] < read[GLOBAL_CALL] <= 100
    # both kinds of chunk call: the three prompts' needed attention over
    # their 13 chunks, x 3.6 chunks traced, over 16 ms of calls
    need = sum(COUNTS.chunk_attention_flops(CONF, n)
               for n in (2048, 512, 4096)) / 13 * 3.6
    assert read[CHUNK_CALLS] == pytest.approx(
        100 * need / (0.016 * 197e12))
    assert 0 < read[CHUNK_CALLS] <= 100
    assert read["kv.window_share_of_pool.mixedlength"] == 25.0
    assert read["moe.held_row_share.mixedlength"] == 12.5
    assert read["engine.decode_occupancy.mixedlength"] == pytest.approx(
        100 * 120_000 / (4000 * 32))
    assert read["kv.preemptions.mixedlength"] == 2.0
    assert read["engine.sched_busy_share_window.mixedlength"] == 25.0


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
    quiet = {**recorded_run(), **quiet_run("any.mixedlength")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = 25.0 if name.startswith("kv.window_share") else 0.0
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the parent's program with these files dropped in: its engine has no
    # window planes, no expert rows and no ``window_context`` on its rounds
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        for key in ("kv_window_pool_bytes", "expert_rows_routed",
                    "expert_rows_held"):
            part["engine"].pop(key, None)
    for spans in parent["host_spans"]:
        for span in spans:
            span[3].pop("window_context", None)
    if name.startswith(("kv.window_share", "moe.held_row")) \
            or name == WINDOW_CALL:
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: a step that took exactly its
    weights' time on the bus, a call exactly its rows' time."""
    run = recorded_run()
    least = COUNTS.decode_weight_bytes(CONF, 2, 30) / 819e9
    rows = {"%paged_decode_attention": 75_000 * 4096 / 819e9,
            "%paged_window_decode_attention": 3840 * 4096 / 819e9}
    device = run["trace"]["devices"][0]
    device["modules"] = [m[:2] + [least] if "decode" in m[0] else m
                         for m in device["modules"]]
    device["ops"] = [
        o[:2] + [rows[o[0].split(".")[0]]]
        if o[0].split(".")[0] in rows and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in ("step.decode_weight_bw_share.mixedlength", GLOBAL_CALL,
                 WINDOW_CALL):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-ring")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"kv_window_pool_bytes", "kv_global_pool_bytes",
            "kv_window_pages_a_sequence", "expert_rows_routed",
            "expert_rows_held", "kv_pool_bytes", "kv_bytes_per_token",
            "prefill_chunks_dispatched", "prefill_programs_dispatched",
            "decode_steps_dispatched", "decode_tokens_emitted",
            "preemptions", "slots", "sched_host_busy_sum_s"} <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2)
    ring = counters["kv_window_pages_a_sequence"]
    assert ring == 5                    # 2 + 2 + 1 pages of 16
    assert counters["kv_window_pool_bytes"] == engine.num_slots \
        * counts.window_ring_bytes_per_sequence(TINY, 2, ring, 16)
    assert counters["kv_window_pool_bytes"] \
        + counters["kv_global_pool_bytes"] == counters["kv_pool_bytes"]


def test_what_pr_40_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/k-exaone-236b-a23b.json",
                 "benchmark/configs/rehearsal-tiny-exaone.json",
                 "benchmark/traffic/batch-mixedlength.json",
                 "benchmark/traffic/rehearsal-closed-ring.json"]
                + [f"benchmark/architectures/exaone-moe/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(READERS) <= set(names)
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert mf.cell(MANIFEST, CELL)["config"] == "k-exaone-236b-a23b"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
