"""The ``longcat-flash`` architecture and its cell
(``longcat-flash-omni.batch-voiceturns``): the cell's path rehearsed on the
CPU at tiny widths and judged ``correct`` against its own plain reference,
the float8 control and the four controls of the block (the expert layer left
out, the zero experts' term left out, the result joined a sublayer early,
both rank factors left out) over the limit, ``counts.py`` against the
numbers reckoned by hand in ISSUE 57, the configuration file against the
published config, ``program.py``'s table refusing a drifted key, and each of
the cell's twelve readers on a recorded run and on a run without samples.

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
CELL = "longcat-flash-omni.batch-voiceturns"
REHEARSAL = "tiny-longcat.rehearsal-closed"
CONF = mf.load_config(MANIFEST, "longcat-flash-omni")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-longcat.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
ZERO = "moe.zero_row_share.voiceturns"
PRODUCTS = "step.expert_matmul_share.voiceturns"
CHUNK_CALLS = "kernel.latent_chunk_attention_mfu.voiceturns"
DECODE_CALL = "kernel.latent_decode_bw_share.voiceturns"
COUNTER_READERS = [ZERO, "moe.held_row_share.voiceturns",
                   "engine.decode_occupancy.voiceturns",
                   "kv.preemptions.voiceturns",
                   "engine.sched_busy_share_window.voiceturns",
                   "engine.sync_state_ms_per_round.voiceturns",
                   "start.unattributed_s.voiceturns"]
READERS = [ZERO, "moe.held_row_share.voiceturns", PRODUCTS,
           "step.decode_weight_bw_share.voiceturns",
           "step.prefill_mfu.voiceturns", DECODE_CALL, CHUNK_CALLS] \
    + COUNTER_READERS[2:]
with open("/opt/skills/guides/model-configs/architectures.jsonl") as _f:
    # config.json of meituan-longcat/LongCat-Flash-Omni, as the catalog
    # beside the model-configs guide gives it
    PUBLISHED = next(json.loads(line) for line in _f
                     if '"LongCat-Flash-Omni"' in line)


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 57, seconds=2.0,
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
        assert 0.0 < value["engine.decode_occupancy.voiceturns"] <= 100.0
        # 8 of the tiny router's 24 outputs are zero experts, 4 are held
        assert 20.0 < value[ZERO] < 50.0
        assert 5.0 < value["moe.held_row_share.voiceturns"] < 35.0
        assert value["kv.preemptions.voiceturns"] == 0.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_controls_are_over_the_limit_and_the_program_under():
    """One precision step down fails by each number, and so does a reference
    with any of the block's four parts got wrong (what the comparison reads
    beside a program that lacks the mechanism); the program's own int8 path
    cannot be a control (a latent pool refuses int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed")
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
        for variant in ref.VARIANTS[1:]:
            fn = jax.jit(lambda p, t, v=variant: ref.logits(
                p, t, TINY, last=8, variant=v))
            with jax.default_matmul_precision("highest"):
                got = [fn(params, jnp.asarray(toks))]
            numbers = correctness.compare_sides(got, want, spec, 32)
            for name in limits:
                assert numbers[name] > 2 * limits[name], (variant, numbers)
    with pytest.raises(ValueError, match="int8 KV"):
        control.serving_sides(TINY, traffic, 5, ["program_int8"])


@pytest.mark.parametrize("what", [
    "normalised weights", "softmax over the experts with weights alone",
    "one attention a layer"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference of NEARBY equations (the chosen
    weights divided by their sum; the softmax without the zero experts'
    outputs; the second attention of a layer left out): not the model, and
    the comparison says so."""
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 5, "bfloat16")
    tokens = jnp.asarray(correctness.check_tokens(5, 0, 100,
                                                  TINY["vocab_size"]))
    own = correctness.reference_logits(params, np.asarray(tokens), TINY,
                                       last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    F32 = jnp.float32
    real = {"routing": ref.routing,
            "latent_attention": ref.latent_attention}

    def routing(moe, h, c, quant):
        logits = h @ moe["router"].astype(F32)
        if what.startswith("softmax over"):
            e = c["n_routed_experts_published"]
            scores = jnp.concatenate(
                [jax.nn.softmax(logits[:, :e], -1),
                 jax.nn.softmax(logits[:, e:], -1)], -1)
        else:
            scores = jax.nn.softmax(logits, -1)
        biased = scores + moe["router_bias"].astype(F32)
        _, chosen = jax.lax.top_k(biased, c["moe_topk"])
        w = jnp.take_along_axis(scores, chosen, -1)
        if what == "normalised weights":
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * c["routed_scaling_factor"]
        return jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)
                       * w[..., None], axis=1)

    calls = {"n": 0}

    def latent_attention(a, u, positions, c, q_block, quant, variant):
        calls["n"] += 1
        out = real["latent_attention"](a, u, positions, c, q_block, quant,
                                       variant)
        return out * 0 if calls["n"] % 2 == 0 else out

    try:
        if what == "one attention a layer":
            ref.latent_attention = latent_attention
        else:
            ref.routing = routing
        with jax.default_matmul_precision("highest"):
            other = ref.logits(params, tokens, TINY, last=64)
    finally:
        ref.routing = real["routing"]
        ref.latent_attention = real["latent_attention"]
    err = np.median(correctness.position_errors(other, own))
    assert err > 2 * limit, (what, err)


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
    """The routing by hand on three tokens; the reference imports nothing of
    the program and absorbs nothing."""
    ref = architecture.part(TINY, "reference")
    c = {"moe_topk": 2, "routed_scaling_factor": 6,
         "n_routed_experts_published": 2, "zero_expert_num": 2}
    moe = {"router": jnp.eye(4, dtype=jnp.float32) * 3.0,
           "router_bias": jnp.asarray([0.0, 0.0, 0.0, 1.0])}
    h = jnp.eye(4, dtype=jnp.float32)[:3]
    got = np.asarray(ref.routing(moe, h, c, lambda v: v))
    s = np.exp(3.0) / (np.exp(3.0) + 3)               # the token's own
    o = 1.0 / (np.exp(3.0) + 3)                       # every other output
    # the bias makes output 3 a choice of every token, never a weight
    np.testing.assert_allclose(got, 6 * np.asarray(
        [[s, 0, 0, o], [0, s, 0, o], [0, 0, s, o]]), rtol=1e-5)
    with open(ref.__file__) as f:
        src = f.read().split('"""', 2)[2]
    assert "kubeflow_tpu" not in src and "pallas" not in src
    assert "wkvb" in src and "latent_query" not in src
    assert "softmax" in src and "sigmoid" not in src


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d = 6144
    assert COUNTS.attention_params(CONF) == (
        d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256
        + 64 * 128 * d + 1536 + 512) == 90_572_800
    assert COUNTS.dense_mlp_params(CONF) == 3 * d * 12288 == 226_492_416
    assert COUNTS.expert_params_one(CONF) == 3 * d * 2048 == 37_748_736
    assert COUNTS.router_width(CONF) == 768
    assert COUNTS.router_params(CONF) == d * 768 + 768 == 4_719_360
    assert COUNTS.layer_params_outside_experts(CONF) == (
        2 * 90_572_800 + 2 * 226_492_416 + 4_719_360 + 4 * d) == 638_874_368
    assert COUNTS.layer_params_published(CONF) == 19_966_227_200
    assert COUNTS.layer_params_total(CONF) == 1_242_854_144
    assert 2 * 16384 * d == 201_326_592
    total = COUNTS.params_total(CONF)
    assert total == 4 * 1_242_854_144 + 201_326_592 + d == 5_172_749_312
    assert round(total * 2 / 1e9, 2) == 10.35
    # five layers, which the issue does not take
    assert round(COUNTS.params_total({**CONF, "num_layers": 5})
                 * 2 / 1e9, 2) == 12.83
    # the whole published language model: 560.66 B
    whole = COUNTS.params_total({
        **CONF, "num_layers": 28, "n_routed_experts": 512,
        "vocab_size": 131072})
    assert whole == 28 * 19_966_227_200 + 2 * 131072 * d + d
    assert round(whole / 1e9, 2) == 560.66
    # a token: 1280 bytes in each of the eight attentions; 816 pages of 128
    assert COUNTS.latent_row_values(CONF) == 640
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 8 * 1280 == 10_240
    assert round(816 * 128 * 10_240 / 1e9, 2) == 1.07
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs_here():
    d = 6144
    # a quarter of a held expert a token: 12 choices over 768 outputs, 16 held
    assert COUNTS.experts_met(CONF) == 0.25
    layer = 2 * (90_572_800 - 2048) + 2 * 226_492_416 + d * 768 \
        + 0.25 * 37_748_736
    assert COUNTS.layer_matmul_params_active(CONF) == layer
    per_token = COUNTS.layers_matmul_params_active(CONF)
    assert per_token == 4 * layer
    assert round(2 * per_token / 1e9, 2) == 5.19
    dense_path = 4 * (2 * (90_572_800 - 2048) + 2 * 226_492_416)
    assert 0.97 < dense_path / per_token < 0.99   # the dense path is the work
    assert COUNTS.attention_flops(CONF, 1) == 2 * 64 * (192 + 128)
    assert COUNTS.latent_chunk_attention_flops(CONF, 1) \
        == 2 * 64 * (576 + 512) == 139_264
    assert COUNTS.visible_pairs(512, 1024) == 512 * 1024 + 512 * 513 / 2
    n = 1024
    want = (2.0 * per_token * n
            + 8 * 2 * 64 * 320 * n * (n + 1) / 2 + 2.0 * d * 16384)
    assert COUNTS.prefill_flops(CONF, n) == pytest.approx(want, rel=1e-12)
    # a zero expert costs nothing: no term of any count grows with them
    more = {**CONF, "zero_expert_num": 512}
    assert COUNTS.params_total(more) == COUNTS.params_total(CONF) \
        + 4 * 256 * (d + 1)                            # the router's alone
    assert COUNTS.experts_met(more) < COUNTS.experts_met(CONF)
    # a step's weights: everything but the embedding, of the 64 held
    # experts those that some live stream is expected to choose
    fixed = 5_172_749_312 - 4 * 16 * 37_748_736 - 16384 * d
    assert 2 * fixed == 5_312_333_824
    assert COUNTS.decode_weight_bytes(CONF, 2, 0) == 2.0 * fixed
    touched = 1 - (1 - 12 / 768) ** 48
    assert round(touched, 2) == 0.53
    assert COUNTS.decode_weight_bytes(CONF, 2, 48) == pytest.approx(
        2.0 * (fixed + touched * 4 * 16 * 37_748_736))
    assert round(COUNTS.decode_weight_bytes(CONF, 2, 48) / 1e9, 1) == 7.9
    assert COUNTS.latent_decode_bytes(CONF, 48 * 1500, 2) == 48 * 1500 * 1280
    assert COUNTS.train_flops_per_token(CONF, 1024) > 6 * per_token


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "longcat-flash-omni")
    reduced = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
               "vocab_size": (131072, 16384)}
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
            CONF["vocab_size_published"]) == (512, 0, 131072)
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("norm_topk_prob", "router_bias", "tie_word_embeddings",
                 "rope", "rank_factors", "correction_bias", "zero_experts",
                 "towers", "weights"):
        assert item in CONF["assumed"]
    assert "NOT built" in CONF["assumed"]["towers"]
    assert "WRITTEN" in CONF["assumed"]["rank_factors"]
    assert "one chip of the 32 that share EACH LAYER" in CONF["deployment"]
    assert "ckv" in CONF["cache"] and "TIMES" in CONF["cache"]
    assert CONF["architecture"] == "longcat-flash" and CONF["chips"] == 1
    longest = max(plen + n for plen, n in CONF["correctness"]["sequences"])
    assert longest <= CONF["program"]["overrides"]["max_seq_len"] == 2176
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")
    # no width is reduced
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key in (
            "vocab_size",)


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    entry = mf.config_entry(MANIFEST, "longcat-flash-omni")
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
            cfg.vocab_size) == (8, 6144, 64, 12288, 16384)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.zero_experts, cfg.experts_held,
            cfg.experts_per_token, cfg.expert_mlp_dim, cfg.router_scale) \
        == (512, 256, 16, 12, 2048, 6.0)
    assert cfg.moe_shortcut and cfg.latent_rank_scale
    assert cfg.router_score == "softmax_all" and not cfg.router_norm_topk
    assert cfg.kinds == ("attention",) * 8 and not cfg.tie_embeddings
    for key, other in (("hidden_size", 4096), ("num_layers", 8),
                       ("ffn_hidden_size", 10240),
                       ("expert_ffn_hidden_size", 1536),
                       ("n_routed_experts", 32),
                       ("n_routed_experts_published", 256),
                       ("expert_offset", 16), ("zero_expert_num", 128),
                       ("zero_expert_type", "copy"), ("moe_topk", 8),
                       ("routed_scaling_factor", 2.5),
                       ("norm_topk_prob", True), ("router_bias", True),
                       ("num_attention_heads", 32), ("q_lora_rank", 2048),
                       ("kv_lora_rank", 256), ("qk_nope_head_dim", 192),
                       ("qk_rope_head_dim", 32), ("v_head_dim", 256),
                       ("mla_scale_q_lora", False),
                       ("mla_scale_kv_lora", False),
                       ("attention_method", "GQA"), ("vocab_size", 131072),
                       ("attention_bias", True), ("rms_norm_eps", 1e-6),
                       ("rope_theta", 10000),
                       ("tie_word_embeddings", True)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="longcat-flash is"):
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
    assert got["embed"].shape == (16384, 6144)
    assert got["lm_head"].shape == (6144, 16384)
    assert got["layers"]["attn"]["wqb"].shape == (8, 1536, 64, 192)
    assert got["layers"]["mlp"]["gate"].shape == (8, 6144, 12288)
    assert got["layers"]["moe"]["router"].shape == (4, 6144, 768)
    assert got["layers"]["moe"]["gate"].shape == (4, 16, 6144, 2048)
    # the stratified bias: every seed the same multiset, each block of the
    # held width (experts with weights and zero experts alike) one value of
    # each stratum
    a, b = (np.asarray(make_params(TINY, s, "float32")["layers"]["moe"][
        "router_bias"]) for s in (3, 4))
    assert a.shape == (2, 24)
    assert np.array_equal(np.sort(a, axis=1), np.sort(b, axis=1))
    assert not np.array_equal(a, b)
    ranks = np.argsort(np.argsort(a, axis=1), axis=1) // 6    # 4 strata of 6
    assert all(sorted(block) == [0, 1, 2, 3]
               for layer in ranks for block in layer.reshape(6, 4))
    tiny = make_params(TINY, 3, "float32")
    # (the matrices behind the rank factors are drawn at 1 / (factor x
    # sqrt(fan_in)): sqrt(64 / 24) / sqrt(24) is 1 / sqrt(64))
    for leaf, fan in ((tiny["layers"]["attn"]["wqb"], 64),
                      (tiny["layers"]["attn"]["wkvb"], 64),
                      (tiny["layers"]["attn"]["wo"], 80),
                      (tiny["layers"]["mlp"]["down"], 160),
                      (tiny["layers"]["moe"]["router"], 64),
                      (tiny["layers"]["moe"]["down"], 48)):
        std = float(np.std(np.asarray(leaf))) * fan ** 0.5
        assert 0.85 < std < 1.15, (fan, std)


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 48
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    # the block names what ISSUE 57 names and no more
    assert set(e) == {"paged", "max_batch_size", "max_seq_len", "page_size",
                      "max_pages", "chunked_prefill_tokens", "decode_steps",
                      "prefill_interleave_steps"}
    assert BatchingSpec(**e).max_concurrent_prefills \
        == BatchingSpec().max_concurrent_prefills == 2
    assert traffic["shared_prefix_tokens"] == 0
    assert traffic["output_len"]["dist"] == traffic["prompt_len"]["dist"] \
        == "uniform"
    # the named sizes or ISSUE 57's one sanctioned fallback: the same means
    prompts = (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"])
    answers = (traffic["output_len"]["min"], traffic["output_len"]["max"])
    assert (prompts, answers) in (((512, 1536), (256, 512)),
                                  ((768, 1280), (320, 448)))
    mpp = e["max_seq_len"] // e["page_size"]
    longest = prompts[1] + answers[1]
    assert mpp == 17 and longest <= e["max_seq_len"] == 2176
    assert e["max_pages"] == 48 * mpp == 816        # 48 whole contexts
    need = required_programs(traffic, BatchingSpec(**e))
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {"paged_decode[1,greedy]"} <= need
    assert {f"paged_chunk_prefill[1x512,{b}]" for b in (4, 8, 16, 17)} \
        == {n for n in need if n.startswith("paged_chunk_prefill")}
    # the comparison's long sequence walks every one of those buckets
    assert max(p for p, _ in CONF["correctness"]["sequences"]) > 2048


# -- the twelve readers -------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 4000 decode steps over 44 live streams and 300 chunk
    programs; 3 traced seconds holding two chunk programs (90 and 94 ms) of
    two rows each (a chunk at 0 and one at 512; one at 512 and one at 1024)
    with a step of 44 streams riding, and one decode-only step (12 ms): in
    each chunk program eight calls of the chunk kernel a row (2 ms), eight
    of the decode kernel (0.2 ms) and twelve grouped matmuls (1.5 ms); in
    the decode-only step eight decode calls and twelve ragged products (0.4
    ms)."""
    run = quiet_run("any.voiceturns")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(slots=48, kv_pool_bytes=1_069_547_520)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=4000, decode_tokens_emitted=176_000,
        prefill_programs_dispatched=300, prefill_chunks_dispatched=600,
        prefill_tokens_dispatched=300_000, preemptions=0,
        sched_host_busy_sum_s=4.0, expert_rows_routed=24_000_000,
        expert_rows_held=500_000, expert_rows_zero=8_000_000,
        decode_rounds=4000, sched_sync_state_sum_s=2.0)
    spans = []
    for i, (at, starts) in enumerate(zip((0.0, 0.1), ((0, 512),
                                                      (512, 1024)))):
        spans += [
            ["engine.prefill_dispatch", at, 0.002,
             {"slot": i, "pos": starts[0], "chunks": 2,
              "context": int(sum(COUNTS.visible_pairs(512, s)
                                 for s in starts)),
              "rows_routed": 52_224, "rows_held": 1_100,
              "rows_zero": 17_400}],
            ["engine.decode_dispatch", at + 0.0005, 0.001,
             {"round": i, "k_steps": 1, "live": 44, "context": 44 * 1500,
              "rows_routed": 52_224, "rows_held": 1_100,
              "rows_zero": 17_400}]]
    spans.append(["engine.decode_dispatch", 0.2, 0.001,
                  {"round": 2, "k_steps": 1, "live": 44,
                   "context": 44 * 1500, "rows_routed": 2_304,
                   "rows_held": 50, "rows_zero": 770}])
    run["host_spans"].append(spans)
    ops = []
    for at in (0.0, 0.1):
        for i in range(8):
            t = at + 0.01 * i
            ops += [[f"%paged_latent_chunk_attention.{2 * i} = custom-call",
                     t, 0.002],
                    [f"%paged_latent_chunk_attention.{2 * i + 1} = "
                     "custom-call", t + 0.002, 0.002],
                    [f"%paged_latent_decode_attention.{i} = custom-call",
                     t + 0.004, 0.0002]]
        for i in range(12):
            ops.append([f"%gmm.{i} = custom-call", at + 0.0045 + 0.006 * i,
                        0.0015])
    for i in range(8):
        ops.append([f"%paged_latent_decode_attention.{i} = custom-call",
                    0.2 + 0.001 * i, 0.0002])
    for i in range(12):
        ops.append([f"%ragged-dot.{i} = bf16[576,2048] ragged-dot(",
                    0.2003 + 0.0008 * i, 0.0004])
    # the op that takes a kernel's result names it too, and is no call
    ops.append(["%slice.7 = bf16[48,64,640] slice(bf16[1,48,64,640] "
                "%paged_latent_decode_attention.1)", 0.215, 1e-7])
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.090],
                    ["jit__lambda(7)", 0.1, 0.094],
                    ["jit__lambda(9)", 0.195, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.012]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.09],
                      ["%fusion.12 = fusion", 0.1, 0.094],
                      ["%fusion.13 = fusion", 0.2, 0.012]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0, "values": {"setup_s": 200.0},
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [1024, 600, 1500]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 2.8e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    assert read[ZERO] == pytest.approx(100 / 3)
    assert read["moe.held_row_share.voiceturns"] == pytest.approx(
        100 * 500_000 / 24_000_000)
    # the products: 24 kernel calls of 1.5 ms and 12 ragged products of 0.4
    # over the busy time (the three fusions cover everything)
    assert read[PRODUCTS] == pytest.approx(
        100 * (24 * 0.0015 + 12 * 0.0004) / (0.09 + 0.094 + 0.012))
    # chunk attention: the visible pairs of four chunks, absorbed, once an
    # attention (eight), over 32 calls of 2 ms
    pairs = sum(COUNTS.visible_pairs(512, s) for s in (0, 512, 512, 1024))
    assert read[CHUNK_CALLS] == pytest.approx(
        100 * 8 * pairs * 139_264 / (32 * 0.002 * 197e12))
    assert 0 < read[CHUNK_CALLS] < 100
    # a decode call: 44 streams' 1500 rows of 1280 B in 0.2 ms
    assert read[DECODE_CALL] == pytest.approx(
        100 * 44 * 1500 * 1280 / 819e9 / 0.0002)
    assert 0 < read[DECODE_CALL] < 100
    # two programs of two chunks of 2.8 TFLOP needed over 184 ms
    assert read["step.prefill_mfu.voiceturns"] == pytest.approx(
        100 * 2 * 2 * 2.8e12 / (0.184 * 197e12))
    # the one decode-ONLY step (eight calls of the latent decode kernel
    # inside a decode program; the steps inside ``jit__lambda`` ride a
    # chunk): the weights 44 live streams are expected to touch over 12 ms
    assert read["step.decode_weight_bw_share.voiceturns"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2, 44.0) / 819e9 / 0.012)
    assert 50 < read["step.decode_weight_bw_share.voiceturns"] < 100
    assert read["engine.decode_occupancy.voiceturns"] == pytest.approx(
        100 * 176_000 / (4000 * 48))
    assert read["kv.preemptions.voiceturns"] == 0.0
    assert read["engine.sched_busy_share_window.voiceturns"] == 10.0
    assert read["engine.sync_state_ms_per_round.voiceturns"] == 0.5
    assert read["start.unattributed_s.voiceturns"] == 200.0 - 6.0


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
    # number
    quiet = {**recorded_run(), **quiet_run("any.voiceturns")}
    quiet["values"] = {"setup_s": 30.0}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = {"start.unattributed_s.voiceturns": 24.0}.get(name, 0.0)
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the PARENT's program with these files dropped in (it cannot build this
    # model; an engine without the counter, spans that say nothing of a
    # chunk's context): nothing or a number, never an exception
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        part["engine"].pop("expert_rows_zero", None)
    for span in parent["host_spans"][-1]:
        for key in ("rows_routed", "rows_held", "rows_zero"):
            span[3].pop(key, None)
        if span[0] == "engine.prefill_dispatch":
            span[3].pop("context", None)
    if name in (ZERO, CHUNK_CALLS):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: calls that took exactly their
    needed work's time."""
    run = recorded_run()
    pairs = sum(COUNTS.visible_pairs(512, s) for s in (0, 512, 512, 1024))
    floor = {"%paged_latent_chunk_attention":
                 8 * pairs * 139_264 / 197e12 / 32,
             "%paged_latent_decode_attention": 44 * 1500 * 1280 / 819e9}
    device = run["trace"]["devices"][0]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in (CHUNK_CALLS, DECODE_CALL):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)
    # the products' share of the busy time is a share of a whole
    assert 0 < mf.load_layer_metric(PRODUCTS).read(run) < 100


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"expert_rows_routed", "expert_rows_held", "expert_rows_zero",
            "kv_pool_bytes", "kv_bytes_per_token",
            "prefill_chunks_dispatched", "prefill_programs_dispatched",
            "decode_steps_dispatched", "decode_tokens_emitted",
            "preemptions", "slots", "decode_rounds",
            "sched_host_busy_sum_s", "sched_sync_state_sum_s"} \
        <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2) == 4 * 128 * 2
    assert counters["kv_pool_bytes"] == engine._num_pages \
        * engine.page_size * counters["kv_bytes_per_token"]


def test_what_pr_57_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/longcat-flash-omni.json",
                 "benchmark/configs/rehearsal-tiny-longcat.json",
                 "benchmark/traffic/batch-voiceturns.json"]
                + [f"benchmark/architectures/longcat-flash/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(READERS[0])
    assert sorted(names[at:at + len(READERS)]) == sorted(READERS)
    assert all(n.split(".")[-1] != "voiceturns" for n in names[:at])
    assert mf.cell(MANIFEST, CELL)["config"] == "longcat-flash-omni"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert len(MANIFEST["workloads"]) >= 11 <= len(MANIFEST["configs"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
