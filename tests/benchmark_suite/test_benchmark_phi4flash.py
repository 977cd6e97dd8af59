"""The ``phi4flash`` architecture and its cell
(``phi-4-mini-flash.batch-reasoning``): the cell's path rehearsed on the CPU
at tiny widths and judged ``correct`` against its own plain reference, which
walks the Mamba layers TOKEN BY TOKEN, attends with two softmaxes and a
subtraction and runs every layer at every position (through
``engine_logits``' calls as they stand: ONE page-table row of ``arange`` and
no slot, from which an ssm layer finds its sequence's state at ``row[0]``
and a window layer its ring over ``row[:R]``), the float8 control over its
limit, a reference of other equations far over it, ``counts.py`` against the
numbers reckoned by hand in ISSUE 47, the configuration file against the
published config, and each of the cell's twelve readers on a recorded run
and on a run without samples.

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
CELL = "phi-4-mini-flash.batch-reasoning"
REHEARSAL = "tiny-phi4flash.rehearsal-closed-ssm"
CONF = mf.load_config(MANIFEST, "phi-4-mini-flash")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-phi4flash.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
STEP = "step.decode_weight_bw_share.reasoning"
SCAN = "kernel.ssm_scan_roofline_share.reasoning"
GLOBAL_CALL = "kernel.paged_decode_attention_bw_share.reasoning"
WINDOW_CALL = "kernel.paged_window_decode_attention_bw_share.reasoning"
CHUNK_CALLS = "kernel.paged_chunk_attention_mfu.reasoning"
TAIL = "step.tail_program_share.reasoning"
COUNTER_READERS = ["kv.state_share_of_pool.reasoning",
                   "kv.window_share_of_pool.reasoning", TAIL,
                   "engine.decode_occupancy.reasoning",
                   "kv.preemptions.reasoning",
                   "engine.sched_busy_share_window.reasoning"]
READERS = [STEP, "step.prefill_mfu.reasoning", SCAN, GLOBAL_CALL, WINDOW_CALL,
           CHUNK_CALLS] + COUNTER_READERS
# config.json of microsoft/Phi-4-mini-flash-reasoning, as the catalog beside
# the model-configs guide gives it
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 47, seconds=2.0,
                    trace=trace, allow_cpu=True)
    counters = set(COUNTER_READERS)   # what the CPU's trace can feed
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
        assert 0.0 < value["engine.decode_occupancy.reasoning"] <= 100.0
        assert value["kv.preemptions.reasoning"] >= 0.0
        # ONE layer's rows in 16 pages of 16 tokens x 128 B; two window
        # layers' rings of 4 pages (a chunk of 32, a window of 8, plus one)
        # for two slots; three ssm layers' entries for two slots
        rows = 16 * 16 * 128
        rings = 2 * 2 * 4 * 16 * 128
        state = 3 * 2 * (4 * 128 * 4 + 3 * 128 * 2)
        total = rows + rings + state
        assert value["kv.state_share_of_pool.reasoning"] == pytest.approx(
            100 * state / total)
        assert value["kv.window_share_of_pool.reasoning"] == pytest.approx(
            100 * rings / total)
        # prompts of 20-60 tokens in chunks of 32: one or two programs a
        # prompt, one of them with its end
        assert 50.0 <= value[TAIL] <= 100.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (ssm layers refuse int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-ssm")
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


@pytest.mark.parametrize("what", [
    "no decay", "no convolution", "lambda ignored", "no window",
    "another layer's memory", "another layer's cache"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference whose state never decays, whose
    convolution sees the current position alone, whose second softmax is
    dropped, whose window layers see every key, whose gated memory units
    read the first Mamba layer's output or whose cross layers attend over
    their own queries' layer: not the model, and the comparison says so."""
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 100, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    conf, tree = TINY, jax.tree.map(lambda a: a, params)
    if what == "no decay":
        for g in ("layers", "layers_rest"):
            tree[g]["ssm"]["a_log"] = jnp.full_like(
                tree[g]["ssm"]["a_log"], -30.0)
    elif what == "no convolution":
        for g in ("layers", "layers_rest"):
            taps = tree[g]["ssm"]["conv"]
            tree[g]["ssm"]["conv"] = taps.at[:, :-1].set(0)
    elif what == "lambda ignored":
        for g, k in (("layers", "window"), ("layers_rest", "attn"),
                     ("layers_rest2", "cross")):
            tree[g][k]["lambda_init"] = jnp.zeros_like(
                tree[g][k]["lambda_init"])
    elif what == "no window":
        conf = {**TINY, "sliding_window": 4096}
    elif what == "another layer's memory":
        tree["layers_rest"]["ssm"] = jax.tree.map(
            lambda a: a[:1], tree["layers"]["ssm"])
    else:
        tree["layers_rest"]["attn"] = {
            k: v[:1] for k, v in tree["layers"]["window"].items()}
    got = correctness.reference_logits(tree, tokens, conf, last=64)
    err = float(jnp.median(correctness.position_errors(got, own)))
    assert err > 1.5 * limit, (what, err)
    assert callable(ref.sequence_nll)


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


def test_the_reference_is_plain():
    """One token of the recurrence, by hand, on a state laid [E, N]; the
    reference imports nothing of the program and names no blocked form."""
    ref = architecture.part(TINY, "reference")
    a = -jnp.asarray([[1.0, 2.0], [0.5, 4.0]])             # [E=2, N=2]
    step = ref.mamba_token(a, jnp.asarray([3.0, 5.0]))
    h, y = step(jnp.ones((2, 2)), (jnp.asarray([2.0, 1.0]),   # c
                                   jnp.asarray([0.5, 0.25]),  # Delta
                                   jnp.asarray([1.0, -1.0]),  # B
                                   jnp.asarray([2.0, 3.0])))  # C
    want = np.exp(np.asarray([[0.5 * -1, 0.5 * -2], [0.25 * -0.5, -1.0]])) \
        + np.asarray([[1.0, -1.0], [0.25, -0.25]])
    np.testing.assert_allclose(h, want, rtol=1e-6)
    np.testing.assert_allclose(
        y, want @ np.asarray([2.0, 3.0]) + np.asarray([6.0, 5.0]), rtol=1e-6)
    with open(ref.__file__) as f:
        src = f.read()
    assert "kubeflow_tpu" not in src.split('"""', 2)[2]
    assert "jax.lax.scan" in src and "associative_scan" not in src
    assert ref.group_sizes(CONF) == (16, 2, 14)
    assert ref.group_sizes(TINY) == (4, 2, 4)


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, e, v = 2560, 5120, 200064
    part = COUNTS.params_by_part(CONF)
    mlp = 3 * d * 10240
    assert part["mlp"] == 32 * mlp == 2_516_582_400          # 2516.6 M
    mamba = 2 * d * e + e * 192 + 160 * e + e * d + 5 * e + e + 16 * e + e
    assert COUNTS.mamba_params(CONF) == mamba == 41_241_600  # 41.2 M
    assert part["mamba"] == 9 * mamba                        # 371 M
    assert round(2 * d * e / 1e6, 2) == 26.21 and e * 192 == 983_040
    attn = d * 5120 + 2560 * d + 5120 + d + 6 * 64
    assert COUNTS.attention_params(CONF) == attn == 19_668_864   # 19.66 M
    assert part["attention"] == 9 * attn                     # 177 M
    assert part["gmu"] == 7 * 2 * d * e == 183_500_800       # 183.5 M
    cross = d * 2560 + 2560 * d + 2560 + d + 6 * 64
    assert COUNTS.attention_params(CONF, cross=True) == cross == 13_112_704
    assert part["cross"] == 7 * cross                        # 91.8 M
    assert part["embedding"] == v * d == 512_163_840         # tied: once
    assert part["norms"] == 65 * 2 * d
    total = COUNTS.params_total(CONF)
    assert total == sum(part.values()) == 3_852_562_944
    assert abs(total / 3.85e9 - 1) < 0.01                    # the published 3.8 B
    assert round(total * 2 / 1e9, 2) == 7.71
    # a token keeps 2 x 20 x 64 values in ONE layer; a sequence a ring of 9
    # pages in eight layers and [16, 5120] float32 + [3, 5120] in nine
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 5120
    assert COUNTS.window_bytes_per_sequence(CONF, 2, 9 * 128) \
        == 8 * 9 * 128 * 5120 == 47_185_920
    assert COUNTS.state_bytes_per_sequence(CONF, 2) == 9 * (
        16 * 5120 * 4 + 3 * 5120 * 2) == 3_225_600
    # the cell's pool: 2080 pages of 128 in one layer, 32 rings, 32 entries;
    # 32 full-attention layers would hold 43.6 GB for the same contexts
    assert 2080 * 128 * 5120 == 1_363_148_800
    assert 32 * 47_185_920 == 1_509_949_440
    assert 32 * 3_225_600 == 103_219_200
    assert round(32 * 2080 * 128 * 5120 / 1e9, 1) == 43.6
    # the program counts the same parameters, the tree holds them (and a
    # constant a differential attention layer)
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total + 16


def test_operations_are_what_the_model_needs_here():
    d, e, v = 2560, 5120, 200064
    mamba_mm = 2 * d * e + e * 192 + 160 * e + e * d
    attn_mm = d * 5120 + 2560 * d
    mlp = 3 * d * 10240
    assert COUNTS.mamba_matmul_params(CONF) == mamba_mm
    own = 9 * mamba_mm + 9 * attn_mm + 18 * mlp
    assert COUNTS.self_decoder_matmul_params(CONF) == own
    tail = 7 * 2 * d * e + 7 * 2 * d * d + 14 * mlp
    assert COUNTS.cross_decoder_matmul_params(CONF) == tail
    assert COUNTS.causal_pairs(512, 4096) == 512 * 4096 + 512 * 513 / 2
    assert COUNTS.causal_pairs(4, 1, window=3) == 2 + 3 + 3 + 3
    assert COUNTS.causal_pairs(1024, 0, window=512) \
        == 512 * 513 / 2 + 512 * 512
    assert COUNTS.pair_flops(CONF) == 4.0 * 64 * 40
    assert COUNTS.ssm_scan_elements(CONF, 1024) == 5120 * 16 * 1024
    assert round(9 * COUNTS.ssm_scan_elements(CONF, 1024) / 1e9, 3) == 0.755
    n = 1024
    want = (2.0 * own * n + 9 * 7.0 * 5120 * 16 * n
            + 4.0 * 64 * 40 * (8 * COUNTS.causal_pairs(n, 0, 512)
                               + n * (n + 1) / 2)
            + 2.0 * tail + 7 * 4.0 * 64 * 40 * n + 2.0 * d * v)
    assert COUNTS.prefill_flops(CONF, n) == want    # tail and head ONCE
    # the tail at every position would add 2 x 1.38 G a token to the
    # self-decoder's 2 x 1.96 G: 70% more
    assert 0.69 < tail / own < 0.71
    assert COUNTS.chunk_attention_flops(CONF, n) \
        == COUNTS.attention_flops(CONF, n)
    # a step's weights: everything held, the tied table read as the head
    assert COUNTS.decode_weight_bytes(CONF, 2, 32) \
        == COUNTS.decode_weight_bytes(CONF, 2, 1) == 2.0 * 3_852_562_944
    assert COUNTS.resident_weight_bytes(CONF, 2) == 2.0 * 3_852_562_944
    # eight calls a step over one layer's rows; 0.66 MB a stream a layer
    assert COUNTS.decode_attention_calls(CONF) == 8
    assert COUNTS.decode_attention_bytes(CONF, 1000, 2) == 1000 * 5120
    assert COUNTS.ssm_step_bytes(CONF, 1) == 2 * 16 * 5120 * 4 \
        + 2 * 3 * 5120 * 2 == 716_800
    assert round(9 * COUNTS.ssm_step_bytes(CONF, 32) / 1e9, 2) == 0.21
    # a scan call of two rows of 512: x, Delta, y and B, C a token, the
    # state twice a row
    assert COUNTS.ssm_scan_bytes(CONF, 1024, 2) == 1024 * 4 * (
        3 * 5120 + 32) + 2 * 2 * 4 * 5120 * 16


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_and_reduces_nothing():
    entry = mf.config_entry(MANIFEST, "phi-4-mini-flash")
    assert entry["reduced"] == [] and CONF["reduced"] == {}
    assert entry["source"] == CONF["source"]
    for key, value in PUBLISHED.items():
        assert CONF[key] == value, key
    assert CONF["layer_types"] == ["mamba", "sliding_attention"] * 8 \
        + ["mamba", "full_attention"] + ["gmu", "cross_attention"] * 7
    assert (CONF["d_state"], CONF["d_conv"], CONF["expand"],
            CONF["dt_rank"]) == (16, 4, 2, 160)
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("d_state", "d_conv", "expand", "dt_rank", "ssm_init",
                 "layer_types", "differential_attention", "attention_bias",
                 "position", "sliding_window_counts_the_query", "norm",
                 "head_dim", "mlp", "weights"):
        assert item in CONF["assumed"]
        assert "arXiv" in CONF["assumed"][item] \
            or "row" in CONF["assumed"][item] \
            or "--seed" in CONF["assumed"][item], item
    assert CONF["architecture"] == "phi4flash" and CONF["chips"] == 1
    assert any(plen + n == 8320 for plen, n
               in CONF["correctness"]["sequences"])
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    entry = mf.config_entry(MANIFEST, "phi-4-mini-flash")
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
            cfg.head_dim, cfg.mlp_dim, cfg.vocab_size) \
        == (32, 2560, 40, 20, 64, 10240, 200064)
    assert cfg.kinds == ("ssm", "window") * 8 + ("ssm", "attention") \
        + ("gmu", "cross") * 7
    assert (cfg.ssm_state, cfg.ssm_inner, cfg.ssm_dt_rank, cfg.conv_taps,
            cfg.attn_window, cfg.stateless_tail) == (16, 5120, 160, 4, 512,
                                                     14)
    assert cfg.diff_attention and cfg.attn_bias and not cfg.use_rope
    assert cfg.norm_kind == "layer" and cfg.tie_embeddings
    for key, other in (("hidden_size", 2048), ("num_hidden_layers", 16),
                       ("num_attention_heads", 20),
                       ("num_key_value_heads", 10),
                       ("intermediate_size", 8192), ("vocab_size", 25008),
                       ("layer_norm_eps", 1e-6), ("sliding_window", 256),
                       ("tie_word_embeddings", False), ("mlp_bias", True),
                       ("mb_per_layer", 1), ("d_state", 8), ("d_conv", 3),
                       ("dt_rank", 80),
                       ("layer_types", CONF["layer_types"][::-1])):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    # a config object that disagrees with the file is refused as well
    with pytest.raises(mf.ManifestError, match="phi4flash is"):
        program.program_config(CONF, use_rope=True)
    with pytest.raises(mf.ManifestError, match="phi4flash is"):
        program.program_config({**CONF, "expand": 4})


def test_the_seeded_tree_is_the_programs_at_the_published_widths():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert got["embed"].shape == (200064, 2560) and "lm_head" not in got
    assert got["layers"]["ssm"]["a_log"].shape == (8, 16, 5120)
    assert got["layers"]["window"]["wk"].shape == (8, 1280, 2560)
    assert got["layers_rest"]["attn"]["wq"].shape == (1, 2560, 2560)
    assert got["layers_rest2"]["gmu"]["w1"].shape == (7, 2560, 5120)
    assert "wk" not in got["layers_rest2"]["cross"]
    tiny = make_params(TINY, 3, "float32")
    ssm = tiny["layers"]["ssm"]
    np.testing.assert_allclose(np.exp(np.asarray(ssm["a_log"]))[0, :, 7],
                               [1, 2, 3, 4], rtol=1e-6)
    step = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert 0.99e-3 <= step.min() and step.max() <= 1.01e-1
    assert float(jnp.abs(ssm["d_skip"] - 1).max()) == 0.0
    # lambda_init by the layer's index: layers 1, 3 | 5 | 7, 9
    depth = {"layers": ("window", [1, 3]), "layers_rest": ("attn", [5]),
             "layers_rest2": ("cross", [7, 9])}
    for group, (kind, at) in depth.items():
        np.testing.assert_allclose(
            tiny[group][kind]["lambda_init"],
            0.8 - 0.6 * np.exp(-0.3 * np.asarray(at)), rtol=1e-6)
    assert 0.05 < float(jnp.std(tiny["layers"]["window"]["lambda_q1"])) < 0.2


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
    mpp = e["max_seq_len"] // e["page_size"]
    assert mpp == 65 and e["max_pages"] == 32 * mpp         # no preemption
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= e["max_seq_len"]
    need = required_programs(traffic, BatchingSpec(**e))
    # The warm-up's first prompt walks every chunk start of the longest
    # context alone (a group of one row at every bucket), its second group
    # sends two prompts at once (the two-row program)
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {f"paged_decode[{k},greedy]" for k in (1,)} <= need
    assert {f"paged_chunk_prefill[1x512,{b}]" for b in (4, 8, 16, 32, 64)} \
        <= need


# -- the twelve readers -------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 3000 decode steps over 30 live streams, 150 chunk
    programs (70 of them with an end) that carried 160 chunks of 70k tokens;
    3 traced seconds holding two chunk programs (45 and 60 ms), a cache
    copy, two decode programs of one step each (16 ms) over 30 streams at
    2000 and 3000 context rows a stream, in each EIGHT calls of the global
    decode kernel (0.5 ms) and eight of the window one (0.1 ms), and in each
    chunk program nine chunk attention calls (0.4 ms) and nine scans (0.3
    ms)."""
    run = quiet_run("any.reasoning")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(
            slots=32, kv_sequence_pool_bytes=103_219_200,
            kv_window_pool_bytes=1_509_949_440,
            kv_global_pool_bytes=1_363_148_800,
            kv_pool_bytes=2_976_317_440, kv_layers_sharing=7)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=3000, decode_tokens_emitted=90_000,
        prefill_programs_dispatched=150, prefill_programs_with_end=70,
        prefill_chunks_dispatched=160, prefill_tokens_dispatched=70_000,
        preemptions=1, sched_host_busy_sum_s=10.0)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 30, "context": 30 * 2000,
          "window_context": 30 * 512}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 30, "context": 30 * 3000,
          "window_context": 30 * 512}]])
    ops = []
    for step in (0.2, 0.25):
        ops += [[f"%paged_decode_attention.{i} = custom-call",
                 step + 0.001 * i, 0.0005] for i in range(8)]
        ops += [[f"%paged_window_decode_attention.{i} = custom-call",
                 step + 0.0006 + 0.001 * i, 0.0001] for i in range(8)]
    for chunk in (0.0, 0.1):
        ops += [[f"%paged_chunk_attention.{i} = custom-call",
                 chunk + 0.004 * i, 0.0004] for i in range(9)]
        ops += [[f"%ssm_scan.{i} = custom-call", chunk + 0.001 + 0.004 * i,
                 0.0003] for i in range(9)]
        # the op that takes a kernel's result names it too, and is no call
        ops.append(["%multiply.7 = f32[2,512,5120] multiply(f32[2,512,5120] "
                    "%ssm_scan.1, %broadcast.3)", chunk + 0.0021, 1e-7])
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.045],
                    ["jit__lambda(7)", 0.1, 0.060],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.016],
                    ["jit__paged_decode_fn(3)", 0.25, 0.016]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.03]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0,
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [1024, 512, 1536]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 3.0e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # a step reads 7.7 GB of weights; 16 ms
    assert read[STEP] == pytest.approx(100 * 2 * 3_852_562_944 / 819e9
                                       / 0.016)
    assert 55 < read[STEP] < 62
    # two programs of 1.07 chunks of 3.0 TFLOP needed over 105 ms
    assert read["step.prefill_mfu.reasoning"] == pytest.approx(
        100 * 2 * (160 / 150) * 3.0e12 / (0.105 * 197e12))
    # a global call: 75k context rows a step x 5120 B in 0.5 ms
    assert read[GLOBAL_CALL] == pytest.approx(
        100 * 75_000 * 5120 / 819e9 / 0.0005)
    # a window call: 30 x 512 rows x 5120 B in 0.1 ms
    assert read[WINDOW_CALL] == pytest.approx(
        100 * 30 * 512 * 5120 / 819e9 / 0.0001)
    # a scan call: 466.7 tokens in 1.07 rows, its bytes on the bus, 0.3 ms
    tokens, rows = 70_000 / 150, 160 / 150
    assert read[SCAN] == pytest.approx(
        100 * COUNTS.ssm_scan_bytes(CONF, tokens, rows) / 819e9 / 0.0003)
    assert 0 < read[SCAN] <= 100
    # the chunk attention calls: three prompts' needed attention over their
    # 6 chunks, x 2.13 chunks traced, over 7.2 ms of calls
    need = sum(COUNTS.chunk_attention_flops(CONF, n)
               for n in (1024, 512, 1536)) / 6 * 2 * 160 / 150
    assert read[CHUNK_CALLS] == pytest.approx(
        100 * need / (18 * 0.0004 * 197e12))
    assert read["kv.state_share_of_pool.reasoning"] == pytest.approx(
        100 * 103_219_200 / 2_976_317_440)
    assert 3.0 < read["kv.state_share_of_pool.reasoning"] < 4.0
    assert 50.0 < read["kv.window_share_of_pool.reasoning"] < 51.0
    assert read[TAIL] == pytest.approx(100 * 70 / 150)
    assert read["engine.decode_occupancy.reasoning"] == pytest.approx(
        100 * 90_000 / (3000 * 32))
    assert read["kv.preemptions.reasoning"] == 1.0
    assert read["engine.sched_busy_share_window.reasoning"] == 25.0


@pytest.mark.parametrize("name", READERS)
def test_reader_on_runs_without_samples_and_without_a_source(name):
    read = mf.load_layer_metric(name).read
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert {k: entry[k] for k in mf.load_layer_metric(name).DECLARATION} \
        == mf.load_layer_metric(name).DECLARATION
    # counters at rest, a trace that holds none of the programs: the
    # stated number (the pool's shares are constants of the engine)
    quiet = {**recorded_run(), **quiet_run("any.reasoning")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = {"kv.state_share_of_pool.reasoning": 12.5,
              "kv.window_share_of_pool.reasoning": 25.0}.get(name, 0.0)
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the PARENT's program with these files dropped in (it cannot build this
    # model; its engine has neither counter of the tail nor the planes by
    # kind): nothing or a number, never an exception
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        for key in ("kv_sequence_pool_bytes", "kv_window_pool_bytes",
                    "prefill_programs_with_end", "kv_layers_sharing"):
            part["engine"].pop(key, None)
    if name in ("kv.state_share_of_pool.reasoning",
                "kv.window_share_of_pool.reasoning", TAIL):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: a step that took exactly its
    weights' time on the bus, a call exactly its bytes' time."""
    run = recorded_run()
    least = COUNTS.decode_weight_bytes(CONF, 2) / 819e9
    floor = {"%paged_decode_attention": 75_000 * 5120 / 819e9,
             "%paged_window_decode_attention": 30 * 512 * 5120 / 819e9,
             "%ssm_scan": COUNTS.ssm_scan_bytes(
                 CONF, 70_000 / 150, 160 / 150) / 819e9}
    device = run["trace"]["devices"][0]
    device["modules"] = [m[:2] + [least] if "decode" in m[0] else m
                         for m in device["modules"]]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in (STEP, GLOBAL_CALL, WINDOW_CALL, SCAN):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-ssm")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"kv_sequence_pool_bytes", "kv_window_pool_bytes",
            "kv_global_pool_bytes", "kv_pool_bytes", "kv_bytes_per_token",
            "kv_layers_sharing", "prefill_chunks_dispatched",
            "prefill_programs_dispatched", "prefill_programs_with_end",
            "prefill_tokens_dispatched", "decode_steps_dispatched",
            "decode_tokens_emitted", "preemptions", "slots",
            "sched_host_busy_sum_s", "state_sequences_started"} \
        <= set(counters)
    counts = architecture.part(TINY, "counts")
    assert counters["kv_layers_sharing"] == 2
    assert counters["kv_bytes_per_token"] == counts.kv_bytes_per_token(
        TINY, 2)
    assert counters["kv_sequence_pool_bytes"] == engine.num_slots \
        * counts.state_bytes_per_sequence(TINY, 2)
    ring = engine._cfg_decode.window_ring_pages * engine.page_size
    assert counters["kv_window_pool_bytes"] == engine.num_slots \
        * counts.window_bytes_per_sequence(TINY, 2, ring)
    assert counters["kv_global_pool_bytes"] == engine._num_pages \
        * engine.page_size * counts.kv_bytes_per_token(TINY, 2)


def test_what_pr_47_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/phi-4-mini-flash.json",
                 "benchmark/configs/rehearsal-tiny-phi4flash.json",
                 "benchmark/traffic/batch-reasoning.json",
                 "benchmark/traffic/rehearsal-closed-ssm.json"]
                + [f"benchmark/architectures/phi4flash/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + len(READERS)] == READERS    # appended, in order
    assert all(n.split(".")[-1] != "reasoning" for n in names[:at])
    assert mf.cell(MANIFEST, CELL)["config"] == "phi-4-mini-flash"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
