"""The ``falcon-h1`` architecture and its cell
(``falcon-h1-34b.batch-assistant``): the cell's path rehearsed on the CPU at
tiny widths and judged ``correct`` against its own plain reference, which
walks the SSD recurrence TOKEN BY TOKEN, attends with one softmax over the
whole causal context and applies every multiplier where the published
forward has it (through ``engine_logits``' calls as they stand: ONE
page-table row of ``arange`` and no slot, from which a parallel layer finds
its sequence's state at ``row[0]``), the float8 control over its limit, a
reference of other equations far over it, ``counts.py`` against the numbers
reckoned by hand in ISSUE 50, the configuration file against the published
config, and each of the cell's ten readers on a recorded run and on a run
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
CELL = "falcon-h1-34b.batch-assistant"
REHEARSAL = "tiny-falconh1.rehearsal-closed-ssd"
CONF = mf.load_config(MANIFEST, "falcon-h1-34b")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-falconh1.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
CHUNK = "kernel.ssd_chunk_roofline_share.assistant"
STEP_KERNEL = "kernel.ssd_step_bw_share.assistant"
DECODE_CALL = "kernel.paged_decode_attention_bw_share.assistant"
CHUNK_CALLS = "kernel.paged_chunk_attention_mfu.assistant"
STEP = "step.decode_weight_bw_share.assistant"
COUNTER_READERS = ["kv.state_share_of_pool.assistant",
                   "engine.decode_occupancy.assistant",
                   "kv.preemptions.assistant",
                   "engine.sched_busy_share_window.assistant"]
READERS = [CHUNK, STEP_KERNEL, DECODE_CALL, CHUNK_CALLS, STEP,
           "step.prefill_mfu.assistant"] + COUNTER_READERS
with open("/opt/skills/guides/model-configs/architectures.jsonl") as _f:
    # config.json of tiiuae/Falcon-H1-34B-Instruct, as the catalog beside the
    # model-configs guide gives it
    PUBLISHED = next(json.loads(line) for line in _f
                     if '"Falcon-H1-34B-Instruct"' in line)


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 50, seconds=2.0,
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
        assert 0.0 < value["engine.decode_occupancy.assistant"] <= 100.0
        assert value["kv.preemptions.assistant"] >= 0.0
        # three layers' rows in 16 pages of 16 tokens x 2 x 2 heads of 16 in
        # bfloat16; three layers' entries for two slots: [4, 32, 16] float32
        # and [3, 192] bfloat16
        rows = 3 * 16 * 16 * 2 * 2 * 16 * 2
        state = 3 * 2 * (4 * 32 * 16 * 4 + 3 * 192 * 2)
        assert value["kv.state_share_of_pool.assistant"] == pytest.approx(
            100 * state / (rows + state))
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (parallel layers refuse int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed-ssd")
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
    "no decay", "no convolution", "no ssd branch", "no attention branch",
    "keys unscaled", "gate after the norm", "one group"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree under a reference whose state never decays, whose
    convolution sees the current position alone, that lacks a branch, whose
    keys skip their multiplier, whose gate follows the group norm, or whose
    heads all read the first group's ``B`` and ``C``: not the model, and the
    comparison says so."""
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 100, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    conf, tree, blind = TINY, jax.tree.map(lambda a: a, params), None
    mixer = tree["layers"]["parallel"]
    if what == "no decay":
        mixer["a_log"] = jnp.full_like(mixer["a_log"], -30.0)
    elif what == "no convolution":
        mixer["conv"] = mixer["conv"].at[:, :-1].set(0)
    elif what == "no ssd branch":
        blind = "ssd"
    elif what == "no attention branch":
        blind = "attention"
    elif what == "keys unscaled":
        conf = {**TINY, "key_multiplier": 1.0}
    elif what == "gate after the norm":
        # y * SiLU(z) normed is not RMSNorm(y) * SiLU(z): the other order
        # is a gate of ones in front of the norm and the gate behind it
        mixer["w_z"] = jnp.zeros_like(mixer["w_z"])
    else:
        gn = TINY["mamba_n_groups"] * TINY["mamba_d_state"]
        e, n = TINY["mamba_d_ssm"], TINY["mamba_d_state"]
        w = mixer["w_xbc"]
        for at in (e, e + gn):          # B's, then C's columns
            w = w.at[:, :, at + n:at + gn].set(w[:, :, at:at + n])
        mixer["w_xbc"] = w
    fn = jax.jit(lambda p, t: ref.logits(p, t, conf, last=64, blind=blind))
    with jax.default_matmul_precision("highest"):
        got = fn(tree, jnp.asarray(tokens))
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
    """One token of the recurrence, by hand, on a state laid [H, P, N] with
    two heads in two groups; the reference imports nothing of the program
    and names no blocked form."""
    ref = architecture.part(TINY, "reference")
    step = ref.ssd_token(jnp.asarray([-1.0, -2.0]), jnp.asarray([3.0, 5.0]),
                         per=1)
    x = jnp.asarray([[2.0, 1.0], [1.0, -1.0]])               # [H=2, P=2]
    dt = jnp.asarray([0.5, 0.25])
    b = jnp.asarray([[1.0, -1.0], [2.0, 0.0]])               # [G=2, N=2]
    c = jnp.asarray([[2.0, 3.0], [1.0, 1.0]])
    s, y = step(jnp.ones((2, 2, 2)), (x, dt, b, c))
    decay = np.exp(np.asarray([-0.5, -0.5]))
    want = decay[:, None, None] * np.ones((2, 2, 2)) + np.einsum(
        "hp,hn->hpn", np.asarray(dt)[:, None] * np.asarray(x), np.asarray(b))
    np.testing.assert_allclose(s, want, rtol=1e-6)
    np.testing.assert_allclose(
        y, np.einsum("hpn,hn->hp", want, np.asarray(c))
        + np.asarray([3.0, 5.0])[:, None] * np.asarray(x), rtol=1e-6)
    with open(ref.__file__) as f:
        src = f.read()
    assert "kubeflow_tpu" not in src.split('"""', 2)[2]
    assert "jax.lax.scan" in src and "associative_scan" not in src
    assert "cumsum" not in src


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, v = 5120, 261120
    part = COUNTS.params_by_part(CONF)
    attn = d * (2560 + 512 + 512) + 2560 * d
    assert COUNTS.attention_params(CONF) == attn == 31_457_280
    in_proj = d * 9248
    assert in_proj == d * (4096 + 5120 + 32) == 47_349_760
    mixer = in_proj + 4096 * d + (5120 * 4 + 5120) + 96 + 4096
    assert COUNTS.ssd_params(CONF) == mixer == 68_351_072
    assert COUNTS.ssd_matmul_params(CONF) == in_proj + 20_971_520
    mlp = 3 * d * 21504
    assert COUNTS.mlp_params(CONF) == mlp == 330_301_440
    assert COUNTS.layer_params(CONF) == attn + mixer + mlp + 2 * d \
        == 430_120_032
    assert part["embedding"] + part["head"] == 2 * v * d == 2_673_868_800
    assert part["norms"] == (2 * 5 + 1) * d       # the final norm counted
    total = COUNTS.params_total(CONF)
    assert total == sum(part.values()) == 5 * 430_120_032 + 2_673_868_800 \
        + 5120 == 4_824_474_080
    assert round(total * 2 / 1e9, 2) == 9.65
    assert COUNTS.params_total({**CONF, "num_hidden_layers": 4}) \
        == 4_394_354_048                          # fallback 2's floor
    assert COUNTS.params_total({**CONF, "num_hidden_layers": 72}) \
        == 72 * 430_120_032 + 2_673_868_800 + 5120    # 33.64 B
    # a token keeps 2 x 4 x 128 values in EVERY layer; a sequence [32, 256,
    # 128] float32 and [3, 5120] bfloat16 in every layer
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 10_240
    assert COUNTS.state_bytes_per_sequence(CONF, 2) == 5 * (
        4_194_304 + 30_720) == 21_125_120
    # the cell's pool: 48 entries and 624 pages of 128 tokens
    assert 48 * 21_125_120 == 1_014_005_760
    assert 624 * 128 * 10_240 == 817_889_280
    # the program counts the same parameters, the tree holds them
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs_here():
    d, v = 5120, 261120
    per_token = COUNTS.layer_matmul_params(CONF)
    assert per_token == 31_457_280 + 47_349_760 + 20_971_520 + 330_301_440
    assert COUNTS.causal_pairs(512, 1024) == 512 * 1024 + 512 * 513 / 2
    # the recurrence: 5 a head, state and value: 5.24 MFLOP a token a layer,
    # of a layer's 865
    assert COUNTS.ssd_chunk_flops(CONF, 1) == 5.0 * 32 * 128 * 256 \
        == 5_242_880
    assert round((2 * per_token + 5_242_880) / 1e6) == 865
    n = 576
    want = (2.0 * 5 * per_token * n + 5 * 4.0 * 128 * 20 * n * (n + 1) / 2
            + 5 * 5_242_880 * n + 2.0 * d * v)
    assert COUNTS.prefill_flops(CONF, n) == want        # the head ONCE
    assert COUNTS.chunk_attention_flops(CONF, n) \
        == 5 * 4.0 * 128 * 20 * n * (n + 1) / 2
    # a step's weights: five layers, the final norm, the head; no embedding
    assert COUNTS.decode_weight_bytes(CONF, 2, 48) \
        == COUNTS.decode_weight_bytes(CONF, 2, 1) \
        == 2.0 * (5 * 430_120_032 + 5120 + v * d)
    assert round(COUNTS.decode_weight_bytes(CONF, 2) / 1e9, 2) == 6.98
    assert round(2 * v * d / COUNTS.decode_weight_bytes(CONF, 2), 2) == 0.38
    # one call a layer a step; 8.4 MB a stream a layer
    assert COUNTS.decode_attention_bytes(CONF, 1000, 2) == 1000 * 2048
    assert COUNTS.ssd_step_bytes(CONF, 1) == 2 * 4_194_304 + 2 * 30_720
    assert round(5 * COUNTS.ssd_step_bytes(CONF, 48) / 1e9, 2) == 2.03
    # a chunk call of one row of 512: x dt in, y out in float32, B, C and a
    # log-decay a head a token; the state twice a row
    assert COUNTS.ssd_chunk_bytes(CONF, 512, 1) == 512 * (
        4096 * 6 + 2 * 512 * 2 + 128) + 2 * 4_194_304


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "falcon-h1-34b")
    assert entry["reduced"] == sorted(CONF["reduced"]) \
        == ["num_hidden_layers"]
    assert entry["source"] == CONF["source"] == PUBLISHED["source_url"]
    for key, value in PUBLISHED["config"].items():
        if key in CONF["reduced"]:
            assert CONF["reduced"][key]["from"] == value == 72
            assert CONF["reduced"][key]["to"] == CONF[key] == 5
        else:
            assert key in CONF and CONF[key] == value, key
    assert CONF["num_hidden_layers_published"] == 72
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    for item in ("weights", "ssm_init", "rope", "mamba_use_mlp",
                 "in_projection", "gated_norm", "attn_layer_indices",
                 "departures"):
        assert item in CONF["assumed"]
    assert "arXiv:2405.21060" in CONF["assumed"]["ssm_init"]
    assert "1 / (multiplier x sqrt(fan_in))" in CONF["assumed"]["weights"]
    assert CONF["architecture"] == "falcon-h1" and CONF["chips"] == 1
    assert "five consecutive layers of a pipelined bfloat16 replica" \
        in CONF["deployment"]
    longest = max(plen + n for plen, n in CONF["correctness"]["sequences"])
    assert longest == CONF["program"]["overrides"]["max_seq_len"]
    assert CONF["correctness"]["limits_from"].startswith("PERF.md")


def test_the_manifests_rules_for_a_configuration_hold_for_this_one():
    entry = mf.config_entry(MANIFEST, "falcon-h1-34b")
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
        == (5, 5120, 20, 4, 128, 21504, 261120)
    assert cfg.kinds == ("parallel",) * 5 and not cfg.tie_embeddings
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_groups,
            cfg.ssd_chunk, cfg.conv_taps) == (32, 128, 256, 2, 128, 4)
    assert cfg.max_seq_len == 2176 and cfg.rope_theta == 1e11
    for key, other in (("hidden_size", 4096), ("num_hidden_layers", 4),
                       ("num_attention_heads", 10),
                       ("num_key_value_heads", 2), ("head_dim", 64),
                       ("intermediate_size", 8192), ("vocab_size", 32640),
                       ("rms_norm_eps", 1e-6), ("rope_theta", 1e4),
                       ("tie_word_embeddings", True), ("mlp_bias", True),
                       ("mamba_d_ssm", 2048), ("mamba_d_state", 128),
                       ("mamba_n_groups", 1), ("mamba_n_heads", 16),
                       ("mamba_d_conv", 3), ("mamba_chunk_size", 64),
                       ("mamba_norm_before_gate", True),
                       ("embedding_multiplier", 1.0),
                       ("lm_head_multiplier", 1.0),
                       ("attention_out_multiplier", 1.0),
                       ("key_multiplier", 1.0), ("ssm_in_multiplier", 1.0),
                       ("ssm_out_multiplier", 1.0),
                       ("ssm_multipliers", [1.0] * 5),
                       ("mlp_multipliers", [1.0, 1.0])):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="falcon-h1 is"):
        program.program_config(CONF, use_rope=False)


def test_the_seeded_tree_is_the_programs_and_undoes_the_multipliers():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert got["embed"].shape == (261120, 5120)
    assert got["lm_head"].shape == (5120, 261120)
    mixer = got["layers"]["parallel"]
    assert mixer["w_z"].shape == (5, 5120, 4096)
    assert mixer["w_xbc"].shape == (5, 5120, 5120)
    assert mixer["w_dt"].shape == (5, 5120, 32)
    assert mixer["wk"].shape == (5, 5120, 4, 128)
    tiny = make_params(TINY, 3, "float32")
    mixer = tiny["layers"]["parallel"]
    a = np.exp(np.asarray(mixer["a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 0.99e-3 <= step.min() and step.max() <= 1.01e-1
    assert float(jnp.abs(mixer["d_skip"] - 1).max()) == 0.0
    # every matrix a multiplier precedes or follows is drawn at 1 /
    # (multiplier x sqrt(fan_in)): times its multiplier, the plain draw's
    # deviation
    d, m, e = 64, 160, 64
    gate_m, down_m = TINY["mlp_multipliers"]
    m_z, m_x, m_b, m_c, m_dt = TINY["ssm_multipliers"]
    s_in = TINY["ssm_in_multiplier"]
    gn = TINY["mamba_n_groups"] * TINY["mamba_d_state"]
    xbc = np.asarray(mixer["w_xbc"])
    for leaf, scale, fan in (
            (tiny["embed"], TINY["embedding_multiplier"], 1),
            (tiny["lm_head"], TINY["lm_head_multiplier"], d),
            (mixer["wq"], TINY["attention_in_multiplier"], d),
            (mixer["wk"], TINY["attention_in_multiplier"]
             * TINY["key_multiplier"], d),
            (mixer["wo"], TINY["attention_out_multiplier"], 64),
            (mixer["w_z"], s_in * m_z, d),
            (xbc[..., :e], s_in * m_x, d),
            (xbc[..., e:e + gn], s_in * m_b, d),
            (xbc[..., e + gn:], s_in * m_c, d),
            (mixer["w_dt"], s_in * m_dt, d),
            (mixer["w_out"], TINY["ssm_out_multiplier"], e),
            (tiny["layers"]["mlp"]["gate"], gate_m, d),
            (tiny["layers"]["mlp"]["up"], 1.0, d),
            (tiny["layers"]["mlp"]["down"], down_m, m)):
        got_std = float(np.std(np.asarray(leaf))) * scale * fan ** 0.5
        assert 0.85 < got_std < 1.15, (scale, fan, got_std)


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 48
    assert e["enable_prefix_caching"] is False      # the cell shares nothing
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    assert traffic["prompt_len"]["dist"] == traffic["output_len"]["dist"] \
        == "uniform"
    mpp = e["max_seq_len"] // e["page_size"]
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    pages = -(-longest // e["page_size"])
    assert mpp == 17 and longest <= e["max_seq_len"]     # fallback 2
    # 48 whole contexts of the NAMED sizes' longest (1536 tokens, 12 pages)
    # and 48 to spare: no preemption under fallback 1's sizes either
    assert e["max_pages"] == 48 * 12 + 48 == 624 >= 48 * pages + 48
    assert (traffic["prompt_len"]["min"] + traffic["prompt_len"]["max"],
            traffic["output_len"]["min"] + traffic["output_len"]["max"]) \
        == (128 + 1024, 128 + 512)                      # the named means
    need = required_programs(traffic, BatchingSpec(**e))
    # The warm-up's first prompt walks every chunk start of the longest
    # context alone, its second group sends two prompts at once
    assert traffic["warmup"][0][0][0] >= longest - 512
    assert len(traffic["warmup"][1]) == 2
    assert {f"paged_decode[{k},greedy]" for k in (1,)} <= need
    assert {f"paged_chunk_prefill[1x512,{b}]" for b in (4, 8, 16)} \
        == {n for n in need if n.startswith("paged_chunk_prefill")}


# -- the ten readers ------------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 2400 decode steps over 47 live streams, 440 chunk
    programs that carried 440 chunks of 160k tokens; 3 traced seconds holding
    two chunk programs (30 and 34 ms), a cache copy, two decode programs of
    one step each (17 ms) over 47 streams at 700 and 900 context rows a
    stream, in each FIVE calls of the decode kernel (0.2 ms) and five of
    ``ssd_step`` (0.65 ms), and in each chunk program five chunk attention
    calls (0.5 ms) and five ``ssd_chunk`` calls (0.1 ms)."""
    run = quiet_run("any.assistant")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(
            slots=48, kv_sequence_pool_bytes=1_014_005_760,
            kv_global_pool_bytes=817_889_280, kv_window_pool_bytes=0,
            kv_pool_bytes=1_831_895_040)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=2400, decode_tokens_emitted=112_800,
        prefill_programs_dispatched=440, prefill_chunks_dispatched=440,
        prefill_tokens_dispatched=160_000, preemptions=1,
        sched_host_busy_sum_s=10.0)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 47, "context": 47 * 700}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 47, "context": 47 * 900}]])
    ops = []
    for step in (0.2, 0.25):
        ops += [[f"%paged_decode_attention.{i} = custom-call",
                 step + 0.002 * i, 0.0002] for i in range(5)]
        ops += [[f"%ssd_step.{i} = custom-call", step + 0.0005 + 0.002 * i,
                 0.00065] for i in range(5)]
    for chunk in (0.0, 0.1):
        ops += [[f"%paged_chunk_attention.{i} = custom-call",
                 chunk + 0.004 * i, 0.0005] for i in range(5)]
        ops += [[f"%ssd_chunk.{i} = custom-call", chunk + 0.001 + 0.004 * i,
                 0.0001] for i in range(5)]
        # the op that takes a kernel's result names it too, and is no call
        ops.append(["%add.7 = f32[1,32,512,128] add(f32[1,32,512,128] "
                    "%ssd_chunk.1, %broadcast.3)", chunk + 0.0021, 1e-7])
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.030],
                    ["jit__lambda(7)", 0.1, 0.034],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.017],
                    ["jit__paged_decode_fn(3)", 0.25, 0.017]],
        "ops": ops + [["%fusion.12 = fusion", 0.0, 0.02]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "window_s": 40.0,
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": [],
                        "prompt_lens_in_window": [1024, 512, 200]},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 1.6e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # a step reads 6.98 GB of weights; 17 ms
    assert read[STEP] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2) / 819e9 / 0.017)
    assert 48 < read[STEP] < 52
    # two programs of one chunk of 1.6 TFLOP needed over 64 ms
    assert read["step.prefill_mfu.assistant"] == pytest.approx(
        100 * 2 * 1.6e12 / (0.064 * 197e12))
    # a decode call: 37.6k context rows a step x 2048 B in 0.2 ms
    assert read[DECODE_CALL] == pytest.approx(
        100 * 47 * 800 * 2048 / 819e9 / 0.0002)
    # an ssd_step call: 47 streams' states in and out, 0.65 ms
    assert read[STEP_KERNEL] == pytest.approx(
        100 * COUNTS.ssd_step_bytes(CONF, 47) / 819e9 / 0.00065)
    assert 70 < read[STEP_KERNEL] < 80
    # an ssd_chunk call: 363.6 tokens in one row: its bytes on the bus (the
    # nearer roof at one row: 10.9 MB against 1.9 GFLOP), 0.1 ms
    tokens = 160_000 / 440
    assert COUNTS.ssd_chunk_bytes(CONF, tokens, 1) / 819e9 \
        > COUNTS.ssd_chunk_flops(CONF, tokens) / 197e12
    assert read[CHUNK] == pytest.approx(
        100 * COUNTS.ssd_chunk_bytes(CONF, tokens, 1) / 819e9 / 0.0001)
    assert 0 < read[CHUNK] <= 100
    # the chunk attention calls: three prompts' needed attention over their
    # 4 chunks, x 2 chunks traced, over 5 ms of calls
    need = sum(COUNTS.chunk_attention_flops(CONF, n)
               for n in (1024, 512, 200)) / 4 * 2
    assert read[CHUNK_CALLS] == pytest.approx(
        100 * need / (10 * 0.0005 * 197e12))
    assert read["kv.state_share_of_pool.assistant"] == pytest.approx(
        100 * 1_014_005_760 / 1_831_895_040)
    assert 55.0 < read["kv.state_share_of_pool.assistant"] < 56.0
    assert read["engine.decode_occupancy.assistant"] == pytest.approx(
        100 * 112_800 / (2400 * 48))
    assert read["kv.preemptions.assistant"] == 1.0
    assert read["engine.sched_busy_share_window.assistant"] == 25.0


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
    quiet = {**recorded_run(), **quiet_run("any.assistant")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = {"kv.state_share_of_pool.assistant": 12.5}.get(name, 0.0)
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the PARENT's program with these files dropped in (it cannot build this
    # model; an engine without the planes by kind): nothing or a number,
    # never an exception
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        part["engine"].pop("kv_sequence_pool_bytes", None)
    if name == "kv.state_share_of_pool.assistant":
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: a step that took exactly its
    weights' time on the bus, a call exactly its bytes' time."""
    run = recorded_run()
    least = COUNTS.decode_weight_bytes(CONF, 2) / 819e9
    floor = {"%paged_decode_attention": 47 * 800 * 2048 / 819e9,
             "%ssd_step": COUNTS.ssd_step_bytes(CONF, 47) / 819e9,
             "%ssd_chunk": COUNTS.ssd_chunk_bytes(
                 CONF, 160_000 / 440, 1) / 819e9}
    device = run["trace"]["devices"][0]
    device["modules"] = [m[:2] + [least] if "decode" in m[0] else m
                         for m in device["modules"]]
    device["ops"] = [
        o[:2] + [floor[o[0].split(".")[0]]]
        if o[0].split(".")[0] in floor and "custom-call" in o[0] else o
        for o in device["ops"]]
    for name in (STEP, DECODE_CALL, STEP_KERNEL, CHUNK):
        assert mf.load_layer_metric(name).read(run) == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed-ssd")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"kv_sequence_pool_bytes", "kv_global_pool_bytes",
            "kv_pool_bytes", "kv_bytes_per_token",
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
    assert counters["kv_global_pool_bytes"] == engine._num_pages \
        * engine.page_size * counts.kv_bytes_per_token(TINY, 2)
    assert counters["kv_pool_bytes"] == counters["kv_sequence_pool_bytes"] \
        + counters["kv_global_pool_bytes"]


def test_what_pr_50_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/falcon-h1-34b.json",
                 "benchmark/configs/rehearsal-tiny-falconh1.json",
                 "benchmark/traffic/batch-assistant.json",
                 "benchmark/traffic/rehearsal-closed-ssd.json"]
                + [f"benchmark/architectures/falcon-h1/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(READERS[0])
    assert sorted(names[at:at + len(READERS)]) == sorted(READERS)
    assert all(n.split(".")[-1] != "assistant" for n in names[:at])
    assert mf.cell(MANIFEST, CELL)["config"] == "falcon-h1-34b"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert len(MANIFEST["workloads"]) == len(MANIFEST["configs"]) == 9
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
