"""The ``lfm2-moe`` architecture and its cell
(``lfm2-24b-a2b.batch-longanswer``): the cell's path rehearsed on the CPU at
tiny widths and judged ``correct`` against its own plain reference (through
``engine_logits``' calls as they stand: a chunk program that is handed a
page-table row and no slot reaches the conv layers' state through that row),
the float8 control over its limit, a reference of other equations far over
it, ``counts.py`` against the numbers reckoned by hand in ISSUE 35, the
configuration file against the published config, and each of the cell's
seven readers on a recorded run and on a run without samples.

The literal tables of the older files of this suite get this cell's entries
from ``tests/conftest.py`` (outside the benchmark's paths)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import architecture, control, correctness
from benchmark import manifest as mf
from benchmark.run import run_cell
from benchmark.weights import make_params, param_shapes
from test_benchmark_program_readers import quiet_run
from test_benchmark_rehearsal_cpu import check_line, rehearsal_manifest

MANIFEST = mf.load_manifest()
CELL = "lfm2-24b-a2b.batch-longanswer"
REHEARSAL = "tiny-lfm2.rehearsal-closed"
CONF = mf.load_config(MANIFEST, "lfm2-24b-a2b")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-lfm2.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
KERNEL_READER = "kernel.paged_packed_decode_attention_bw_share.longanswer"
READERS = ["step.decode_weight_bw_share.longanswer",
           "step.prefill_mfu.longanswer",
           "kv.state_share_of_pool.longanswer",
           "engine.decode_occupancy.longanswer", "kv.preemptions.longanswer",
           "engine.sched_busy_share.longanswer", KERNEL_READER]
# config.json of LiquidAI/LFM2-24B-A2B, as the catalog beside the
# model-configs guide gives it (``layer_types``: conv, conv, then
# full_attention at 2, 6, ... 38 and conv everywhere else)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["full_attention" if i % 4 == 2 else "conv"
                    for i in range(40)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 43, seconds=2.0,
                    trace=trace, allow_cpu=True)
    # what the CPU's trace can feed: the counters and the host's spans
    counters = {"kv.state_share_of_pool.longanswer",
                "engine.decode_occupancy.longanswer",
                "kv.preemptions.longanswer",
                "engine.sched_busy_share.longanswer"}
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
        assert 0.0 < value["engine.decode_occupancy.longanswer"] <= 100.0
        assert value["kv.preemptions.longanswer"] >= 0.0
        assert 0.0 < value["engine.sched_busy_share.longanswer"] <= 100.0
        # seven conv layers' two 64-wide rows a page beside two attention
        # layers' 16 x 2 x 32 values a page
        assert value["kv.state_share_of_pool.longanswer"] == pytest.approx(
            100 * 7 * 2 * 64 / (7 * 2 * 64 + 2 * 2 * 16 * 32))
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (packed K/V rows refuse int8 KV)."""
    limits = TINY["correctness"]["limits"]
    traffic = mf.load_traffic("rehearsal-closed")
    sound, low = [], []
    for seed in (5, 2**31 + 6, 77):
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


def _without(params, group, *path, scale=0.0):
    """``params`` with one leaf of a group scaled (a tree of new dicts)."""
    def walk(node, keys):
        if not keys:
            return jax.tree.map(lambda x: x * scale, node)
        return {**node, keys[0]: walk(node[keys[0]], keys[1:])}

    return {**params, group: walk(params[group], path)}


@pytest.mark.parametrize("what", ["no expert bias", "no k norm weight",
                                  "no conv history", "no dense conv"])
def test_a_reference_of_other_equations_is_far_over_the_limit(what):
    """The same tree with the bias dropped from the reference's choice, the
    key norm's weights halved, the convolution cut to its current tap, or
    the leading layer's operator left out: not the model, and the
    comparison says so."""
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 64, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]
    taps = params["layers"]["conv"]["taps"]
    other = {
        "no expert bias": _without(params, "layers", "mlp", "router_bias"),
        "no k norm weight": _without(params, "layers", "attn", "k_norm",
                                     scale=0.5),
        "no conv history": {**params, "layers": {
            **params["layers"], "conv": {
                **params["layers"]["conv"],
                "taps": taps.at[:, :-1].set(0)}}},
        "no dense conv": _without(params, "dense_layers", "conv", "wout"),
    }[what]
    got = correctness.reference_logits(other, tokens, TINY, last=64)
    err = float(jnp.median(correctness.position_errors(got, own)))
    assert err > 3 * limit, (what, err)
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


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, v = 2048, 65536
    conv = 4 * d * d + 3 * d
    assert conv == COUNTS.conv_params(CONF) == 16_783_360
    attention = 2 * d * 32 * 64 + 2 * d * 8 * 64 + 2 * 64
    assert attention == COUNTS.attention_params(CONF) == 10_485_888
    expert = 3 * d * 1536
    assert expert == COUNTS.expert_params_one(CONF) == 9_437_184
    experts = d * 64 + 64 + 64 * expert                 # router, bias, all 64
    dense_layer = conv + 3 * d * 11776 + 2 * d
    assert dense_layer == 89_139_200
    conv_layer = conv + experts + 2 * d
    attention_layer = attention + experts + 2 * d
    assert (conv_layer, attention_layer) == (620_898_368, 614_600_896)
    assert v * d == 134_217_728                         # tied: held once
    total = dense_layer + 6 * conv_layer + 2 * attention_layer + v * d + d
    assert total == COUNTS.params_total(CONF) == 5_177_950_976  # 5.18 B
    assert round(total * 2 / 2**30, 2) == 9.64
    # an attention layer holds 2 x 8 x 64 values a token, a conv layer none
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 2 * 2048 == 4096
    assert COUNTS.state_bytes_per_sequence(CONF, 2) == 7 * 2 * 2048 * 2
    # the cell's pool: 1600 pages of 128 tokens
    assert 1600 * 128 * 4096 == 838_860_800
    # the program counts the same parameters
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs():
    d, v = 2048, 65536
    matmuls = (7 * 4 * d * d + 2 * (2 * d * 2048 + 2 * d * 512)
               + 3 * d * 11776 + 8 * (d * 64 + 4 * 3 * d * 1536))
    assert COUNTS.layers_matmul_params_active(CONF) == matmuls == 513_802_240
    per_pair = COUNTS.attention_flops_causal(CONF, 1)
    assert per_pair == 4 * 64 * 32 * 2                  # two layers attend
    assert COUNTS.conv_flops_per_token(CONF) == 7 * d * 8
    n = 2048
    want = ((2.0 * matmuls + 7 * d * 8) * n + per_pair * n * (n + 1) / 2
            + 2.0 * d * v)                              # the head ONCE
    assert COUNTS.prefill_flops(CONF, n) == want
    assert COUNTS.train_flops_per_token(CONF, 4096) == (
        6.0 * (matmuls + d * v) + 3.0 * 7 * d * 8
        + 3.0 * per_pair * 4097 / 2)
    least = COUNTS.decode_weight_bytes(CONF, 2)
    assert least == 2.0 * (
        matmuls + 7 * 3 * d + 2 * 128 + 9 * 2 * d + 8 * 64 + d * v + d)
    # one token's experts are an eighth of what a full batch reads
    assert least < COUNTS.resident_weight_bytes(CONF, 2) / 7
    assert COUNTS.resident_weight_bytes(CONF, 2) == 2.0 * 5_177_950_976
    assert COUNTS.packed_decode_bytes(CONF, 1000, 2) == 1000 * 2048
    assert COUNTS.packed_decode_flops(CONF, 1) == 32 * 4 * 64
    # the kernel is bound by the bus even at the whole rows it multiplies
    assert COUNTS.packed_decode_bytes(CONF, 1, 2) / PEAKS["hbm_bytes_per_s"] \
        > 8 * COUNTS.packed_decode_flops(CONF, 1) / PEAKS["bf16_flops"]


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "lfm2-24b-a2b")
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) == [
        "num_dense_layers", "num_hidden_layers"]
    assert entry["source"] == CONF["source"]
    for key, value in PUBLISHED.items():
        if key in CONF["reduced"]:
            assert CONF["reduced"][key]["from"] == value
            assert CONF["reduced"][key]["to"] == CONF[key] != value
        else:
            assert CONF[key] == value, key
    # the layers held are published layers 1-9: a dense conv layer and two
    # whole periods
    assert CONF["layer_types_held"] == PUBLISHED["layer_types"][1:10] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"]
    assert len(CONF["layer_types_held"]) == CONF["num_hidden_layers"]
    assert CONF["num_hidden_layers"] - CONF["num_dense_layers"] >= 4
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    assert "tie_word_embeddings" in CONF["assumed"]
    assert CONF["architecture"] == "lfm2-moe" and CONF["chips"] == 1
    assert any(plen + n >= 3072 for plen, n
               in CONF["correctness"]["sequences"])


def test_the_programs_config_is_held_against_the_file():
    program = architecture.part(CONF, "program")
    cfg = program.program_config(CONF)
    assert (cfg.n_layers, cfg.leading_dense_layers, cfg.num_experts,
            cfg.shared_experts, cfg.experts_per_token) == (9, 1, 64, 0, 4)
    assert cfg.kinds.count("attention") == 2 and cfg.kinds[0] == "conv"
    assert cfg.qk_norm and cfg.kv_heads_packed and cfg.head_dim == 64
    assert cfg.tie_embeddings and cfg.router_norm_eps == 1e-6
    for key, other in (("num_experts", 32), ("num_dense_layers", 2),
                       ("num_hidden_layers", 10), ("conv_L_cache", 4),
                       ("conv_bias", True), ("routed_scaling_factor", 2.5),
                       ("use_expert_bias", False), ("norm_topk_prob", False),
                       ("num_key_value_heads", 4),
                       ("tie_word_embeddings", False),
                       ("layer_types_held", ["conv"] * 9)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})
    with pytest.raises(mf.ManifestError, match="rope_parameters"):
        program.program_config({**CONF, "rope_parameters": {
            "rope_theta": 10000, "rope_type": "default"}})


def test_the_seeded_tree_is_the_programs_at_the_published_widths():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert "lm_head" not in got
    assert got["layers"]["mlp"]["router_bias"].dtype == "float32"
    tiny = make_params(TINY, 3, "bfloat16")
    assert float(abs(tiny["layers"]["mlp"]["router_bias"]).min()) > 0


def test_the_traffic_reaches_every_program_the_window_can_meet():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.paged import context_bucket

    from benchmark.serving import required_programs

    cell = mf.cell(MANIFEST, CELL)
    traffic = mf.load_traffic(cell["traffic"])
    e = traffic["engine"]
    assert traffic["kind"] == "closed_loop" and cell["chips"] == 1
    assert traffic["clients"] == e["max_batch_size"] == 64
    # a window completes about ``pool`` requests, so every seed serves the
    # same multiset of sizes in another order (PERF.md, PR 32's refusal)
    assert traffic["pool"] % 64 == 0 and traffic["pool"] >= 64
    mpp = e["max_seq_len"] // e["page_size"]
    assert mpp == 25 and e["max_pages"] == 64 * mpp        # no preemption
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
    assert need == reached
    assert f"paged_chunk_prefill[1x512,{mpp}]" in need


# -- the seven readers ---------------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 3000 decode steps over 60 live slots, 300 chunk programs
    that carried 540 chunks; 3 traced seconds holding two chunk prefills (40
    and 60 ms), a cache copy, two decode programs of one step each (13 ms)
    over 60 slots at 1500 and 1700 context rows a slot, and two calls of
    the packed-row kernel a step (an attention layer each) at 0.5 ms."""
    run = quiet_run("any.longanswer")
    for part in (run["counters_before"], run["counters_after"]):
        part["engine"].update(slots=64, state_pool_bytes=91_750_400,
                              kv_pool_bytes=930_611_200)
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=3000, decode_tokens_emitted=180_000,
        prefill_programs_dispatched=300, prefill_chunks_dispatched=540,
        preemptions=1, state_tail_writes=2000)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 60, "context": 60 * 1500}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 60, "context": 60 * 1700}]])
    calls = [[f"%paged_packed_decode_attention.{i % 2} = custom-call",
              0.2 + 0.05 * (i // 2) + 0.0003 * (i % 2), 0.0005]
             for i in range(4)]
    # the op that takes the kernel's result names it too, and is no call
    takers = [["%multiply.7 = bf16[64,32,512] multiply(bf16[64,32,512] "
               "%paged_packed_decode_attention.1, %broadcast.3)",
               c[1] + c[2], 1e-7] for c in calls]
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.040],
                    ["jit__lambda(7)", 0.1, 0.060],
                    ["jit__lambda(9)", 0.17, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.013],
                    ["jit__paged_decode_fn(3)", 0.25, 0.013]],
        "ops": calls + takers + [["%fusion.12 = fusion", 0.0, 0.03]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "loadgen": {"late_ms": [], "ttft_ms": [], "itl_ms": []},
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 0.5e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # a step's 1.296 GB of weights (one token's experts) over 819 GB/s in
    # the 13 ms a step took: two kernel calls a program over two layers
    assert read["step.decode_weight_bw_share.longanswer"] == pytest.approx(
        100 * COUNTS.decode_weight_bytes(CONF, 2) / 819e9 / 0.013)
    assert 11 < read["step.decode_weight_bw_share.longanswer"] < 13
    # two programs of 1.8 chunks of 0.5 TFLOP needed over 100 ms
    assert read["step.prefill_mfu.longanswer"] == pytest.approx(
        100 * 2 * 1.8 * 0.5e12 / (0.100 * 197e12))
    assert read["kv.state_share_of_pool.longanswer"] == pytest.approx(
        100 * 91_750_400 / 930_611_200)
    assert read["engine.decode_occupancy.longanswer"] == pytest.approx(
        100 * 180_000 / (3000 * 64))
    assert read["kv.preemptions.longanswer"] == 1.0
    # the scheduler's 61 ms stretch less the 10 ms it waited in the fetch
    assert read["engine.sched_busy_share.longanswer"] == pytest.approx(
        100 * 0.051 / 0.061)
    # the traced rounds' 96k context rows a step of 2048 bytes over 819
    # GB/s, in the traced calls' mean time
    assert read[KERNEL_READER] == pytest.approx(
        100 * 96_000 * 2048 / 819e9 / 0.0005)
    assert 0 < read[KERNEL_READER] <= 100


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
    quiet = {**recorded_run(), **quiet_run("any.longanswer")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    stated = 12.5 if name.startswith("kv.state_share") else 0.0
    assert read(quiet) == stated
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # the parent's program with these files dropped in: its engine has no
    # state planes and no such counter
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        part["engine"].pop("state_pool_bytes")
    if name.startswith("kv.state_share"):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_no_share_of_a_peak_reads_over_a_hundred_where_time_covers_it():
    """The floors at the peaks themselves: a step that took exactly its
    weights' time on the bus, a kernel call exactly its rows' time."""
    run = recorded_run()
    least = COUNTS.decode_weight_bytes(CONF, 2) / 819e9
    rows = 96_000 * 2048 / 819e9
    device = run["trace"]["devices"][0]
    device["modules"] = [m[:2] + [least] if "decode" in m[0] else m
                         for m in device["modules"]]
    device["ops"] = [o[:2] + [rows] if o[0].startswith(
        "%paged_packed_decode_attention") and "custom-call" in o[0] else o
        for o in device["ops"]]
    assert mf.load_layer_metric(
        "step.decode_weight_bw_share.longanswer").read(run) \
        == pytest.approx(100.0)
    assert mf.load_layer_metric(KERNEL_READER).read(run) \
        == pytest.approx(100.0)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"state_pool_bytes", "state_tail_writes", "kv_pool_bytes",
            "kv_bytes_per_token", "prefill_chunks_dispatched",
            "prefill_programs_dispatched", "decode_steps_dispatched",
            "decode_tokens_emitted", "preemptions", "slots"} <= set(counters)
    assert counters["kv_bytes_per_token"] == \
        architecture.part(TINY, "counts").kv_bytes_per_token(TINY, 2)
    assert 0 < counters["state_pool_bytes"] < counters["kv_pool_bytes"]


def test_what_this_pr_added_is_listed_with_the_benchmark_at_the_end():
    for rel in (["benchmark/configs/lfm2-24b-a2b.json",
                 "benchmark/configs/rehearsal-tiny-lfm2.json",
                 "benchmark/traffic/batch-longanswer.json"]
                + [f"benchmark/architectures/lfm2-moe/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(READERS) <= set(names)
    assert [w["name"] for w in MANIFEST["workloads"]].count(CELL) == 1
    assert mf.cell(MANIFEST, CELL)["config"] == "lfm2-24b-a2b"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert set(mf.declared(MANIFEST, CELL, "per_layer")) == set(READERS)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(json.dumps(MANIFEST)) < 64 * 1024
