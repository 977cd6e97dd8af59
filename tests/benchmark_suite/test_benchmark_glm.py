"""The ``glm4-moe-lite`` architecture and its cell
(``glm-4.7-flash.batch-longcontext``): the cell's path rehearsed on the CPU
at tiny widths and judged ``correct`` against its own plain reference, the
float8 control over its limit, ``counts.py`` against the numbers reckoned by
hand in ISSUE 28, the configuration file against the published config, and
each of the cell's five readers on a recorded run and on a run without
samples."""

import json
import os

import jax
import pytest

from benchmark import architecture, control, correctness
from benchmark import manifest as mf
from benchmark.run import run_cell
from benchmark.weights import make_params, param_shapes
from test_benchmark_program_readers import quiet_run
from test_benchmark_rehearsal_cpu import check_line, rehearsal_manifest

MANIFEST = mf.load_manifest()
CELL = "glm-4.7-flash.batch-longcontext"
REHEARSAL = "tiny-glm.rehearsal-closed"
CONF = mf.load_config(MANIFEST, "glm-4.7-flash")
TINY = mf.load_json("benchmark/configs/rehearsal-tiny-glm.json")
COUNTS = architecture.part(CONF, "counts")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
READERS = ["step.prefill_mfu.longctx",
           "kernel.latent_decode_bw_share.longctx",
           "engine.decode_occupancy.longctx", "kv.preemptions.longctx",
           "engine.sched_busy_share.longctx"]
# config.json of zai-org/GLM-4.7-Flash, as the catalog beside the
# model-configs guide gives it
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


# -- the CPU rehearsal of the cell's path -----------------------------------------

@pytest.mark.parametrize("trace", [0, 1, 2])
def test_the_cells_path_runs_end_to_end_on_the_cpu(trace, tmp_path,
                                                   monkeypatch):
    from benchmark import run as bench_run

    monkeypatch.setattr(bench_run, "OUT_ROOT", str(tmp_path))
    manifest = rehearsal_manifest()
    line = run_cell(manifest, REHEARSAL, seed=2**31 + 41, seconds=2.0,
                    trace=trace, allow_cpu=True)
    # what the CPU's trace can feed: the counters and the host's spans
    counters = {"engine.decode_occupancy.longctx", "kv.preemptions.longctx",
                "engine.sched_busy_share.longctx"}
    if trace == 2:
        assert line["correct"] is True and line["failed"] == 0
        assert set(line["metrics"]) == {"serve_tokens_per_s",
                                        "setup_s"} | counters
    else:
        check_line(line, manifest, REHEARSAL, trace=bool(trace))
        if trace:
            assert set(line["metrics"]) == counters
    if trace:
        assert 0.0 < line["metrics"][
            "engine.decode_occupancy.longctx"]["value"] <= 100.0
        assert line["metrics"]["kv.preemptions.longctx"]["value"] >= 0.0
        assert 0.0 < line["metrics"][
            "engine.sched_busy_share.longctx"]["value"] <= 100.0
    else:
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_float8_control_is_over_the_limit_and_the_program_under():
    """One precision step down fails by each number; the program's own int8
    path cannot be a control here (a latent pool refuses int8 KV)."""
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


def test_a_reference_of_other_equations_is_far_over_the_limit():
    """The same tree with the correction bias dropped from the reference's
    choice, or the shared expert left out: not the model, and the
    comparison says so."""
    ref = architecture.part(TINY, "reference")
    params = make_params(TINY, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 64, TINY["vocab_size"])
    own = correctness.reference_logits(params, tokens, TINY, last=64)
    limit = TINY["correctness"]["limits"]["prefill_logit_err"]

    def err(p):
        other = correctness.reference_logits(p, tokens, TINY, last=64)
        return float(jax.numpy.median(correctness.position_errors(other,
                                                                  own)))

    mlp = params["layers"]["mlp"]
    no_bias = {**params, "layers": {**params["layers"], "mlp": {
        **mlp, "router_bias": mlp["router_bias"] * 0}}}
    no_shared = {**params, "layers": {**params["layers"], "mlp": {
        **mlp, "shared": jax.tree.map(lambda x: x * 0, mlp["shared"])}}}
    assert err(no_bias) > 3 * limit
    assert err(no_shared) > 3 * limit
    assert callable(ref.sequence_nll)


# -- counts, by hand ----------------------------------------------------------------

def test_counts_are_the_numbers_reckoned_by_hand():
    d, h, v = 2048, 20, 154880
    attention = (d * 768 + 768 + 768 * h * (192 + 64) + d * (512 + 64) + 512
                 + 512 * h * (192 + 256) + h * 256 * d)
    assert attention == COUNTS.attention_params(CONF) == 21_759_232
    expert = 3 * d * 1536
    assert expert == COUNTS.expert_params_one(CONF) == 9_437_184
    expert_layer = attention + d * 64 + 64 + 65 * expert + 2 * d
    assert expert_layer == COUNTS.expert_layer_params_total(CONF) \
        == 635_311_424
    assert COUNTS.expert_layer_matmul_params_active(CONF) \
        == attention - 768 - 512 + d * 64 + 5 * expert == 69_074_944
    dense = attention + 3 * d * 10240 + 2 * d
    assert dense == COUNTS.dense_layer_params_total(CONF) == 84_677_888
    assert 2 * v * d == 634_388_480
    total = dense + 6 * expert_layer + 2 * v * d + d
    assert total == COUNTS.params_total(CONF) == 4_530_936_960
    assert round(total * 2 / 2**30, 2) == 8.44
    assert COUNTS.kv_bytes_per_token(CONF, 2) == 7 * 1152
    # the pool ISSUE 28 reckoned: 2080 pages of 128 tokens (the cell's
    # fallback traffic holds 1568)
    assert 2080 * 128 * COUNTS.kv_bytes_per_token(CONF, 2) / 2**30 \
        == pytest.approx(2.0, abs=0.01)
    # the program counts the same parameters
    cfg = architecture.part(CONF, "program").program_config(CONF)
    assert cfg.num_params() == total
    shapes = jax.tree.leaves(param_shapes(CONF, "bfloat16"))
    assert sum(s.size for s in shapes) == total


def test_operations_are_what_the_model_needs():
    per_pair = COUNTS.attention_flops_causal(CONF, 1) / 7
    assert per_pair == 2 * (192 + 64 + 256) * 20 == 20480     # expanded
    matmuls = 84_677_888 - 1280 - 4096 + 6 * 69_074_944
    assert COUNTS.layers_matmul_params_active(CONF) == matmuls
    n = 8192
    want = (2.0 * matmuls * n + 20480 * 7 * n * (n + 1) / 2
            + 2.0 * 2048 * 154880)                 # the head ONCE
    assert COUNTS.prefill_flops(CONF, n) == want
    # a token more costs its layers and its row of attention, no head
    assert COUNTS.prefill_flops(CONF, n + 1) - want \
        == 2.0 * matmuls + 20480 * 7 * (n + 1)
    assert COUNTS.train_flops_per_token(CONF, 4096) == (
        6.0 * (matmuls + 2048 * 154880) + 3.0 * 20480 * 7 * 4097 / 2)
    least = COUNTS.decode_weight_bytes(CONF, 2)
    assert least == 2.0 * (matmuls + 7 * (768 + 512 + 4096) + 6 * 64
                           + 2048 * 154880 + 2048)
    assert least < 2 * COUNTS.params_total(CONF) / 3   # top-4 of 64 held
    assert COUNTS.latent_decode_bytes(CONF, 1000, 2) == 1000 * 1152
    assert COUNTS.latent_decode_flops(CONF, 1) == 20 * 2 * (512 + 64 + 512)
    # the kernel is bound by the bus: bytes over bandwidth above
    # operations over peak
    assert COUNTS.latent_decode_bytes(CONF, 1, 2) / PEAKS["hbm_bytes_per_s"] \
        > COUNTS.latent_decode_flops(CONF, 1) / PEAKS["bf16_flops"]


# -- the configuration file -----------------------------------------------------------

def test_the_file_holds_the_published_config_but_for_what_reduced_names():
    entry = mf.config_entry(MANIFEST, "glm-4.7-flash")
    assert sorted(entry["reduced"]) == sorted(CONF["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    for key, value in PUBLISHED.items():
        if key in CONF["reduced"]:
            assert CONF["reduced"][key]["from"] == value
            assert CONF["reduced"][key]["to"] == CONF[key] != value
        else:
            assert CONF[key] == value, key
    assert CONF["num_hidden_layers"] - CONF["first_k_dense_replace"] >= 4
    for said in ("source", "assumed", "deployment", "cache"):
        assert CONF[said]
    assert CONF["architecture"] == "glm4-moe-lite" and CONF["chips"] == 1
    assert any(plen >= 12000 for plen, _ in CONF["correctness"]["sequences"])


def test_the_programs_config_is_held_against_the_file():
    program = architecture.part(CONF, "program")
    cfg = program.program_config(CONF)
    assert (cfg.n_layers, cfg.leading_dense_layers, cfg.num_experts,
            cfg.shared_experts, cfg.experts_per_token) == (7, 1, 64, 1, 4)
    assert cfg.is_latent and cfg.moe_impl == "sorted"
    for key, other in (("n_routed_experts", 32), ("n_shared_experts", 2),
                       ("first_k_dense_replace", 0), ("num_hidden_layers", 9),
                       ("kv_lora_rank", 256), ("routed_scaling_factor", 2.5),
                       ("num_nextn_predict_layers", 1), ("n_group", 8),
                       ("norm_topk_prob", False)):
        with pytest.raises(mf.ManifestError, match=key):
            program.program_config({**CONF, key: other})


def test_the_seeded_tree_is_the_programs_at_the_published_widths():
    from kubeflow_tpu.models.decoder import init_decoder_params

    cfg = architecture.part(CONF, "program").program_config(CONF)
    want = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    got = param_shapes(CONF, cfg.param_dtype)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
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
    assert traffic["clients"] == e["max_batch_size"] == 16
    assert (e["decode_steps"], e["prefill_interleave_steps"]) == (1, 1)
    mpp = e["max_seq_len"] // e["page_size"]
    assert mpp == 98 and e["max_pages"] == 16 * mpp        # no preemption
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest < e["max_seq_len"]
    need = required_programs(traffic, BatchingSpec(**e))
    first = traffic["warmup"][0][0][0]
    reached = {f"paged_chunk_prefill[1x512,"
               f"{context_bucket(pos, 512, 128, mpp)}]"
               for pos in range(0, first, 512)} | {"paged_decode[1,greedy]"}
    assert need == reached
    assert f"paged_chunk_prefill[1x512,{mpp}]" in need


# -- the five readers ---------------------------------------------------------------------

def recorded_run() -> dict:
    """A window of 1000 decode steps over 9 live slots at 5k context each,
    20 requests prefilled; 3 traced seconds holding two chunk prefills (30
    and 50 ms), a cache copy, two decode rounds of one step over 9 slots at
    10k context each, and seven calls of the latent kernel a step (a layer
    each) at 0.4 ms, one slow outlier among them."""
    run = quiet_run("any.longctx")
    run["counters_after"]["engine"].update(
        decode_steps_dispatched=1000, decode_tokens_emitted=9000,
        decode_context_tokens=1000 * 9 * 5_000, preemptions=2)
    run["host_spans"].append([
        ["engine.decode_dispatch", 0.19, 0.001,
         {"round": 4, "k_steps": 1, "live": 9, "context": 9 * 9_000}],
        ["engine.fetch", 0.2, 0.01, {"round": 4}],
        ["engine.decode_dispatch", 0.25, 0.001,
         {"round": 5, "k_steps": 1, "live": 9, "context": 9 * 11_000}]])
    calls = [[f"%paged_latent_decode_attention.{i % 2} = custom-call",
              0.2 + 0.001 * i, 0.0004] for i in range(14)]
    calls.append(["%paged_latent_decode_attention.1 = custom-call", 0.5,
                  0.004])
    # the op that takes the kernel's result names it too, and is no call
    takers = [["%slice.7 = bf16[16,20,512] slice(bf16[16,20,640] "
               "%paged_latent_decode_attention.1)", c[1] + c[2], 1e-7]
              for c in calls]
    trace = {"window_s": 3.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit__lambda(7)", 0.0, 0.030],
                    ["jit__lambda(7)", 0.1, 0.050],
                    ["jit__lambda(9)", 0.15, 0.0001],
                    ["jit__paged_decode_fn(3)", 0.2, 0.012]],
        "ops": calls + takers + [["%fusion.12 = fusion", 0.0, 0.03],
                        ["%paged_decode_attention.2 = custom-call", 0.6,
                         0.1]]}]}
    return {**run, "kind": "closed_loop", "config": CONF, "trace": trace,
            "peaks": PEAKS, "weight_bytes_per_param": 2,
            "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 2.0e12}}


def test_readers_on_a_recorded_run():
    run = recorded_run()
    read = {name: mf.load_layer_metric(name).read(run) for name in READERS}
    # two chunks of 2 TFLOP needed over 80 ms of a 197 TFLOP/s chip
    assert read["step.prefill_mfu.longctx"] == pytest.approx(
        100 * 4.0e12 / (0.080 * 197e12))
    # the traced rounds' 90k context rows a step (not the window's 45k) of
    # 1152 bytes over 819 GB/s, in the traced calls' mean time
    assert read["kernel.latent_decode_bw_share.longctx"] == pytest.approx(
        100 * 90_000 * 1152 / 819e9 / ((14 * 0.0004 + 0.004) / 15))
    assert 0 < read["kernel.latent_decode_bw_share.longctx"] <= 100
    assert read["engine.decode_occupancy.longctx"] == pytest.approx(
        100 * 9000 / (1000 * 32))
    assert read["kv.preemptions.longctx"] == 2.0
    # the scheduler's 61 ms stretch less the 10 ms it waited in the fetch
    assert read["engine.sched_busy_share.longctx"] == pytest.approx(
        100 * 0.051 / 0.061)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_runs_without_samples_and_without_a_source(name):
    read = mf.load_layer_metric(name).read
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    # counters at rest, a trace that holds none of the programs: 0.0
    quiet = {**recorded_run(), **quiet_run("any.longctx")}
    quiet["trace"] = {"window_s": 1.0, "other_planes": [], "devices": [{
        "name": "/device:TPU:0", "lines": {},
        "modules": [["jit_other(1)", 0.0, 0.5]],
        "ops": [["%fusion.1 = fusion", 0.0, 0.5]]}]}
    assert read(quiet) == 0.0
    # another kind of run: nothing, and no exception
    assert read({"window_s": 1.0}) is None
    # a program from before this PR (the parent, with these files dropped
    # in): its engine has no ``decode_context_tokens`` and its rounds do
    # not say their context
    parent = recorded_run()
    for part in (parent["counters_before"], parent["counters_after"]):
        part["engine"].pop("decode_context_tokens")
    for thread in parent["host_spans"]:
        for span in thread:
            span[3].pop("context", None)
    if name.startswith("kernel."):
        assert read(parent) is None
    else:
        assert isinstance(read(parent), float)


def test_the_engine_has_the_counters_the_readers_take():
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = architecture.part(TINY, "program").program_config(TINY)
    engine = LLMEngine(cfg, BatchingSpec(
        **mf.load_traffic("rehearsal-closed")["engine"]),
        params=make_params(TINY, 1, "bfloat16"))
    counters = engine.counters()
    assert {"decode_context_tokens", "decode_steps_dispatched",
            "decode_tokens_emitted", "preemptions", "slots",
            "kv_bytes_per_token", "kv_pool_bytes"} <= set(counters)
    # what a token HOLDS (a row padded to whole lanes) is at least what
    # the model needs it to hold
    assert counters["kv_bytes_per_token"] == 4 * 128 * 2 >= \
        architecture.part(TINY, "counts").kv_bytes_per_token(TINY, 2)


def test_what_this_pr_added_is_listed_with_the_benchmark():
    for rel in (["benchmark/configs/glm-4.7-flash.json",
                 "benchmark/configs/rehearsal-tiny-glm.json",
                 "benchmark/traffic/batch-longcontext.json"]
                + [f"benchmark/architectures/glm4-moe-lite/{p}.py"
                   for p in architecture.PARTS]
                + [f"benchmark/layer_metrics/{n}.py" for n in READERS]):
        assert os.path.exists(os.path.join(mf.ROOT, rel)), rel
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(READERS):] == READERS     # new entries at the end
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == "glm-4.7-flash"
    e2e = mf.declared(MANIFEST, CELL, "end_to_end")
    assert set(e2e) == {"serve_tokens_per_s", "setup_s"}
    assert len(json.dumps(MANIFEST)) < 64 * 1024
