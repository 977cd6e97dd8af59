"""The reduction from a trace to numbers, on hand-built traces, and the FLOP
and byte functions (the ``mistral`` architecture's ``counts.py``) against
hand counts for one Mistral and one Mixtral layer."""

import json
import os

import pytest

from benchmark import architecture, tracing
from benchmark import manifest as mf

MANIFEST = mf.load_manifest()
MISTRAL = mf.load_config(MANIFEST, "mistral-7b")
MIXTRAL = mf.load_config(MANIFEST, "mixtral-8x7b")
# Both files name the ``mistral`` architecture: its counts, through the seam.
flops = architecture.part(MISTRAL, "counts")


def trace_of(*devices, window=1.0):
    return {"window_s": window, "other_planes": [], "devices": [
        {"name": f"/device:TPU:{i}", "lines": {}, "modules": mods,
         "ops": ops} for i, (mods, ops) in enumerate(devices)]}


def test_union_measure_and_subtract():
    assert tracing.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == [(0, 2), (3, 4)]
    assert tracing.measure([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert tracing.subtract([(0, 10)], [(1, 2), (3, 5), (9, 12)]) == [
        (0, 1), (2, 3), (5, 9)]
    assert tracing.subtract([(0, 1)], []) == [(0, 1)]
    assert tracing.subtract([(0, 1)], [(0, 1)]) == []


def test_busy_is_the_union_of_op_intervals_mean_over_devices():
    ops0 = [["a", 0.0, 0.2], ["b", 0.1, 0.2], ["c", 0.5, 0.1]]   # 0.4 busy
    ops1 = [["a", 0.0, 0.2]]                                     # 0.2 busy
    t = trace_of(([], ops0), ([], ops1))
    assert tracing.busy_s(t) == pytest.approx(0.3)
    assert tracing.busy_s(trace_of()) == 0.0
    assert tracing.traced_window_s(t) == 1.0
    long = trace_of(([], [["a", 0.0, 0.2], ["b", 1.5, 0.1]]), window=1.0)
    assert tracing.traced_window_s(long) == pytest.approx(1.6)


def test_per_program_device_time_and_ops_inside_one_execution():
    mods = [["jit__paged_decode_fn(7)", 0.0, 0.4], ["jit__lambda_(9)", 0.5, 0.1],
            ["jit__paged_decode_fn(7)", 0.7, 0.2]]
    ops = [["paged_decode_attention.1", 0.0 + 0.01 * i, 0.005]
           for i in range(8)] + [["fusion.2", 0.75, 0.01]]
    t = trace_of((mods, ops))
    assert tracing.module_time_s(t, r"paged_decode") == pytest.approx(0.6)
    assert tracing.module_time_s(t, r"^jit__lambda") == pytest.approx(0.1)
    assert tracing.module_time_s(t, r"absent") == 0.0
    assert len(tracing.ops_within(t, 0.0, 0.4,
                                  r"paged_decode_attention")) == 8
    assert tracing.ops_within(t, 0.7, 0.9, r"paged_decode_attention") == []


def test_exposed_collective_rule():
    # all-gather 0.0-0.4; compute covers 0.1-0.3 of it: exposed 0.2.
    # reduce-scatter 0.6-0.7 alone: exposed 0.1. Device 1 has none.
    ops0 = [["all-gather-start.1", 0.0, 0.4], ["fusion.1", 0.1, 0.2],
            ["reduce-scatter.3", 0.6, 0.1], ["fusion.2", 0.8, 0.1]]
    t = trace_of(([], ops0), ([], [["fusion.1", 0.0, 0.5]]))
    assert tracing.exposed_collective_s(t) == pytest.approx(0.3 / 2)
    assert tracing.exposed_collective_s(
        trace_of(([], [["fusion.1", 0.0, 0.5]]))) == 0.0
    assert tracing.exposed_collective_s(trace_of()) == 0.0


def test_top_ops_and_idle_gaps():
    mods = [["jit_a(1)", 0.0, 0.2], ["jit_b(2)", 0.5, 0.2],
            ["jit_a(1)", 0.8, 0.1]]
    ops = [["x", 0.0, 0.2], ["y", 0.5, 0.1], ["x", 0.6, 0.1], ["x", 0.8, 0.1]]
    t = trace_of((mods, ops))
    ops.append(["%while.3 = (s32[]) while(...)", 0.0, 0.9])      # a container
    assert tracing.top_ops(t)[:2] == [["jit_a/x", pytest.approx(0.3)],
                                      ["jit_b/y", pytest.approx(0.1)]]
    assert tracing.short_name("%copy.72 = bf16[2]{0} copy(%p)") == "copy.72"
    ops.pop()
    gaps = dict(tracing.idle_gaps(t))
    assert gaps["before jit_b"] == pytest.approx(0.3)
    assert gaps["before jit_a"] == pytest.approx(0.1)
    s = tracing.summary(t)
    assert s["devices"][0]["modules"]["jit_a"] == [2, pytest.approx(0.3)]


def test_readers_on_a_built_trace():
    layers = MISTRAL["num_hidden_layers"]
    steps = 4
    mods = [["jit__paged_decode_fn(1)", 0.0, 0.06]]
    ops = [["paged_decode_attention", 0.0005 * i, 0.0001]
           for i in range(layers * steps)]
    run = {"trace": trace_of((mods, ops)), "config": MISTRAL, "loadgen": {},
           "weight_bytes_per_param": 2,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    got = mf.load_layer_metric("step.decode_weight_bw_share.chat").read(run)
    least = flops.decode_weight_bytes(MISTRAL, 2) / 819e9
    assert got == pytest.approx(100.0 * least / (0.06 / steps))
    run = {"trace": trace_of(([["jit__lambda_(3)", 0.0, 0.05],
                               ["jit__lambda_(3)", 0.1, 0.05],
                               ["jit__lambda_(4)", 0.2, 0.00001]], [])),
           "prefill": {"chunk": 512, "mean_useful_flops_per_chunk": 2e12},
           "peaks": {"bf16_flops": 197e12}}
    got = mf.load_layer_metric("step.prefill_mfu.batch").read(run)
    assert got == pytest.approx(100.0 * 2 * 2e12 / (0.1 * 197e12))


def test_counts_for_one_mistral_layer_by_hand():
    # q and o: 4096 x 4096 each; k and v: 4096 x (8 x 128) each.
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    mlp = 3 * 4096 * 14336
    assert flops.attention_params(MISTRAL) == attn == 41_943_040
    assert flops.mlp_params_one(MISTRAL) == mlp == 176_160_768
    assert flops.layer_matmul_params_active(MISTRAL) == attn + mlp
    assert flops.layer_params_total(MISTRAL) == attn + mlp + 2 * 4096
    total = 16 * (attn + mlp + 8192) + 2 * 32768 * 4096 + 4096
    assert flops.params_total(MISTRAL) == total
    assert round(total / 1e9, 2) == 3.76
    # 64 KB of KV per token at depth 16 in bf16.
    assert flops.kv_bytes_per_token(MISTRAL, 2) == 2 * 16 * 8 * 128 * 2 == 65536
    # One decode step reads every layer and the head once.
    assert flops.decode_weight_bytes(MISTRAL, 2) == 2 * (
        16 * (attn + mlp + 8192) + 4096 * 32768 + 4096)


def test_counts_for_one_mixtral_layer_by_hand():
    attn = 41_943_040
    expert = 3 * 4096 * 14336
    router = 4096 * 8
    assert flops.layer_params_total(MIXTRAL) == attn + router + 8 * expert \
        + 8192
    assert flops.layer_matmul_params_active(MIXTRAL) == attn + router \
        + 2 * expert
    total = 3 * (attn + router + 8 * expert + 8192) + 2 * 32000 * 4096 + 4096
    assert flops.params_total(MIXTRAL) == total
    assert round(total / 1e9, 2) == 4.62


def test_prefill_and_train_flops_by_hand():
    n = 1024
    mm = 2.0 * (16 * (41_943_040 + 176_160_768) + 4096 * 32768) * n
    attn = 4.0 * 32 * 128 * (n * (n + 1) / 2) * 16
    assert flops.prefill_flops(MISTRAL, n) == pytest.approx(mm + attn)
    # A chunk of 512 starting at 1024 attends to 1024 earlier positions too.
    assert flops.attention_flops_causal(MISTRAL, 512, start=1024) == \
        pytest.approx(4.0 * 32 * 128 * (512 * 1024 + 512 * 513 / 2) * 16)
    train = mf.load_config(MANIFEST, "mistral-7b-fsdp4")
    per_tok = flops.train_flops_per_token(train, 4096)
    mm = 6.0 * (8 * (41_943_040 + 176_160_768) + 4096 * 32768)
    attn = 3.0 * 4.0 * 32 * 128 * (4097 / 2) * 8
    assert per_tok == pytest.approx(mm + attn)
    assert 11e9 < per_tok < 13e9          # "about 12 GFLOP a token"


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace.json")


def test_reduction_on_the_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    t = rec["trace"]
    assert tracing.busy_s(t) == pytest.approx(rec["busy_s"], rel=1e-9)
    assert 0.0 < tracing.busy_s(t) <= tracing.traced_window_s(t)
    for pattern, seconds in rec["module_time_s"].items():
        assert tracing.module_time_s(t, pattern) == pytest.approx(seconds)
    assert rec["module_time_s"]["paged_decode"] > 0
    # The decode dispatch's steps are countable in it: the paged-attention
    # kernel runs once per layer and step.
    mods = tracing.module_events(t, "paged_decode")
    n = len(tracing.ops_within(t, mods[0][1], mods[0][1] + mods[0][2],
                               "paged_decode_attention"))
    assert n > 0 and n % 16 == 0
    assert all(not tracing.CONTAINER.match(k.split("/", 1)[1])
               for k, _ in tracing.top_ops(t))
