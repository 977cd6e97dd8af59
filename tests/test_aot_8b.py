"""AOT shardability proof for the flagship 8B recipe (SURVEY.md §6;
VERDICT round-2 next #5): lower + compile — never execute — the real train
step and the TP-sharded serving decode against virtual TPU topologies via
libtpu's topology-only AOT path, and check per-chip memory against the HBM
budget. scripts/aot_validate_8b.py runs the full config table (results in
BASELINE.md); this test pins the mechanism + the v5p-16 train point and
the v5e-8 serving point.

Requires libtpu (present in this image); skips cleanly where the TPU AOT
plugin is unavailable.
"""

import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """An entry written for a described chip cannot be read back without
    one: every later compile would warn and compile again."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _topo(name):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(name, "tpu")
    except Exception as exc:  # noqa: BLE001
        # Skip ONLY where libtpu genuinely isn't installed. On an image
        # that ships it, a failing topology lookup means the flagship
        # shardability guarantee silently degraded to scripts-only — that
        # must be a loud failure, not a skip (round-3 verdict weak #5).
        import importlib.util

        if importlib.util.find_spec("libtpu") is not None:
            pytest.fail(
                f"libtpu is present but the AOT topology path broke: {exc}")
        pytest.skip(f"no libtpu: TPU AOT topology unavailable: {exc}")


@pytest.mark.slow
def test_train_step_8b_compiles_on_v5p16_within_hbm():
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import train_step_analysis

    _topo("v5p:2x2x4")      # same skip/loud-fail semantics as the serve test
    out = train_step_analysis("v5p:2x2x4", {"fsdp": 8, "model": 2},
                              per_chip_batch=1)
    assert out["params_b"] > 7.5           # the real 8B, not a toy
    assert out["total_gb"] < 95.0, out     # v5p HBM budget
    # fp32 params + Adam state sharded 16 ways ≈ 96 GB/16 = 6 GB arguments.
    assert 3.0 < out["argument_gb"] < 12.0, out


@pytest.mark.slow
def test_serving_decode_8b_compiles_on_v5e8_within_hbm():
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import (
        SERVE_BF16, SERVE_POOL, paged_serve_analysis)

    _topo("v5e:2x4x1")
    out = paged_serve_analysis("v5e:2x4x1", 8, model="llama3-8b",
                               overrides=SERVE_BF16["llama3-8b"],
                               **SERVE_POOL)
    # bf16 8B weights sharded 8 ways ≈ 2 GB/chip + KV pool: far under the
    # 16 GB a single v5e chip has — which full replication could never fit.
    for prog in out.values():
        assert prog["total_gb"] < 16.0, out
        assert prog["argument_gb"] > 1.5, out


# -- Mixtral-8x7B north star (BASELINE.json configs[2]; VERDICT r4 #2) ---------


@pytest.mark.slow
def test_train_step_mixtral_compiles_on_v5p64_within_hbm():
    """The real 46.7B MoE train step, expert×fsdp-sharded on a virtual
    v5p-64, per-chip memory within the 95 GB budget. Measured this session:
    30.6 GB/chip (fp32 params + Adam ≈ 560 GB sharded 64 ways + remat
    activations)."""
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import train_step_analysis

    _topo("v5p:4x4x4")
    out = train_step_analysis("v5p:4x4x4", {"expert": 8, "fsdp": 8},
                              model="mixtral-8x7b", per_chip_batch=1)
    assert out["params_b"] > 45.0, out       # the real 8x7B, not a toy
    assert out["total_gb"] < 95.0, out
    # 560 GB of fp32 state over 64 chips ≈ 8.75 GB arguments per chip.
    assert 5.0 < out["argument_gb"] < 20.0, out


@pytest.mark.slow
def test_train_step_multislice_dcn_mechanism():
    """2-slice DCN multislice compiles end-to-end: the topology carries
    distinct slice_index per slice, build_mesh routes through the hybrid
    ICI×DCN assignment, and the dcn-axis collectives lower. Runs the tiny
    MoE config so the suite stays fast; the full 46.7B 2-slice point
    (49.9 GB/chip on v5p:2x4x4 ×2) lives in scripts/aot_validate_8b.py and
    BASELINE.md."""
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import train_step_analysis

    _topo("v5p:2x2x1")
    # 2 slices x (2x2x1 = 4 chips/slice) = 8 devices: dcn 2 x ep 2 x fsdp 2.
    out = train_step_analysis("v5p:2x2x1", {"dcn": 2, "expert": 2,
                                            "fsdp": 2},
                              model="tiny-moe", per_chip_batch=1,
                              num_slices=2)
    assert out["total_gb"] < 95.0, out


@pytest.mark.slow
def test_serving_decode_mixtral_compiles_on_v5e8_within_hbm():
    """Mixtral-8x7B bf16 serving decode TP-sharded on v5e-8: ≈11.4 GB/chip
    of params (93 GB / 8) + KV — fits the 16 GB chip with room for the
    cache; single-chip serving could never hold it."""
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import (
        SERVE_BF16, SERVE_POOL, paged_serve_analysis)

    _topo("v5e:2x4x1")
    out = paged_serve_analysis("v5e:2x4x1", 8, model="mixtral-8x7b",
                               overrides=SERVE_BF16["mixtral-8x7b"],
                               **SERVE_POOL)
    for prog in out.values():
        assert prog["total_gb"] < 16.0, out
        assert prog["argument_gb"] > 10.0, out    # the real 46.7B resident


# -- int8 density (VERDICT r4 #3: AOT-prove the quantization HBM win) ----------


@pytest.mark.slow
def test_serving_decode_8b_int8_fits_one_v5e_chip():
    """Weight-only int8 8B decode on ONE v5e chip: 12.7 GB of 16 — a
    deployment bf16 cannot reach (16 GB of params alone). The quantized
    param tree lowers through the same decode step (QuantizedTensor
    pytrees + per-field shardings)."""
    import sys
    sys.path.insert(0, ".")
    from scripts.aot_validate_8b import (
        SERVE_BF16, SERVE_POOL, paged_serve_analysis)

    _topo("v5e:2x4x1")      # libtpu-presence gate (shared skip semantics)
    out = paged_serve_analysis(
        "v5e:1x1x1", 1, model="llama3-8b",
        overrides=SERVE_BF16["llama3-8b"], quantize="int8",
        topo_kwargs={"chips_per_host_bounds": [1, 1, 1]},
        **{**SERVE_POOL, "slots": 8, "num_pages": 128})
    for prog in out.values():
        assert prog["total_gb"] < 16.0, out
        assert prog["argument_gb"] < 11.0, out    # int8 params ≈ 8 GB + KV


# -- chip_smoke.py's own programs (ISSUE 21 (f)) -------------------------------
#
# Ask the compiler before the chip: the whole step programs of both smoke
# phases, at the smoke's real sizes (imported from chip_smoke.py, so the two
# cannot drift), compiled for a described v5e. Code that asks
# ``jax.default_backend()`` sees the CPU here and would take its CPU branch
# (XLA norms, interpreted kernels), so the TEST steers it — not an option of
# the program. A compile that passes is not a chip run.


@pytest.fixture()
def as_tpu(monkeypatch):
    import jax

    _topo("v5e:2x2")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _smoke_train(sizes, fsdp):
    import sys
    sys.path.insert(0, ".")
    import chip_smoke
    from scripts.aot_validate_8b import train_step_analysis

    conf = chip_smoke.train_job("t", sizes, seed=0, chips=fsdp, fsdp=fsdp)[
        "spec"]["replica_specs"]["worker"]["template"]["config"]
    over = dict(conf["model_overrides"])
    return train_step_analysis(
        "v5e:2x2", {"fsdp": fsdp}, model=conf["model"],
        per_chip_batch=sizes["global_batch"] // fsdp,
        seq_len=over.pop("max_seq_len"), model_overrides=over,
        optimizer=conf["optimizer"])


@pytest.mark.slow
@pytest.mark.parametrize("sizes,fsdp,fused", [
    ("TRAIN_ONE", 1, True), ("TRAIN_PAIR", 1, True),
    ("TRAIN_FOUR", 4, False), ("TRAIN_PAIR", 4, False)])
def test_smoke_train_step_compiles_for_v5e(as_tpu, sizes, fsdp, fused):
    import chip_smoke

    out = _smoke_train(getattr(chip_smoke, sizes), fsdp)
    assert out["total_gb"] < 15.75, out          # one v5e chip's usable HBM
    # One device: every fused layer + flash. Under the mesh: flash alone
    # (through shard_map); the fused layers give way to XLA.
    want = chip_smoke.FLASH + (chip_smoke.FUSED_TRAIN if fused else ())
    assert set(out["kernels"]) == set(want), out["kernels"]


@pytest.mark.slow
@pytest.mark.parametrize("tp", [1, 4])
def test_smoke_serving_programs_compile_for_v5e(as_tpu, tp):
    import sys
    sys.path.insert(0, ".")
    import chip_smoke
    from scripts.aot_validate_8b import paged_serve_analysis

    b = chip_smoke.BATCHING
    out = paged_serve_analysis(
        "v5e:2x2", tp, model=chip_smoke.MODEL,
        overrides=chip_smoke.SERVE_OVERRIDES, slots=b["max_batch_size"],
        max_len=b["max_seq_len"], page_size=b["page_size"],
        num_pages=b["max_pages"], chunk=b["chunked_prefill_tokens"],
        decode_steps=b["decode_steps"],
        attn_impl="pallas" if tp == 1 else "gather")
    for prog in out.values():
        assert prog["total_gb"] < 15.75, out
    if tp == 1:
        assert "paged_decode_attention" in out["decode"]["kernels"], out
        for prog in out.values():
            assert set(chip_smoke.FUSED_SERVE) <= set(prog["kernels"]), out
    else:
        # A Mosaic kernel over GSPMD-sharded operands is refused by the
        # compiler; the TP engine must hand it none.
        assert not out["decode"]["kernels"], out
        assert not out["chunk_prefill"]["kernels"], out
