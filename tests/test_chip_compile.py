"""The main path's Pallas kernels compile for the chip, at real widths.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: a tile not aligned to the lane/sublane grid, a kernel
that wants more fast memory than its scoped limit, a block that does not
divide. libtpu compiles for a chip that is DESCRIBED, not attached
(``jax.experimental.topologies``), so these cases cost no chip time and
guard every later PR. Each compiles ONE kernel (forward, or forward +
backward through ``jax.grad``) for one device of a ``v5e:2x2`` and
asserts the Mosaic custom call is in the compiled program — a kernel that
quietly fell back to XLA or to the interpreter fails here.

Two width sets: Gemma-2B's published shapes (8 query heads / 1 KV head x
256, hidden 2048, MLP 16384, vocab 256128 — what ``chip_smoke.py`` runs)
and Llama-3-8B's (32 / 8 heads x 128, hidden 4096, MLP 14336, vocab
128256). Rows are the smoke's train step: batch 2 x sequence 2048.

Nothing here runs on a device: a compile that passes is not a chip run.
The whole-step compiles live in tests/test_aot_8b.py under ``slow``.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest

ROWS = 2 * 2048
WIDTHS = {
    # name: (q heads, kv heads, head dim, hidden, mlp, vocab, act, plus_one)
    "gemma-2b": (8, 1, 256, 2048, 16384, 256128, "gelu", True),
    "llama3-8b": (32, 8, 128, 4096, 14336, 128256, "silu", False),
}
# The serving smoke's pool: 32 slots x 4096 tokens, 1024 pages of 128.
SLOTS, PAGES, PAGE, MPP = 32, 1024, 128, 32


@pytest.fixture(scope="module")
def chip():
    """One described v5e device; skip only where libtpu is truly absent
    (the loud-fail rule of tests/test_aot_8b.py). The persistent compile
    cache is off around the module: an entry written for a described
    chip cannot be read back without one, and every later compile would
    warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as exc:  # noqa: BLE001 — any failure is classified below
        import importlib.util

        if importlib.util.find_spec("libtpu") is not None:
            pytest.fail(
                f"libtpu is present but the AOT topology path broke: {exc}")
        pytest.skip(f"no libtpu: TPU AOT topology unavailable: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _flash(w, grad):
    from kubeflow_tpu.ops.flash_attention import flash_attention

    h, kh, d = w[:3]
    shapes = [((2, 2048, h, d), jnp.bfloat16),
              ((2, 2048, kh, d), jnp.bfloat16),
              ((2, 2048, kh, d), jnp.bfloat16)]

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    if not grad:
        return fwd, shapes
    return jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2)), shapes


def _xent(w, grad, head_dtype=jnp.bfloat16):
    from kubeflow_tpu.ops.fused_xent import fused_cross_entropy

    hidden, vocab = w[3], w[5]
    shapes = [((ROWS, hidden), jnp.bfloat16), ((hidden, vocab), head_dtype),
              ((ROWS,), jnp.int32)]

    def fwd(h, head, t):
        return fused_cross_entropy(h, head, t, interpret=False)[0].sum()

    return (jax.grad(fwd, argnums=(0, 1)) if grad else fwd), shapes


def _norm(w, grad, add):
    from kubeflow_tpu.ops import fused_norm

    hidden, plus_one = w[3], w[7]
    x = ((ROWS, hidden), jnp.bfloat16)
    wt = ((hidden,), jnp.float32)
    if add:
        def fwd(x, r, wt):
            y, h = fused_norm.add_rmsnorm_fused(
                x, r, wt, eps=1e-6, plus_one=plus_one, interpret=False)
            return (y.astype(jnp.float32) + h.astype(jnp.float32)).sum()
        shapes, argnums = [x, x, wt], (0, 1, 2)
    else:
        def fwd(x, wt):
            return fused_norm.rmsnorm_fused(
                x, wt, eps=1e-6, plus_one=plus_one,
                interpret=False).astype(jnp.float32).sum()
        shapes, argnums = [x, wt], (0, 1)
    return (jax.grad(fwd, argnums=argnums) if grad else fwd), shapes


def _glu(w, grad):
    from kubeflow_tpu.ops.fused_norm import swiglu_fused

    mlp, act = w[4], w[6]
    shapes = [((ROWS, mlp), jnp.bfloat16)] * 2

    def fwd(g, u):
        return swiglu_fused(g, u, act=act,
                            interpret=False).astype(jnp.float32).sum()

    return (jax.grad(fwd, argnums=(0, 1)) if grad else fwd), shapes


def _paged(w, int8):
    from kubeflow_tpu.ops.paged_attention import paged_decode_attention

    h, kh, d = w[:3]
    pool = ((PAGES, PAGE, kh, d), jnp.int8 if int8 else jnp.bfloat16)
    shapes = [((SLOTS, 1, h, d), jnp.bfloat16), pool, pool,
              ((SLOTS, MPP), jnp.int32), ((SLOTS,), jnp.int32)]
    if not int8:
        return (lambda q, k, v, t, ln: paged_decode_attention(
            q, k, v, t, ln, interpret=False)), shapes
    scale = ((PAGES, PAGE, kh), jnp.float32)
    return (lambda q, k, v, t, ln, ks, vs: paged_decode_attention(
        q, k, v, t, ln, pool_ks=ks, pool_vs=vs,
        interpret=False)), shapes + [scale, scale]


KERNELS = {
    "flash_fwd": lambda w: _flash(w, False),
    "flash_bwd": lambda w: _flash(w, True),
    "xent_fwd": lambda w: _xent(w, False),
    "xent_bwd": lambda w: _xent(w, True),
    "rmsnorm_fwd": lambda w: _norm(w, False, False),
    "rmsnorm_bwd": lambda w: _norm(w, True, False),
    "add_rmsnorm_fwd": lambda w: _norm(w, False, True),
    "add_rmsnorm_bwd": lambda w: _norm(w, True, True),
    "glu_fwd": lambda w: _glu(w, False),
    "glu_bwd": lambda w: _glu(w, True),
    "paged_decode_bf16": lambda w: _paged(w, False),
    "paged_decode_int8": lambda w: _paged(w, True),
    # The case the compiler refused before fused_xent._blocks counted fast
    # memory: a float32 head (the trainer casts its head to bf16; a caller
    # that does not must still get a kernel that fits). The d_head
    # backward's [D, bv] tiles at 4 bytes wanted 17 MB of a 16 MB scoped
    # limit at Gemma-2B's vocab.
    "xent_bwd_f32_head": lambda w: _xent(w, True, jnp.float32),
}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, kernel, widths):
    fn, shapes = KERNELS[kernel](WIDTHS[widths])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- PR 45: the decode kernel walks a row's live pages by its own copies ---------

# shape -> (rows, query heads, KV heads, pages of the flat plane, pages a
# table row, a window layer's call): the chat cell's decode step (16 layers x
# 448 pages), the long-document cell's GQA layer, K-EXAONE's window layers
# (four rings of 192 pages, two pages a row).
WALK_SHAPES = {
    "chat": (32, 32, 8, 16 * 448, 32, False),
    "longdoc": (32, 64, 8, 4352, 136, False),
    "window": (32, 64, 8, 4 * 192, 2, True),
}


@pytest.mark.parametrize("shape", sorted(WALK_SHAPES))
def test_decode_walk_compiles_for_v5e(chip, shape):
    """``paged_decode_attention`` at the cells' shapes: a loop of a traced
    length around copies and waits, four pages of each plane a turn in a
    double buffer (4 MiB of the 16 MiB scoped limit: a kernel over it is
    refused here), the pools left in HBM where they lie (the call holds
    nothing beyond its arguments and its result: no copy of a plane)."""
    from kubeflow_tpu.ops import paged_attention as pa

    rows, h, kv, pages, mpp, window = WALK_SHAPES[shape]

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    assert pa._pages_a_turn(PAGE * kv * 128 * 2, mpp) == min(4, mpp)
    pool = sds((pages, PAGE, kv, 128))
    args = [sds((rows, 1, h, 128)), pool, pool, sds((rows, mpp), jnp.int32),
            sds((rows,), jnp.int32)] + [sds((rows,), jnp.int32)] * window
    compiled = jax.jit(lambda q, k, v, t, ln, lo=None: (
        pa.paged_decode_attention(q, k, v, t, ln, lower=lo,
                                  interpret=False))).lower(*args).compile()
    text = compiled.as_text()
    assert _calls(text, "paged_window_decode_attention" if window
                  else "paged_decode_attention") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# -- GLM-4.7-Flash's widths: the latent decode kernel, the sorted experts -------

def test_latent_kernels_compile_for_v5e(chip):
    """20 heads against one shared 640-wide row (512 latent + 64 rotary +
    padding), over the benchmark cell's pool viewed flat (7 layers x 2080
    pages of 128): the decode kernel at 16 slots of 130 pages (PR 48: the
    walk over a row's live pages, eight of the one plane's 164 KB pages a
    turn in a double buffer, the pool left in HBM where it lies: the call
    holds nothing beyond its arguments and its result), the chunk kernel at
    512 queries over a slot's 130 pages."""
    from kubeflow_tpu.ops import paged_attention as pa

    slots, h, w, pages, mpp, chunk = 16, 20, 640, 7 * 2080, 130, 512

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    assert pa._pages_a_turn(PAGE * w * 2, mpp, 1) == 8
    pool = sds((pages, PAGE, w))
    decode = jax.jit(lambda *a: pa.paged_latent_decode_attention(
        *a, sm_scale=256 ** -0.5, interpret=False)).lower(
            sds((slots, h, w)), pool, sds((slots, mpp), jnp.int32),
            sds((slots,), jnp.int32)).compile()
    prefill = jax.jit(lambda *a: pa.paged_latent_chunk_attention(
        *a, sm_scale=256 ** -0.5, interpret=False)).lower(
            sds((h, chunk, w)), pool, sds((mpp,), jnp.int32),
            sds((), jnp.int32)).compile()
    for compiled, name in ((decode, "paged_latent_decode_attention"),
                           (prefill, "paged_latent_chunk_attention")):
        assert _calls(compiled.as_text(), name) == 1
    assert decode.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("tokens", [512, 16])
def test_sorted_experts_are_a_grouped_matmul_on_v5e(chip, tokens):
    """64 experts of 1536, top-4. A chunk's 512 tokens go through the
    Pallas grouped matmul (whole 128-row tiles), a decode step's 16 through
    ``ragged_dot``, which the chip's compiler takes as a grouped matmul of
    its own; neither computes every expert for every token."""
    import dataclasses

    from kubeflow_tpu.models import layers as L
    from kubeflow_tpu.models.config import preset

    cfg = dataclasses.replace(
        preset("glm-4.7-flash", dtype="bfloat16", param_dtype="bfloat16"),
        fused_kernels="on")
    d, m, e = cfg.hidden, cfg.expert_mlp_dim, cfg.num_experts

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    p = {"router": sds((d, e)), "router_bias": sds((e,), jnp.float32),
         "gate": sds((e, d, m)), "up": sds((e, d, m)), "down": sds((e, m, d)),
         "shared": {"gate": sds((d, m)), "up": sds((d, m)),
                    "down": sds((m, d))}}
    compiled = jax.jit(lambda p, x: L.moe_block(p, x, cfg)[0]).lower(
        p, sds((1, tokens, d))).compile()
    text = compiled.as_text()
    assert ("gmm" if tokens % 128 == 0 else "ragged-dot") in text, tokens
    if tokens % 128:        # a kernel's tiles are not in XLA's count
        need = 2.0 * tokens * (cfg.experts_per_token + 1) * 3 * d * m
        assert compiled.cost_analysis()["flops"] < 2 * need


def test_packed_row_decode_step_compiles_for_v5e_without_pool_copies(chip):
    """LFM2's heads of 64: K and V lie one 512-value row a token in the
    pool (``kv_heads_packed``). At the cell's sizes (64 slots of 25 pages,
    1600 pages of 128, two attention layers) the packed-row kernel compiles
    (PR 48: the walk over a row's live pages, four pages of each plane a
    turn, both planes left in HBM), and the decode write over the flat pool
    leaves no pool-sized copy in the program (an [8, 64] plane is padded to
    twice its bytes and copied whole, twice a plane: PERF.md, PR 35)."""
    import re

    from kubeflow_tpu.ops.paged_attention import (
        _pages_a_turn, paged_packed_decode_attention,
    )

    slots, h, kv, d, layers, pages, mpp = 64, 32, 8, 64, 2, 1600, 25
    assert _pages_a_turn(PAGE * kv * d * 2, mpp, 2) == 4

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def step(q, pool_k, pool_v, rows, pidx, off, table, lengths):
        pool_k = pool_k.at[pidx, off].set(rows, mode="drop")
        pool_v = pool_v.at[pidx, off].set(rows, mode="drop")
        return paged_packed_decode_attention(
            q, pool_k, pool_v, table, lengths, kv, interpret=False), \
            pool_k, pool_v

    pool = sds((layers * pages, PAGE, kv * d))
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        sds((slots, 1, h, d)), pool, pool, sds((slots, kv * d)),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, mpp), jnp.int32), sds((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _calls(text, "paged_packed_decode_attention") == 1
    whole = re.findall(
        rf"= bf16\[{layers * pages},{PAGE},{kv * d}\]\S* copy\(", text)
    assert not whole, whole


# -- the chunk program of a per-head pool, in place ------------------------------

CHUNK_PROGRAMS = {
    # cell: (preset, overrides, pool pages, pages a slot, rows a program)
    "mixtral-8x7b.batch-longprompt": (
        "mixtral-8x7b", {"n_layers": 3, "max_seq_len": 8320}, 1040, 65, 2),
    "mistral-7b.chat-open": (
        "llama3-8b", {"vocab_size": 32768, "rope_theta": 1e6, "n_layers": 16,
                      "max_seq_len": 4096}, 448, 32, 1),
}


@pytest.mark.parametrize("cell", sorted(CHUNK_PROGRAMS))
def test_chunk_program_compiles_for_v5e_in_place(chip, cell, monkeypatch):
    """The chunk programs of the two per-head cells at their sizes, as the
    engine builds them on one chip ("pallas"): they compile, every row of
    every scanned layer group attends through ``paged_chunk_attention`` (the
    engine's ``program_kernels`` reads the same lowered text), and the
    program holds no temporary the size of a layer's pool or of a row's
    gathered context: the pool is written and read where it lies. Code that
    asks for the backend is told "tpu" here, as benchmark/aot_sizes.py
    tells it: a kernel interprets anywhere else."""
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.runtime.device_report import lowered_kernel_calls
    from kubeflow_tpu.serve.paged import paged_chunk_prefill, pool_shapes

    name, over, pages, mpp, rows = CHUNK_PROGRAMS[cell]
    cfg = preset(name, dtype="bfloat16", param_dtype="bfloat16", **over)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree.map(
        lambda s: sds(s.shape, s.dtype),
        jax.eval_shape(lambda: init_decoder_params(jax.random.PRNGKey(0),
                                                   cfg)))
    cache = {n: sds(shape, dt)
             for n, (shape, dt) in pool_shapes(cfg, pages, PAGE).items()}
    program = jax.jit(
        lambda p, c, t, tr, st, vl: paged_chunk_prefill(
            p, c, t, tr, st, vl, cfg, context_pages=mpp,
            paged_attn_impl="pallas"), donate_argnums=(1,))
    args = (params, cache, sds((rows, 512)), sds((rows, mpp)), sds((rows,)),
            sds((rows,)))
    kernels = lowered_kernel_calls(program, *args)
    assert kernels["paged_chunk_attention"] == rows, kernels
    compiled = program.lower(*args).compile()
    layer_pool = pages * PAGE * cfg.n_kv_heads * cfg.head_dim * 2
    context = mpp * PAGE * cfg.n_kv_heads * cfg.head_dim * 2
    logits = rows * 512 * cfg.vocab_size * 4
    temps = compiled.memory_analysis().temp_size_in_bytes
    # What the gathered form held: K and V of every layer's context, padded
    # by a chunk, twice over (the copy and the copy as written).
    assert temps < min(layer_pool, 2 * cfg.n_layers * context), temps
    assert temps < logits + 256 * 2 ** 20, (temps, logits)


# -- the serving cells' programs copy no weight before they use it ---------------

# cell -> the parameters whose copy stands on the parent too and that no
# layout of this repo's removes: named so that nothing NEW can join them.
# GLM's decode program lays its 14 MB `wkva` stack out again a dispatch;
# LFM2's programs their 2 MB router stack. `Layout.AUTO` prefers another
# layout for both in every program (scripts/aot_weight_copies.py --auto);
# whether that is worth anything on the chip is open (PERF.md section 7).
SERVING_CELLS = {
    "mistral-7b.chat-open": set(),
    "mixtral-8x7b.batch-longprompt": set(),
    "glm-4.7-flash.batch-longcontext": {"['layers']['attn']['wkva']"},
    "lfm2-24b-a2b.batch-longanswer": {"['layers']['mlp']['router']"},
}
# (cell, program) -> scripts/aot_weight_copies.py::lowered_fingerprint of the
# program as it lowered on the commit BEFORE PR 40 (cc77c2e), which gave the
# two paged kernels a window, the pool planes of a third kind and the sorted
# expert layer a held share: none of these cells has a window or a share,
# and each of these programs is, operation for operation and kernel body for
# kernel body, what it was. A change that means to move one of them records
# the new digest here and says why. (PR 46 moved the five in-place chunk
# programs that stood here: ``CHUNK_SINCE_PR46``; PR 48 the two decode
# programs: ``DECODE_SINCE_PR48``.)
LOWERED_BEFORE_PR40 = {
    ("lfm2-24b-a2b.batch-longanswer", "chunk[1]"): "6a4770d8248e2dcb",
    ("lfm2-24b-a2b.batch-longanswer", "chunk[2]"): "3aa5bb964b3b2815",
}
# PR 45 gave ``paged_decode_attention`` another schedule (grid ``(rows,)``,
# the kernel walks a row's live pages by its own copies): the decode programs
# of the cells that attend through it are new programs, pinned here as PR 45
# left them (db316db56b64acbb / ddb784f45f5677a3 before). PR 48, which put
# the other two decode kernels on the same walk, leaves these two as they
# are: two planes of 524 KB page pairs still get four pages a turn.
DECODE_SINCE_PR45 = {
    ("mistral-7b.chat-open", "decode"): "21df67c531cb4588",
    ("mixtral-8x7b.batch-longprompt", "decode"): "2e6eb883f208d847",
}
# PR 48 gave ``paged_latent_decode_attention`` (GLM: latent rows) and
# ``paged_packed_decode_attention`` (LFM2: packed rows) that schedule too:
# grid ``(rows,)`` in place of ``(rows, pages)``, a row's live pages copied
# by the kernel itself (``_rows_decode_kernel`` under ``_walk_live_pages``),
# ONE cached inlined call for both. The two cells' decode programs are new
# programs in nothing but that kernel's body and operands, pinned here as
# PR 48 left them (before, and since before PR 40: GLM 51228f8cbfaf3acc, LFM2
# ac6c858f412bc9bb). Their chunk programs do not reach either kernel and
# stand where they were.
DECODE_SINCE_PR48 = {
    ("glm-4.7-flash.batch-longcontext", "decode"): "ed7f52be335dfbc1",
    ("lfm2-24b-a2b.batch-longanswer", "decode"): "521adff686368ef4",
}
# PR 46 put the decode step (T = 1), the chunk prefill in place (T = the
# chunk) and the speculative verify (T = k+1) behind ONE block over the pool
# (``paged._pool_block``), whose addressing is the decode block's, with a
# token axis where T > 1. Every decode digest above STANDS (the four older
# cells run the block at T = 1 operation for operation), and so do LFM2's
# three (its chunks go the gathered way, which PR 46 does not touch). What
# MOVED is every chunk program built in place: a row's write index, its
# positions and a window layer's touched pages were computed once in front of
# the layer scans by the chunk builder's own closure and are now computed by
# the block, inside the scan's body, as the decode step always did (the
# compiled programs hold the same instructions: PERF.md section 6, PR 46,
# with each cell's parent and change medians from the chip). Before PR 46:
# chat chunk[1] 9a16ab9307d01ba7; batch chunk[1] 03a77e220d400617, chunk[2]
# 24684074f747f6c2; GLM chunk[1] 57ce1b084168e27a, chunk[2] 4ca45b4b80949972.
CHUNK_SINCE_PR46 = {
    ("mistral-7b.chat-open", "chunk[1]"): "d3e188b71af063b1",
    ("mixtral-8x7b.batch-longprompt", "chunk[1]"): "837e7469f651050f",
    ("mixtral-8x7b.batch-longprompt", "chunk[2]"): "093224dc77fc0a9a",
    ("glm-4.7-flash.batch-longcontext", "chunk[1]"): "cf8d888984c356b5",
    ("glm-4.7-flash.batch-longcontext", "chunk[2]"): "a822e0159c25f6c9",
}
# The program over rows as the engine builds it since PR 41 (the head at each
# row's last valid position, ``[2, V]`` logits, under a conditional on "some
# row ends its prompt"): another program than "chunk[2]" above, which is its
# all-position form. LFM2's (gathered) stands as PR 41 left it; the two built
# in place moved with PR 46 as their all-position forms did (before:
# Mixtral 1f4f9199db3e0328, GLM 8d33877ceb87639a).
ROWS_PROGRAM_SINCE_PR41 = {
    "lfm2-24b-a2b.batch-longanswer": "661f820c73930e41",
}
ROWS_PROGRAM_SINCE_PR46 = {
    "glm-4.7-flash.batch-longcontext": "6832c82200bca8c7",
    "mixtral-8x7b.batch-longprompt": "773a9fd9ca219067",
}
# program -> the Mosaic kernel its attention goes through, a cell's family
ATTENTION_KERNELS = {
    "mistral-7b.chat-open": ("paged_decode_attention",
                             "paged_chunk_attention"),
    "mixtral-8x7b.batch-longprompt": ("paged_decode_attention",
                                      "paged_chunk_attention"),
    "glm-4.7-flash.batch-longcontext": ("paged_latent_decode_attention",
                                        "paged_latent_chunk_attention"),
    "lfm2-24b-a2b.batch-longanswer": ("paged_packed_decode_attention",
                                      None),      # packed rows stay gathered
}


@pytest.fixture(scope="module")
def cell_programs(chip):
    """``(cell, relaid) -> {program: Lowered}``, each cell lowered once a
    module: the benchmark's sizes (configuration and traffic files), the
    engine's own construction (scripts/aot_weight_copies.py), the parameters
    in the formats the engine's function returns or all default, the
    program over rows with the head where the engine asks for it (each
    row's last valid position) or over every position."""
    import functools

    from scripts.aot_weight_copies import lowered_programs, serving_cell

    @functools.lru_cache(maxsize=None)
    def lowered(cell: str, relaid: bool = True,
                rows_logits_at: str = "last", mixed: bool = False) -> dict:
        cfg, batching = serving_cell(cell)
        with pytest.MonkeyPatch.context() as mp:    # as benchmark/aot_sizes.py
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return lowered_programs(cfg, batching, chip, relaid=relaid,
                                    rows_logits_at=rows_logits_at,
                                    mixed=mixed)

    return lowered


# A dense model at 512 tokens a chunk: the engine builds no program over
# several prompts' rows (``chunk_programs.chunk_rows_per_weight``), so the
# chat cell has two.
SERVING_PROGRAMS = [
    (cell, program) for cell in sorted(SERVING_CELLS)
    for program in ("decode", "chunk[1]", "chunk[2]")
    if (cell, program) != ("mistral-7b.chat-open", "chunk[2]")]


@pytest.mark.parametrize("cell,program", SERVING_PROGRAMS)
def test_serving_program_lowers_to_what_it_was_before_pr40(cell_programs,
                                                           cell, program):
    """With no window and no share set, the decode and chunk programs of the
    four accepted serving cells lower, at the cells' shapes for a described
    v5e, to the programs of the commit before the window went into
    ``paged_decode_attention`` / ``paged_chunk_attention`` and the share
    into ``_moe_sorted`` (the decode programs of the two cells that attend
    through ``paged_decode_attention``: to PR 45's, ``DECODE_SINCE_PR45``;
    of the two whose decode kernels took the same walk: to PR 48's,
    ``DECODE_SINCE_PR48``; the chunk programs built in place: to PR 46's,
    ``CHUNK_SINCE_PR46``).
    The program over rows in its all-position form too (``logits_at``'s
    default: what every caller but the engine's program over rows takes);
    the form the engine builds since PR 41 is pinned beside it."""
    from scripts.aot_weight_copies import lowered_fingerprint

    rows = program == "chunk[2]"
    pinned = {**LOWERED_BEFORE_PR40, **DECODE_SINCE_PR45,
              **DECODE_SINCE_PR48, **CHUNK_SINCE_PR46}
    assert lowered_fingerprint(
        cell_programs(cell, True, "all" if rows else "last")[program]) \
        == pinned[cell, program]
    if rows:
        assert lowered_fingerprint(cell_programs(cell)[program]) == {
            **ROWS_PROGRAM_SINCE_PR41, **ROWS_PROGRAM_SINCE_PR46}[cell]


# (cell, program) -> the digest of the two cells whose arms the four older
# cells do not run (a window layer's ring and planes, a held share of
# experts; linear layers whose state a sequence lies in the pool).
# ``serving_cell`` builds both. "rows[2]" is the program over rows as the
# engine builds it (PR 41's form), "chunk[2]" its all-position form. Recorded
# on the commit BEFORE PR 46 (e29c218), ahead of any edit, as: mixedlength
# decode 8a3d1940de856e91, chunk[1] 8fede4ad7e9b60dd, chunk[2]
# 8dd7a78eb5524fb2, rows[2] 7c0cd4c5a51642b2; longdoc decode
# 93d131f0326d7640, chunk[1] 89bd64f404d1b287, chunk[2] 54a9679f5b6e1c57,
# rows[2] 1685c4df765cd50a. ALL EIGHT MOVED with PR 46 and stand here as it
# left them. The chunk programs for ``CHUNK_SINCE_PR46``'s reason. The decode
# programs because the one block addresses a window layer through ONE helper
# for every T (the ring's page for the write by ``_token_pages``, the touched
# pages and the first key a query sees by ``_window_pages`` and
# ``_kv_attention``: the same values from other operations) and no longer
# computes a page index for a linear layer, which writes none (the decode
# block computed one and dropped it). Both cells' parent and change medians
# from the chip: PERF.md section 6, PR 46.
LOWERED_SINCE_PR46 = {
    ("k-exaone-236b-a23b.batch-mixedlength", "decode"): "0fdfbf21e1e5d814",
    ("k-exaone-236b-a23b.batch-mixedlength", "chunk[1]"): "27ef78fcc42c5462",
    ("k-exaone-236b-a23b.batch-mixedlength", "chunk[2]"): "027d5cbd6aaba6e3",
    ("k-exaone-236b-a23b.batch-mixedlength", "rows[2]"): "6f1045fbb9f0dfd9",
    ("solar-open2-250b.batch-longdoc", "decode"): "bda4aec20a02d93c",
    ("solar-open2-250b.batch-longdoc", "chunk[1]"): "ff1cd95d0e635193",
    ("solar-open2-250b.batch-longdoc", "chunk[2]"): "a64d9e7ee9199456",
    ("solar-open2-250b.batch-longdoc", "rows[2]"): "43d827beeebc967b",
}


@pytest.mark.parametrize("cell,program", sorted(LOWERED_SINCE_PR46))
def test_newer_serving_program_lowers_to_what_pr46_left(cell_programs,
                                                           cell, program):
    """The window, ring, held-share and linear arms of the pool's block held
    to the standard of the four older cells: the mixed-length and the
    long-document cell's programs lower, at the cells' shapes for a
    described v5e, to the digests PR 46 left (the comment above names what
    moved them off the parent's)."""
    from scripts.aot_weight_copies import lowered_fingerprint

    form = "all" if program == "chunk[2]" else "last"
    name = "chunk[2]" if program == "rows[2]" else program
    assert lowered_fingerprint(cell_programs(cell, True, form)[name]) \
        == LOWERED_SINCE_PR46[cell, program]


@pytest.mark.parametrize("cell,program", SERVING_PROGRAMS)
def test_serving_program_copies_no_weight_on_v5e(cell_programs, cell,
                                                 program):
    """At the cell's real sizes, with the parameters as the engine holds
    them: the compiled program has no ``copy`` the size of a parameter (or
    of a layer's slice of one) of a million elements or more, beyond the
    cell's standing ones, and its attention is still the Mosaic kernel. The
    chat and batch cases fail on a tree whose per-head projections lie in
    the default layout (``copy.19`` / ``.18`` / ``.20`` of the chat cell's
    decode program: 0.8 GB a dispatch); the GLM and LFM2 cases pin that the
    rule changes nothing there."""
    from scripts.aot_weight_copies import weight_copies

    programs = cell_programs(cell)
    assert sorted(programs) == sorted(
        p for c, p in SERVING_PROGRAMS if c == cell)
    lowered = programs[program]
    compiled = lowered.compile()
    text = compiled.as_text()
    copied = {leaf for c in weight_copies(text, lowered.args_info[0][0])
              for leaf in c["leaf"]}
    assert copied <= SERVING_CELLS[cell], copied
    kernel = ATTENTION_KERNELS[cell][program != "decode"]
    assert "tpu_custom_call" in text
    assert kernel is None or kernel in text, kernel
    if cell == "mistral-7b.chat-open" and program == "decode":
        # the hoisted copies were the program's temporaries: 0.806 GB
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


# PR 49: the chunk program that carries the slots' decode step
# (``paged.paged_mixed_step``: the chunk's rows and the slots' rows through
# ONE layer scan, the feed-forward once over all their tokens), at its one
# width, as many rows as the engine sends chunks together: the cells whose
# every layer is of kind "attention". NEW programs, pinned as PR 49 left
# them. Every digest above stands: a program with one group of rows lowers
# to what it lowered to. The engine of these three cells no longer
# DISPATCHES the program over rows ("chunk[2]" in the engine's form,
# ``ROWS_PROGRAM_SINCE_PR46``): the two-row program below, no slot riding,
# is it. It is still built and pinned, for every other pool and for
# scripts/chunk_rows_chip.py.
MIXED_SINCE_PR49 = {
    ("mistral-7b.chat-open", "mixed[1]"): "66b2485d321d364b",
    ("mixtral-8x7b.batch-longprompt", "mixed[2]"): "005fd78fc582fd87",
    ("glm-4.7-flash.batch-longcontext", "mixed[2]"): "a58b95787ee20d98",
}


@pytest.mark.parametrize("cell,program", sorted(MIXED_SINCE_PR49))
def test_mixed_program_compiles_for_v5e_with_both_kernels(cell_programs,
                                                          cell, program):
    """At the cell's real sizes, with the parameters as the engine holds
    them: the program compiles for the described chip, holds the chunk
    kernel a row and the decode kernel by name, copies no parameter (beyond
    the cell's standing ones) and no plane of the pool, returns ``[R, V]``
    logits and a token a slot beyond the buffers it was donated, and lowers
    to the pinned digest."""
    from scripts.aot_weight_copies import (
        lowered_fingerprint, serving_cell, weight_copies,
    )

    programs = cell_programs(cell, True, "last", True)
    assert sorted(p for p in programs if p.startswith("mixed")) == sorted(
        p for c, p in MIXED_SINCE_PR49 if c == cell)
    lowered = programs[program]
    assert lowered_fingerprint(lowered) == MIXED_SINCE_PR49[cell, program]
    compiled = lowered.compile()
    text = compiled.as_text()
    copies = weight_copies(text, lowered.args_info[0][0])
    assert {leaf for c in copies for leaf in c["leaf"]} \
        <= SERVING_CELLS[cell]
    cfg, batching = serving_cell(cell)
    # nothing the size of a layer's pages of one plane is copied
    page = batching.page_size * (
        cfg.kv_lora_rank + cfg.qk_rope_dim if cfg.is_latent
        else cfg.n_kv_heads * cfg.head_dim)
    assert max([math.prod(c["shape"]) for c in copies] + [0]) \
        < batching.max_pages * page
    step_kernel, chunk_kernel = ATTENTION_KERNELS[cell]
    rows = int(program[len("mixed["):-1])
    assert "tpu_custom_call" in text
    assert chunk_kernel in text and step_kernel in text
    ma = compiled.memory_analysis()
    result = ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert rows * cfg.vocab_size * 4 <= result < 16 * 2 ** 20, result
    assert ma.temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("cell,carries", [
    ("mistral-7b.chat-open", True),
    ("mixtral-8x7b.batch-longprompt", True),
    ("glm-4.7-flash.batch-longcontext", True),
    ("lfm2-24b-a2b.batch-longanswer", False),           # conv layers
    ("k-exaone-236b-a23b.batch-mixedlength", False),    # window layers
    ("solar-open2-250b.batch-longdoc", True),   # linear layers: PR 60
    ("phi-4-mini-flash.batch-reasoning", False),        # ssm, gmu, cross
    ("falcon-h1-34b.batch-assistant", True),    # parallel layers: PR 58
    # ssd layers beside attention, a block of one sublayer: PR 61
    ("nemotron-3-super-120b-a12b.batch-agentturns", True),
])
def test_which_cells_chunk_program_carries_the_step(cell, carries):
    """The plan reads the stack and the pool (``plan_chunks``, through
    ``paged.chunk_carries_step``), at the cells' own shapes: every layer of
    a kind whose chunk and decode operators are held side by side in one
    program
    (``paged.STEP_CARRYING_KINDS``: "attention", since PR 58 "parallel",
    since PR 60 "linear", since PR 61 "ssd"); the three that stay keep a conv tail, a ring, or
    an ssm state in front of a stateless tail."""
    from scripts.aot_weight_copies import serving_cell
    from test_serve_chunk_plan import plan_of

    cfg, b = serving_cell(cell)
    assert plan_of(cfg, b, "pallas").carries_step == carries
    assert not plan_of(cfg, b, "gather").carries_step


@pytest.mark.parametrize("cell", sorted(
    c for c, p in SERVING_PROGRAMS if p == "chunk[2]"))
def test_rows_program_returns_one_position_a_row_on_v5e(cell_programs, cell):
    """The program over two prompts' rows as the engine builds it (PR 41:
    the head at each row's last valid position, under a conditional on
    "some row ends its prompt") beside its all-position form, at the cell's sizes: what it returns beyond the pool it was
    donated is ``[2, V]`` float32 and some scalars where the other form
    returns ``[2, 512, V]`` (634 MB at GLM's vocabulary, with the program
    before it still in flight). Its temporaries ALONE read larger than the
    other form's (Mixtral 83 MB where 10, LFM2 441 where 229): the compiler
    lays the other form's out inside the logits' buffer before the head
    fills it, so what is compared is what a program holds beyond its
    arguments, temporaries and result together (GLM 24 MB where 646,
    Mixtral 83 where 141, LFM2 441 where 497)."""
    from scripts.aot_weight_copies import serving_cell

    vocab = serving_cell(cell)[0].vocab_size
    last, every = (
        cell_programs(cell, True, form)["chunk[2]"].compile()
        .memory_analysis() for form in ("last", "all"))
    assert every.output_size_in_bytes - every.alias_size_in_bytes \
        >= 2 * 512 * vocab * 4
    result = last.output_size_in_bytes - last.alias_size_in_bytes
    assert 2 * vocab * 4 <= result < 16 * 2 ** 20, result
    held = {form: m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes for form, m in (("last", last),
                                                    ("all", every))}
    assert held["last"] < held["all"], held


def test_the_detector_finds_the_copies_of_default_layouts(cell_programs):
    """The guard's own guard: with every parameter in the compiler's default
    layout the chat cell's decode program copies all three per-head
    projections, whole, and the reader of the compiled text says so. (A
    change of the text's format that blinded the reader would pass every
    case above.)"""
    from scripts.aot_weight_copies import weight_copies

    lowered = cell_programs("mistral-7b.chat-open", False)["decode"]
    compiled = lowered.compile()
    copies = [c for c in weight_copies(compiled.as_text(),
                                       lowered.args_info[0][0]) if c["leaf"]]
    assert sorted(c["shape"] for c in copies) == [
        [16, 4096, 8, 128], [16, 4096, 8, 128], [16, 4096, 32, 128]]
    assert {leaf for c in copies for leaf in c["leaf"]} == {
        f"['layers']['attn']['{n}']" for n in ("wq", "wk", "wv")}
    assert compiled.memory_analysis().temp_size_in_bytes > 800e6


# -- PR 43: the two gated delta-rule kernels, and the cell that runs them ------
# -- PR 44: a third in front of the scan, for what does not read the state ----

def _calls(text: str, kernel: str) -> int:
    """Instructions of a compiled text named ``kernel`` (or ``kernel.N``)."""
    return len(re.findall(rf"^\s*(?:ROOT )?%?{kernel}[.\d]* = ", text,
                          re.M))


def _f32_relayouts(text: str, elements: int) -> list:
    """The float32 ``copy`` and ``transpose`` instructions of a compiled
    text over ``elements`` elements or more, by result shape."""
    found = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (f32\[[\d,]+\])\S* (?:copy|transpose)\(",
        text, re.M)
    return [s for s in found
            if math.prod(int(n) for n in s[4:-1].split(",")) >= elements]


def test_kda_kernels_compile_for_v5e(chip):
    """``ops/kda.py`` at Solar-Open2's widths (64 heads of 128 keys and
    values): the two chunk kernels over two rows of 512 positions
    (``kda_operands``, what does not read the state, inside the kernel's
    VMEM limit, in front of the scan ``kda_chunk``: here over operands that
    lie positions-major, as a parameter does; the cell's program, below,
    hands them over heads-major and copies none), and the step kernel
    over 32 streams whose state lies in a plane of 96 entries, ALIASED to
    the result (no copy of the plane: what the call holds beyond its
    arguments stays under the operands' few megabytes)."""
    from kubeflow_tpu.ops import kda

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    b, c, h, dk = 2, 512, 64, 128
    chunk = jax.jit(lambda q, k, v, g, beta, s: kda.kda_chunk(
        q, k, v, g, beta, s, impl="pallas", interpret=False)).lower(
        *(sds(b, c, h, dk) for _ in range(4)), sds(b, c, h),
        sds(b, h, dk, dk)).compile()
    text = chunk.as_text()
    assert "tpu_custom_call" in text
    assert (_calls(text, "kda_operands"), _calls(text, "kda_chunk")) == (1, 1)
    b = 32
    plane = sds(96, h, dk, dk)
    step = jax.jit(
        lambda q, k, v, g, beta, p, i, f, lv: kda.kda_step(
            q, k, v, g, beta, p, i, f, lv, impl="pallas", interpret=False),
        donate_argnums=(5,)).lower(
        *(sds(b, h, dk) for _ in range(4)), sds(b, h), plane,
        sds(b, dtype=jnp.int32), sds(b, dtype=jnp.bool_),
        sds(b, dtype=jnp.bool_)).compile()
    assert "kda_step" in step.as_text()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 96 * h * dk * dk * 4
    assert mem.temp_size_in_bytes < 8 * 2 ** 20, mem.temp_size_in_bytes


LONGDOC = "solar-open2-250b.batch-longdoc"


@pytest.mark.parametrize("program", ["decode", "chunk[1]", "chunk[2]"])
def test_longdoc_program_compiles_for_v5e_with_its_kernels(cell_programs,
                                                           program):
    """The long-document cell's three programs at the cell's real sizes,
    parameters as the engine holds them: each fits the chip beside its
    arguments, runs the GQA layer's paged kernel and the KDA layers' own
    (``kda_step`` in the decode step; in the chunk programs, which are
    built in place, one ``kda_operands`` in front of every ``kda_chunk``
    and no float32 array of the chunk's size copied or transposed to feed
    them: PR 44), walks its experts through the grouped
    matmul (tiles of 256 columns at experts of 1280: a whole [4096, 1280]
    tile twice over is 20 MB of the kernel's 16), and copies no weight but
    the two small low-rank second halves (``wf2`` / ``wg2``, 2 MB a
    layer)."""
    from scripts.aot_weight_copies import weight_copies

    lowered = cell_programs(LONGDOC)[program]
    compiled = lowered.compile()
    text = compiled.as_text()
    decode = program == "decode"
    for kernel in ("kda_step" if decode else "kda_chunk",
                   "paged_decode_attention" if decode
                   else "paged_chunk_attention", "gmm"):
        assert kernel in text, kernel
    if not decode:
        assert (_calls(text, "kda_operands"), _calls(text, "kda_chunk")) \
            == (3, 3)
        assert "f32[2,64,8,64,128]" not in text
        # q, k, v, g reach the kernel as the projections' fusions wrote them
        rows = int(program[len("chunk["):-1])
        assert _f32_relayouts(text, rows * 512 * 64 * 128) == []
    copied = {leaf for c in weight_copies(text, lowered.args_info[0][0])
              for leaf in c["leaf"]}
    assert copied <= {"['layers']['linear']['wf2']",
                      "['layers']['linear']['wg2']"}, copied
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11e9
    assert mem.temp_size_in_bytes < 0.4e9      # 0.363 GB, two rows (0.65 before PR 44)


# PR 60: the long-document cell's chunk program carries the slots' decode
# step where BOTH its rows hold a chunk ("mixed[2]": ``paged.paged_mixed_step``
# over a stack of one gated GQA layer and three KDA layers, at the cell's one
# width, two rows by the ridge). A NEW program, pinned as PR 60 left it; in the
# engine it REPLACES "rows[2]" (a pair with no slot live takes it with ``ride``
# false; ``ChunkPlan.programs()`` names no program over rows for this plan).
# The cell's other digests did not move and stand where they stood
# (``LOWERED_SINCE_PR46``: "decode", "chunk[1]", the ``[C, V]`` program a lone
# chunk keeps and the benchmark's ``correct`` drives, and "rows[2]", still
# built for callers that ask for it by name).
LONGDOC_SINCE_PR60 = {"mixed[2]": "7dd81f4b4577278a"}


def test_longdoc_mixed_program_copies_no_plane_and_no_weight_on_v5e(
        cell_programs):
    """The two-row chunk program that carries the 32 slots' step, at the
    cell's real sizes, parameters as the engine holds them: it fits the chip
    beside its arguments and runs BOTH groups' kernels in every layer's scan
    (``kda_operands`` in front of ``kda_chunk`` for the chunks' rows and
    ``kda_step`` for the slots', three KDA layers a scan iteration; the chunk
    kernel a row and the decode kernel for the GQA layer; the experts ONCE
    over both groups' tokens, as many grouped matmuls as the chunk program
    alone). The KDA state plane (``[3 x 32, 64, 128, 128]`` float32, 1.6 GB)
    has two writers in one program, ``kda_chunk``'s two entries by a scatter
    and ``kda_step``'s in place: it is the operand of no ``copy``, nor are K
    and V; no float32 array of the chunk's size is copied or transposed to
    feed the chunk kernels (PR 44); no weight is copied but the two small
    low-rank second halves (``wf2`` / ``wg2``, 2 MB a layer: standing, the
    chunk program's and the decode program's too). The conv-tail plane (14
    MB) is laid out again on the way in and out, as in both of those (the
    parent's: standing). What it returns beyond the buffers it was donated is
    ``[2, V]`` float32 and a token a slot. It lowers to the pinned digest."""
    from scripts.aot_weight_copies import (
        lowered_fingerprint, serving_cell, weight_copies,
    )

    programs = cell_programs(LONGDOC, True, "last", True)
    assert sorted(p for p in programs if p.startswith("mixed")) == sorted(
        LONGDOC_SINCE_PR60)
    lowered = programs["mixed[2]"]
    assert lowered_fingerprint(lowered) == LONGDOC_SINCE_PR60["mixed[2]"]
    compiled = lowered.compile()
    text = compiled.as_text()
    alone = programs["chunk[2]"].compile().as_text()
    for kernel, calls in (("kda_operands", 3), ("kda_chunk", 3),
                          ("kda_step", 3), ("paged_chunk_attention", 2),
                          ("paged_decode_attention", 1),
                          ("gmm", _calls(alone, "gmm"))):
        assert _calls(text, kernel) == calls > 0, kernel
    assert "f32[2,64,8,64,128]" not in text
    assert _f32_relayouts(text, 2 * 512 * 64 * 128) == []
    cfg, batching = serving_cell(LONGDOC)
    copies = weight_copies(text, lowered.args_info[0][0])
    assert {leaf for c in copies for leaf in c["leaf"]} <= {
        "['layers']['linear']['wf2']", "['layers']['linear']['wg2']"}
    # nothing the size of a layer's pages of K or V, or of a layer's KDA
    # states (a third of the plane), is copied; the largest copy standing
    # is the conv-tail plane's (3 x 32 entries of 9 rows of 8192)
    page = batching.page_size * cfg.n_kv_heads * cfg.head_dim
    state = cfg.linear_heads * cfg.linear_head_dim ** 2
    assert max(math.prod(c["shape"]) for c in copies) < min(
        batching.max_pages * page, batching.max_batch_size * state)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11e9
    assert mem.temp_size_in_bytes < 0.4e9      # 0.374 GB (the chunk program: 0.363)
    result = mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 2 * cfg.vocab_size * 4 <= result < 2 * cfg.vocab_size * 4 + 2 ** 20


def test_ssm_scan_compiles_for_v5e(chip):
    """``ops/ssm.py`` at Phi-4-mini-flash's widths (5120 channels of 16
    states, two rows of 512 positions): ONE Mosaic call named ``ssm_scan``
    (the two rows' ``B`` and ``C``, 128 KB of float32 scalars, fit its scalar
    memory), the state's sixteen registers a block of 1024 channels."""
    from kubeflow_tpu.ops import ssm

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    b, t, e, n = 2, 512, 5120, 16
    scan = jax.jit(lambda x, dt, bm, cm, a, d, h: ssm.ssm_scan(
        x, dt, bm, cm, a, d, h, impl="pallas", interpret=False)).lower(
        sds(b, t, e), sds(b, t, e), sds(b, t, n), sds(b, t, n), sds(n, e),
        sds(e), sds(b, n, e)).compile()
    text = scan.as_text()
    assert "tpu_custom_call" in text and _calls(text, "ssm_scan") == 1
    assert scan.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


REASONING = "phi-4-mini-flash.batch-reasoning"
# program -> the digest of the reasoning cell's programs, the one cell whose
# stack ends in a stateless tail (``paged._pool_forward``'s branch for it:
# the tail's layers run for the rows that end, at one position a row).
# "rows[2]" and "rows[1]" are the program over rows as the engine builds it
# (the one-row form is what it sends for one prompt alone), "chunk[2]" the
# all-position form. Recorded on PR 48's commit (3ad1a64) AND on PR 49's
# tree, which rewrote that branch for groups of rows (the carry a tuple of
# one group, the rows behind the tail a ``_Rows``): the same five digests,
# so the cell runs the parent's programs. A change that means to move one
# records the new digest here and says why.
REASONING_SINCE_PR48 = {
    "decode": "2dbb416d3dfd499d",
    "chunk[1]": "60c103aa02f0bca6",
    "chunk[2]": "2bdf2fdb8ffe3367",
    "rows[2]": "ea139d1679aaca64",
    "rows[1]": "d452283b3fbaf306",
}


@pytest.mark.parametrize("program", sorted(REASONING_SINCE_PR48))
def test_reasoning_program_lowers_to_what_pr48_left(cell_programs, program):
    """The ssm, gmu and cross arms of the pool's block and the stateless
    tail held to the standard of the other six serving cells: the reasoning
    cell's programs lower, at the cell's shapes for a described v5e, to the
    digests of the commit before the block took groups of rows."""
    from scripts.aot_weight_copies import lowered_fingerprint

    form = "all" if program == "chunk[2]" else "last"
    name = "chunk[2]" if program == "rows[2]" else program
    assert lowered_fingerprint(cell_programs(REASONING, True, form)[name]) \
        == REASONING_SINCE_PR48[program]


@pytest.mark.parametrize("program", ["decode", "chunk[2]"])
def test_reasoning_program_compiles_for_v5e_with_its_kernels(cell_programs,
                                                             program):
    """The reasoning cell's decode step and its two-row program over rows at
    the cell's real sizes (all 32 layers, the whole vocabulary): each fits
    the chip beside its arguments; the decode step attends through the
    global kernel at two call sites (the full layer, the cross layers' scan)
    and the window kernel over planes KEPT AS ROWS (a ``[page, 10, 128]``
    page is padded to 16 heads in device memory and the kernels' copy engine
    refuses a page of it: this compile is what said so); the chunk program
    runs one ``ssm_scan`` a Mamba layer's scan site, the chunk kernels and,
    for the tail at one position a row, the decode kernel; neither copies a
    weight but the Mamba layers' narrow ``wx`` (192 columns, 2 MB a layer):
    differential attention's q, k and v matrices lie OUT by IN, as the
    compiler wants a projection whose result is parted into heads."""
    from scripts.aot_weight_copies import weight_copies

    lowered = cell_programs(REASONING)[program]
    compiled = lowered.compile()
    text = compiled.as_text()
    decode = program == "decode"
    for kernel in (("paged_decode_attention", "paged_window_decode_attention")
                   if decode else
                   ("ssm_scan", "paged_chunk_attention",
                    "paged_window_chunk_attention",
                    "paged_decode_attention")):
        assert kernel in text, kernel
    copied = {leaf for c in weight_copies(text, lowered.args_info[0][0])
              for leaf in c["leaf"]}
    assert copied <= {"['layers']['ssm']['wx']",
                      "['layers_rest']['ssm']['wx']"}, copied
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.5e9
    assert mem.temp_size_in_bytes < 0.5e9


def test_ssd_kernels_compile_for_v5e(chip):
    """``ops/ssd.py`` at Falcon-H1's widths (32 heads of 128 values, 2
    groups, a state of 256, bfloat16 operands): the chunk kernel over one
    and two rows of 512 positions, ONE Mosaic call named ``ssd_chunk`` whose
    grid step holds a group's sixteen states beside its blocks (over the
    compiler's default of 16 MiB: ``ssd.CHUNK_VMEM_BYTES``), and the step
    kernel over 48 streams whose state lies in a plane of 240 entries,
    ALIASED to the result (no copy of the plane: what the call holds beyond
    its arguments stays under the operands' few megabytes)."""
    from kubeflow_tpu.ops import ssd

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    bf = jnp.bfloat16
    h, p, g, n = 32, 128, 2, 256
    for b in (1, 2):
        c = 512
        chunk = jax.jit(lambda x, dt, a, bm, cm, d, s: ssd.ssd_chunk(
            x, dt, a, bm, cm, d, s, impl="pallas", interpret=False)).lower(
            sds(b, c, h, p, dtype=bf), sds(b, c, h), sds(h),
            sds(b, c, g, n, dtype=bf), sds(b, c, g, n, dtype=bf), sds(h),
            sds(b, h, n, p)).compile()
        text = chunk.as_text()
        assert "tpu_custom_call" in text and _calls(text, "ssd_chunk") == 1
        assert chunk.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
    b = 48
    plane = sds(5 * 48, h, n, p)
    step = jax.jit(
        lambda x, dt, a, bm, cm, d, pl, i, f, lv: ssd.ssd_step(
            x, dt, a, bm, cm, d, pl, i, f, lv, impl="pallas",
            interpret=False), donate_argnums=(6,)).lower(
        sds(b, h, p, dtype=bf), sds(b, h), sds(h), sds(b, g, n, dtype=bf),
        sds(b, g, n, dtype=bf), sds(h), plane, sds(b, dtype=jnp.int32),
        sds(b, dtype=jnp.bool_), sds(b, dtype=jnp.bool_)).compile()
    assert _calls(step.as_text(), "ssd_step") == 1
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 48 * h * n * p * 4
    assert mem.temp_size_in_bytes < 8 * 2 ** 20, mem.temp_size_in_bytes


ASSISTANT = "falcon-h1-34b.batch-assistant"


# PR 58: the assistant cell's chunk program carries the slots' decode step
# ("mixed[1]": ``paged.paged_mixed_step`` over a stack of parallel layers, at
# the cell's one width, ONE row: a dense model at 512 tokens sends one chunk
# a program). A NEW program, pinned as PR 58 left it; it REPLACES "rows[1]",
# the program over rows at one row that the cell's traffic ran since PR 52
# (7f8fbb38f1f36822 on the parent): the engine no longer builds that one
# (``ChunkPlan.lone_at_last`` is false for an engine whose chunk program
# carries the step). "decode" and "chunk[1]" (the ``[C, V]`` program of
# callers outside the engine: the benchmark's ``correct``) lower to what they
# lowered to on the parent (1fa4a2c), recorded there ahead of any edit.
# PR 61: ``ssd_step`` reads a lane's decay as a ROW ``[1, P]`` one lane tile
# behind ``dt x`` (one form for heads of 128 and for two heads of 64 side by
# side in a tile), where it read ``[1, 1]`` and spread it: the two programs
# that hold the kernel moved (cff3492965d294ad, 91ac85ae8f9e63aa before);
# "chunk[1]" did not.
ASSISTANT_SINCE_PR58 = {
    "decode": "44a5adb2507a7a84",
    "chunk[1]": "7272d6eead7dff51",
    "mixed[1]": "83f7de518505745a",
}


@pytest.mark.parametrize("program", ["decode", "chunk[1]", "mixed[1]"])
def test_assistant_program_compiles_for_v5e_with_its_kernels(cell_programs,
                                                             program):
    """The assistant cell's three programs (a dense model at 512 tokens
    sends one chunk a program: "mixed[1]", the chunk program that carries
    the slots' decode step, is what its traffic runs since PR 58, the head
    ONCE over the chunk row's last position and the slots' tokens under a
    conditional; "chunk[1]", every position's logits, is what callers
    outside the engine drive; "decode" the step alone) at the cell's real
    sizes, parameters as the engine holds them: each lowers to its pinned
    digest, fits the chip beside its arguments, runs BOTH branches' kernels
    in every layer's scan (``ssd_step`` and the decode kernel; ``ssd_chunk``
    and the chunk kernel; the mixed program all four, each ONE call site in
    the scan's body) and copies no weight: the in-projection is held as
    three lane-aligned leaves (as ONE ``[5120, 9248]`` matrix the decode
    program copied all five layers' 0.47 GB of it in front of every step:
    this compile is what said so). Nor a plane: the SSD state plane (``[5 x
    48, 32, 256, 128]`` float32, 1.0 GB), written by two call sites in the
    mixed program (``ssd_chunk``'s one entry by a scatter, ``ssd_step``'s
    in place), and the K and V planes are no operand of any ``copy`` (the
    conv tail plane, 7 MB, is laid out again on the way in and out of every
    one of the three programs: standing, the parent's too). What a chunk
    program returns beyond the pool it was donated is ``[512, V]`` float32
    (535 MB at a vocabulary of 261120) in the one form and ``[1, V]`` (1
    MB) and a token a slot in the other."""
    from scripts.aot_weight_copies import (
        lowered_fingerprint, serving_cell, weight_copies,
    )

    programs = cell_programs(ASSISTANT, True, "last", True)
    assert set(programs) == set(ASSISTANT_SINCE_PR58)
    assert set(cell_programs(ASSISTANT)) == {"decode", "chunk[1]"}
    lowered = programs[program]
    assert lowered_fingerprint(lowered) == ASSISTANT_SINCE_PR58[program]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"decode": ("ssd_step", "paged_decode_attention"),
               "chunk[1]": ("ssd_chunk", "paged_chunk_attention")}
    for kernel in kernels.get(program, sum(kernels.values(), ())):
        assert _calls(text, kernel) == 1, kernel
    cfg, batching = serving_cell(ASSISTANT)
    copies = weight_copies(text, lowered.args_info[0][0])
    assert {leaf for c in copies for leaf in c["leaf"]} == set()
    # nothing the size of a layer's pages of K or V, or of a layer's SSD
    # states (either is a fifth of its plane), is copied
    page = batching.page_size * cfg.n_kv_heads * cfg.head_dim
    state = cfg.ssd_heads * cfg.ssd_state * cfg.ssd_head_dim
    assert max([math.prod(c["shape"]) for c in copies] + [0]) < min(
        batching.max_pages * page, batching.max_batch_size * state)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.2e9
    assert mem.temp_size_in_bytes < 0.1e9
    if program != "decode":
        positions = batching.chunked_prefill_tokens \
            if program == "chunk[1]" else 1
        logits = positions * cfg.vocab_size * 4
        result = mem.output_size_in_bytes - mem.alias_size_in_bytes
        print(f"{program}: result {result} temp {mem.temp_size_in_bytes}")
        assert logits <= result < logits + 2 ** 20, result


# -- GLM-5's widths: the indexer's scores, the selection, the masked kernels ----

def test_dsa_kernels_compile_for_v5e(chip):
    """At the agent-context cell's shapes (64 heads over 640-wide rows, 32
    index heads of 128, the pool viewed flat: 5 layers x 1568 pages of 128, a
    table row of 98 pages): ``paged_index_scores`` for one query a row of 16
    (a turn's eight pages in ONE product) and for a chunk of 512 in four
    tiles (a tile's result over the whole row, 6.4 MB twice, over the
    compiler's default of 16 MiB: ``INDEX_VMEM_BYTES``); ``dsa_select`` for
    the sixteen queries as one tile and for a chunk's four; both latent
    kernels under the selection as a second mask, page-major. What the
    chip's compiler takes here and interpret mode cannot see: a page's
    scores stored at an index of an UNTILED axis, the mask's pages put side
    by side along the lanes, integer counting passes over folded bit
    patterns."""
    from kubeflow_tpu.ops import paged_attention as pa

    slots, h, w, pages, mpp, chunk = 16, 64, 640, 5 * 1568, 98, 512
    hi, di, tile = 32, 128, pa.INDEX_QUERY_TILE

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    pool, idx = sds((pages, PAGE, w)), sds((pages, PAGE, di))
    programs = {
        "paged_index_scores": [
            jax.jit(lambda *a: pa.paged_index_scores(
                *a, interpret=False)).lower(
                sds((rows, t, hi, di)), sds((rows, t, hi), f32), idx,
                sds((rows, mpp), i32), sds((rows,), i32))
            for rows, t in ((slots, 1), (1, chunk))],
        "dsa_select": [
            jax.jit(lambda *a: pa.paged_select_keys(
                *a, 2048, interpret=False)).lower(
                sds((r, mpp, t, PAGE), f32), sds((r,), i32))
            for r, t in ((1, slots), (chunk // tile, tile))],
        "paged_latent_decode_attention": [
            jax.jit(lambda q, p, tb, ln, sel: pa.paged_latent_decode_attention(
                q, p, tb, ln, sm_scale=256 ** -0.5, selected=sel,
                interpret=False)).lower(
                sds((slots, h, w)), pool, sds((slots, mpp), i32),
                sds((slots,), i32), sds((slots, mpp, PAGE), i32))],
        "paged_latent_chunk_attention": [
            jax.jit(lambda q, p, tb, st, sel: pa.paged_latent_chunk_attention(
                q, p, tb, st, sm_scale=256 ** -0.5, selected=sel,
                interpret=False)).lower(
                sds((h, chunk, w)), pool, sds((mpp,), i32), sds((), i32),
                sds((chunk // tile, mpp, tile, PAGE), i32))],
    }
    for name, lowered in programs.items():
        for one in lowered:
            assert _calls(one.compile().as_text(), name) == 1, name


AGENTCONTEXT = "glm-5.batch-agentcontext"


@pytest.mark.parametrize("program", ["decode", "chunk[1]", "mixed[2]"])
def test_agentcontext_program_compiles_for_v5e_with_its_kernels(
        cell_programs, program):
    """The agent-context cell's decode step, its one-row ``[C, V]`` chunk
    program (what the comparison drives) and the chunk program that carries
    the slots' step (what its traffic runs: TWO rows, the engine's default
    of two prefills at a time) at the cell's real sizes,
    parameters as the engine holds them: each fits the chip beside its
    arguments and runs the indexer's kernel, the selection and the masked
    latent kernel in every layer's scan (the mixed program for BOTH groups
    of rows). The indexer's query projection is held ``[Hi x Di, q]`` (as
    ``[q, Hi, Di]`` and as ``[q, Hi x Di]`` the decode program copied the
    stacked leaf whole, transposed, in front of every step: 84 MB; this
    compile is what said so); what is still
    copied is latent attention's own ``wqb`` and ``wkva``, as in every
    latent model's programs (GLM-4.7-Flash's standing ``wkva``; ``wqb`` at
    64 heads of 256 where 20 are not: PERF.md section 7)."""
    from scripts.aot_weight_copies import weight_copies

    lowered = cell_programs(AGENTCONTEXT, mixed=program.startswith("mixed"))[
        program]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"decode": ("paged_latent_decode_attention",),
               "chunk[1]": ("paged_latent_chunk_attention",),
               "mixed[2]": ("paged_latent_decode_attention",
                            "paged_latent_chunk_attention")}[program]
    for kernel in ("paged_index_scores", "dsa_select", *kernels):
        assert _calls(text, kernel) >= 1, kernel
    copied = {leaf for c in weight_copies(text, lowered.args_info[0][0])
              for leaf in c["leaf"]}
    assert not any("idx" in leaf for leaf in copied), copied
    assert copied <= {"['layers']['attn']['wqb']",
                      "['dense_layers']['attn']['wqb']",
                      "['layers']['attn']['wkva']",
                      "['dense_layers']['attn']['wkva']"}, copied
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.0e9
    assert mem.temp_size_in_bytes < 0.6e9


VOICETURNS = "longcat-flash-omni.batch-voiceturns"


@pytest.mark.parametrize("program", ["decode", "chunk[1]", "mixed[2]"])
def test_voiceturns_program_compiles_for_v5e_with_its_kernels(
        cell_programs, program):
    """The voice-turns cell's decode step, its one-row ``[C, V]`` chunk
    program (what the comparison drives) and the chunk program that carries
    the slots' step (what its traffic runs: TWO rows) at the cell's real
    sizes, parameters as the engine holds them: each fits the chip beside
    its arguments and runs the latent kernels in every block of a pair.
    The mixed program's 13056 sorted rows (1088 tokens at twelve choices)
    take the grouped matmul's NARROWER tile (``layers._grouped_tile_columns``:
    at ``(128, 6144, 512)`` this compile is refused, 0.75 MiB over the
    kernel's fast memory: PR 57 found it here, before any chip call). A scan
    unit is a pair of blocks, and neither the pair's two dense MLPs nor its
    two attentions are copied out of their stacks: what is copied is latent
    attention's own ``wkvb`` and ``wkva``, as in every latent model's
    programs."""
    from scripts.aot_weight_copies import weight_copies

    lowered = cell_programs(VOICETURNS, mixed=program.startswith("mixed"))[
        program]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"decode": ("paged_latent_decode_attention",),
               "chunk[1]": ("paged_latent_chunk_attention",),
               "mixed[2]": ("paged_latent_decode_attention",
                            "paged_latent_chunk_attention", "gmm")}[program]
    for kernel in kernels:
        assert _calls(text, kernel) >= 1, kernel
    copied = {leaf for c in weight_copies(text, lowered.args_info[0][0])
              for leaf in c["leaf"]}
    assert copied <= {"['layers']['attn']['wkvb']",
                      "['layers']['attn']['wkva']"}, copied
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.2e9
    assert mem.temp_size_in_bytes < 0.7e9


AGENTTURNS = "nemotron-3-super-120b-a12b.batch-agentturns"


def test_ssd_kernels_compile_for_v5e_at_heads_of_64(chip):
    """``ops/ssd.py`` at Nemotron-3-Super's widths (128 heads of 64 values,
    8 groups, a state of 128): the chunk kernel over two rows of 512
    positions, and the step kernel over 128 streams whose state lies in a
    plane of 640 entries that holds TWO heads a lane tile (``[E, 64, 128,
    128]``: ``ssd.pack_state``), aliased to the result. As ``[E, 128, 128,
    64]`` the plane is held padded to twice its bytes and this compile
    showed 5.45 GB of temporaries beside it (PR 61, before any chip call);
    a ``[1, 1]`` decay read from inside a lane tile it refused outright."""
    from kubeflow_tpu.ops import ssd

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    bf = jnp.bfloat16
    h, p, g, n, b = 128, 64, 8, 128, 128
    chunk = jax.jit(lambda x, dt, a, bm, cm, d, s: ssd.ssd_chunk(
        x, dt, a, bm, cm, d, s, impl="pallas", interpret=False)).lower(
        sds(2, 512, h, p, dtype=bf), sds(2, 512, h), sds(h),
        sds(2, 512, g, n, dtype=bf), sds(2, 512, g, n, dtype=bf), sds(h),
        sds(2, h, n, p)).compile()
    assert _calls(chunk.as_text(), "ssd_chunk") == 1
    # PR 63: rows that FOLLOW one another (consecutive chunks of one
    # sequence): the grid walks a group's rows in turn, ``follows``
    # prefetched, the state a row ends in kept in VMEM for the row behind;
    # still ONE call, whatever follows; at Falcon-H1's widths too (a group's
    # sixteen states of [256, 128] once more, for the hand-over)
    plain = chunk.memory_analysis().temp_size_in_bytes
    for hh, pp, gg, nn, temp in ((h, p, g, n, plain),
                                 (32, 128, 2, 256, 64 * 2 ** 20)):
        rows = jax.jit(lambda x, dt, a, bm, cm, d, s, f: ssd.ssd_chunk(
            x, dt, a, bm, cm, d, s, follows=f, impl="pallas",
            interpret=False)).lower(
            sds(2, 512, hh, pp, dtype=bf), sds(2, 512, hh), sds(hh),
            sds(2, 512, gg, nn, dtype=bf), sds(2, 512, gg, nn, dtype=bf),
            sds(hh), sds(2, hh, nn, pp), sds(2, dtype=jnp.bool_)).compile()
        assert _calls(rows.as_text(), "ssd_chunk") == 1
        assert rows.memory_analysis().temp_size_in_bytes <= temp
    r = ssd.heads_a_tile(h, g, p)
    assert r == 2
    step = jax.jit(
        lambda x, dt, a, bm, cm, d, pl, i, f, lv: ssd.ssd_step(
            x, dt, a, bm, cm, d, pl, i, f, lv, impl="pallas",
            interpret=False), donate_argnums=(6,)).lower(
        sds(b, h, p, dtype=bf), sds(b, h), sds(h), sds(b, g, n, dtype=bf),
        sds(b, g, n, dtype=bf), sds(h), sds(5 * b, h // r, n, r * p),
        sds(b, dtype=jnp.int32), sds(b, dtype=jnp.bool_),
        sds(b, dtype=jnp.bool_)).compile()
    assert _calls(step.as_text(), "ssd_step") == 1
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * b * h * n * p * 4
    assert mem.temp_size_in_bytes < 8 * 2 ** 20, mem.temp_size_in_bytes


# PR 63: the agent-turns cell's programs, pinned for the first time, as PR 63
# left them. A row of a chunk program hands its SSD state and conv tail to
# the row behind it (``paged._ssd``): "mixed[2]", the two-row program that
# carries the slots' step, MOVED (8e709613609e32f9 on the parent, 13d2251):
# its ``ssd_chunk`` call walks (group, row, block) with ``follows``
# prefetched, and the mixer computes which row follows, selects the tail and
# drops a followed row's write. "decode" and "chunk[1]" (ONE row: nothing
# follows, the kernel walks the grid as it walked) lower to what they lowered
# to on the parent, recorded there ahead of any edit; so do the assistant
# cell's three (``ASSISTANT_SINCE_PR58``: Falcon-H1's plan is one row wide).
AGENTTURNS_SINCE_PR63 = {
    "decode": "944277da13dfd736",
    "chunk[1]": "42ca61ccb1802c46",
    "mixed[2]": "fbc6a713322917a3",
}


@pytest.mark.parametrize("program", ["decode", "mixed[2]", "chunk[1]"])
def test_agentturns_program_compiles_for_v5e_with_its_kernels(
        cell_programs, program):
    """The agent-turns cell's decode step, the chunk program that carries
    the slots' step (TWO rows, 128 slots riding; since PR 63 its second row
    is the chunk behind the first's wherever a prompt is alone) and the
    one-row program (a prompt's odd last chunk with no slot live, and what
    callers outside the engine drive: ``ChunkPlan.send``, case 5) at the
    cell's real sizes, parameters as the engine holds them:
    each fits the chip beside its arguments, runs both SSD kernels where it
    has both kinds of row, the paged attention kernels at sixteen query
    heads to a KV head, and the grouped matmul over 25,344 sorted rows of
    the LATENT's width; it copies no weight and neither the state plane nor
    the K and V planes (the conv tail plane, 39 MB, is laid out again on the
    way in and out, as Falcon-H1's is: PERF.md section 7).
    "chunk[1]" is the program that met ``pack_state``'s transposition: with
    ONE row the entry's gather is a dynamic slice, and layout assignment
    carried a ``swapaxes`` on the entry through the slice to the program's
    parameter, held the whole 2.68 GB plane transposed inside the program
    and converted it on the way in and out (PR 61's tree: ``temp`` 2.82 GB,
    two copies of 671 M elements, 15 ms a program on the chip); with two
    rows the gather is a gather and the transposition stayed on the entry.
    Since PR 62 pack and unpack are lane slices, which stay on the entry at
    every number of rows. The hand-over (PR 63) keeps ONE ``ssd_chunk`` call
    a layer (five mixers, each its own stretch of the stack) and brings no
    copy of the plane back: "mixed[2]"
    holds 0.3068 GB of temporaries (PR 62: 0.3066), the largest copy in it
    the conv plane's 19.66 M elements."""
    from scripts.aot_weight_copies import (
        lowered_fingerprint, serving_cell, weight_copies,
    )

    lowered = cell_programs(AGENTTURNS, mixed=program.startswith("mixed"))[
        program]
    assert lowered_fingerprint(lowered) == AGENTTURNS_SINCE_PR63[program]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernels = {"decode": ("ssd_step", "paged_decode_attention"),
               "mixed[2]": ("ssd_step", "ssd_chunk", "paged_decode_attention",
                            "paged_chunk_attention", "gmm"),
               "chunk[1]": ("ssd_chunk", "paged_chunk_attention", "gmm")}[
                   program]
    for kernel in kernels:
        assert _calls(text, kernel) >= 1, kernel
    cfg, batching = serving_cell(AGENTTURNS)
    if "ssd_chunk" in kernels:      # one call a layer, whatever follows
        assert _calls(text, "ssd_chunk") == cfg.kinds.count("ssd") == 5
    copies = weight_copies(text, lowered.args_info[0][0])
    assert {leaf for c in copies for leaf in c["leaf"]} == set()
    state = cfg.ssd_heads * cfg.ssd_state * cfg.ssd_head_dim
    assert max([math.prod(c["shape"]) for c in copies] + [0]) \
        < batching.max_batch_size * state
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    assert mem.temp_size_in_bytes < {"mixed[2]": 0.32e9}.get(program, 0.6e9)


def test_the_grouped_tile_is_narrower_only_where_the_rows_are_many():
    """The cells the benchmark had send at most 8320 sorted rows a call and
    keep their tile; from 9216 rows on a call whose wide tile would fill the
    kernel's fast memory takes the next narrower one."""
    from kubeflow_tpu.models.layers import _grouped_tile_columns as columns

    assert columns(2048, 6144, 8320) == columns(2048, 6144, 9216) == 512
    assert columns(2048, 6144, 12288) == columns(2048, 6144, 13056) == 256
    assert columns(6144, 2048, 13056) == 512     # the down product's tile
    assert columns(1280, 4096, 8192) == columns(1280, 4096, 16384) == 256
    assert columns(1536, 2048, 16384) == 512
    assert columns(48, 64, 16384) == 48          # a tiny preset's
