"""Fused Pallas kernel suite (ops/fused_xent.py, ops/fused_norm.py) —
numerics pinned against the unfused XLA references, the loss-path memory
claim asserted on the jaxpr, serve decode identity, dispatch stability
under the recompile sanitizer, and the input-staging double buffer.

Numerics policy (the bit-compare contract the README documents):
- forward RMSNorm / residual-add / SwiGLU(silu) / CE-nll are the SAME op
  sequence as the references → asserted BIT-identical in interpret mode;
- GeGLU's tanh polynomial may reassociate under compilation → pinned to
  float32 ulp-level tolerance;
- backward passes reduce in blocked order → pinned to fp32 tolerances.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kubeflow_tpu.models.config import preset  # noqa: E402
from kubeflow_tpu.ops import fused_norm, fused_xent  # noqa: E402

F32_TOL = 1e-6          # forward-level fp32 tolerance (pinned)
GRAD_TOL = 5e-6         # backward fp32 tolerance (pinned)


def _maxdiff(a, b):
    return float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())


def _tree_maxdiff(a, b):
    return max(_maxdiff(x, y)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -- fused cross-entropy kernel ------------------------------------------------

class TestFusedXent:
    @pytest.fixture()
    def data(self):
        k = jax.random.PRNGKey(0)
        b, s, d, v = 2, 16, 64, 256
        h = jax.random.normal(k, (b, s, d), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (d, v), jnp.float32) * 0.1
        t = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)
        return h, w, t

    @pytest.mark.parametrize("softcap", [None, 5.0])
    def test_forward_matches_reference(self, data, softcap):
        h, w, t = data
        nll, corr = fused_xent.fused_cross_entropy(h, w, t,
                                                   logits_softcap=softcap)
        rn, rc = fused_xent.reference_cross_entropy(h, w, t,
                                                    logits_softcap=softcap)
        assert _maxdiff(nll, rn) <= F32_TOL
        # argmax bookkeeping (first-occurrence ties included) is exact
        assert (corr == rc).all()

    @pytest.mark.parametrize("softcap", [None, 5.0])
    def test_gradients_match_reference(self, data, softcap):
        h, w, t = data

        def f(fn):
            return jax.grad(
                lambda h, w: fn(h, w, t, logits_softcap=softcap)[0].mean(),
                argnums=(0, 1))

        gh, gw = f(fused_xent.fused_cross_entropy)(h, w)
        rh, rw = f(fused_xent.reference_cross_entropy)(h, w)
        assert _maxdiff(gh, rh) <= GRAD_TOL
        assert _maxdiff(gw, rw) <= GRAD_TOL

    def test_under_jit_and_scan(self, data):
        h, w, t = data

        def loss(h, w):
            return fused_xent.fused_cross_entropy(h, w, t)[0].mean()

        ref = jax.grad(lambda h, w: fused_xent.reference_cross_entropy(
            h, w, t)[0].mean(), argnums=(0, 1))(h, w)
        jit_g = jax.jit(jax.grad(loss, argnums=(0, 1)))(h, w)
        assert _tree_maxdiff(jit_g, ref) <= GRAD_TOL

        def step(c, _):
            return c - 0.1 * jax.grad(loss)(c, w), loss(c, w)

        _, ls = jax.jit(lambda h: jax.lax.scan(step, h, None, length=2))(h)
        assert bool(jnp.isfinite(ls).all())

    def test_loss_mask_flows_through_cotangent(self, data):
        """Masked rows contribute exactly zero gradient (the decoder_loss
        masking composes with the kernel through the nll cotangent)."""
        h, w, t = data
        mask = (jnp.arange(t.shape[1]) < 8).astype(jnp.float32)[None, :]

        def masked(fn):
            def f(h):
                nll, _ = fn(h, w, t)
                return (nll * mask).sum() / mask.sum()
            return jax.grad(f)(h)

        gf = masked(fused_xent.fused_cross_entropy)
        gr = masked(fused_xent.reference_cross_entropy)
        assert _maxdiff(gf, gr) <= GRAD_TOL
        assert float(jnp.abs(gf[:, 8:]).max()) == 0.0

    def test_odd_shapes_fit_blocks(self):
        # Rows/vocab without 128-aligned divisors still run in interpret
        # (block fit falls back to any divisor).
        h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 24), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (24, 100), jnp.float32)
        t = jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0, 100)
        nll, _ = fused_xent.fused_cross_entropy(h, w, t)
        rn, _ = fused_xent.reference_cross_entropy(h, w, t)
        assert _maxdiff(nll, rn) <= F32_TOL


class TestFastMemoryFit:
    """Tile choice when compiling for the chip (interpret=False) counts the
    kernels' fast memory; the compile that proves the counted sizes fit is
    tests/test_chip_compile.py. Pure arithmetic here — nothing compiles."""

    ROWS, D, V = 4096, 2048, 256128            # the smoke's train step

    def test_bf16_trainer_path_keeps_its_tile(self):
        assert fused_xent._blocks(self.ROWS, self.D, self.V, 2, 2,
                                  None, None, False) == (256, 384)
        assert fused_xent.supported(self.ROWS, self.D, self.V, False)

    def test_f32_head_shrinks_the_tile_instead_of_being_refused(self):
        """The case the compiler refused: [D, bv] tiles of a float32 head
        in the d_head backward, 17 MB against a 16 MB scoped limit."""
        over = fused_xent._vmem_bytes(256, 384, self.D, 2, 4)
        assert over > fused_xent.VMEM_BUDGET_BYTES
        br, bv = fused_xent._blocks(self.ROWS, self.D, self.V, 2, 4,
                                    None, None, False)
        assert (br, bv) == (256, 128)
        assert fused_xent._vmem_bytes(br, bv, self.D, 2, 4) \
            <= fused_xent.VMEM_BUDGET_BYTES
        assert fused_xent.supported(self.ROWS, self.D, self.V, False,
                                    dtype=jnp.bfloat16,
                                    head_dtype=jnp.float32)

    def test_interpreter_keeps_the_default_tile(self):
        # The CPU tests' tiles never depend on the chip's fast memory.
        assert fused_xent._blocks(self.ROWS, self.D, self.V, 4, 4,
                                  None, None, True) == (256, 384)

    def test_supported_refuses_what_no_tile_can_hold(self):
        # hidden 131072 in float32: even the smallest aligned tile is over.
        assert fused_xent._fit_vmem(64, 131072, 1024, 4, 4) is None
        assert not fused_xent.supported(64, 131072, 1024, False,
                                        dtype=jnp.float32)
        assert fused_xent.supported(64, 131072, 1024, True)    # interpreter

    @pytest.mark.parametrize("d,tiles,want", [
        (2048, 4, 256),     # Gemma-2B add-RMSNorm: unchanged
        (4096, 3, 256),     # Llama-3-8B RMSNorm: unchanged
        (4096, 4, 128),     # Llama-3-8B add-RMSNorm: 17.9 MB at 256 rows
    ])
    def test_norm_row_block(self, d, tiles, want):
        assert fused_norm._norm_blocks(4096, d, 2, tiles, None, False) == want
        assert fused_norm._norm_blocks(4096, d, 2, tiles, None, True) == 256


# -- fused norm / swiglu kernels -----------------------------------------------

def _ref_rmsnorm(x, w, plus_one=False, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    wf = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (xf * wf).astype(x.dtype)


class TestFusedNorm:
    @pytest.fixture()
    def data(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64), jnp.float32)
        r = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 64), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (64,),
                              jnp.float32) * 0.2 + 1.0
        return x, r, w

    @pytest.mark.parametrize("plus_one", [False, True])
    def test_forward_bit_identical(self, data, plus_one):
        x, _, w = data
        o = fused_norm.rmsnorm_fused(x, w, eps=1e-5, plus_one=plus_one)
        assert (o == _ref_rmsnorm(x, w, plus_one)).all()

    @pytest.mark.parametrize("plus_one", [False, True])
    def test_gradients(self, data, plus_one):
        x, _, w = data
        gf = jax.grad(lambda x, w: (fused_norm.rmsnorm_fused(
            x, w, eps=1e-5, plus_one=plus_one) ** 2).sum(),
            argnums=(0, 1))(x, w)
        gr = jax.grad(lambda x, w: (_ref_rmsnorm(x, w, plus_one) ** 2).sum(),
                      argnums=(0, 1))(x, w)
        assert _tree_maxdiff(gf, gr) <= 1e-4   # dw sums 24 fp32 rows

    def test_add_rmsnorm_bit_identical_and_grads(self, data):
        x, r, w = data
        y, h = fused_norm.add_rmsnorm_fused(x, r, w, eps=1e-5)
        assert (y == x + r).all()
        assert (h == _ref_rmsnorm(x + r, w)).all()

        def f(fn):
            def loss(x, r, w):
                y, h = fn(x, r, w)
                return (y ** 2).sum() + (h ** 3).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(x, r, w)

        gf = f(lambda x, r, w: fused_norm.add_rmsnorm_fused(x, r, w, eps=1e-5))
        gr = f(lambda x, r, w: (x + r, _ref_rmsnorm(x + r, w)))
        assert _tree_maxdiff(gf, gr) <= 1e-4

    def test_swiglu_silu_bit_identical(self):
        g = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 128), jnp.float32)
        u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 128), jnp.float32)
        assert (fused_norm.swiglu_fused(g, u, act="silu")
                == jax.nn.silu(g) * u).all()

    def test_geglu_within_ulp_tolerance(self):
        # The documented exception to bit-identity: the gelu tanh
        # polynomial reassociates under compilation.
        g = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 128), jnp.float32)
        u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 128), jnp.float32)
        o = fused_norm.swiglu_fused(g, u, act="gelu")
        assert _maxdiff(o, jax.nn.gelu(g, approximate=True) * u) <= 1e-6

    @pytest.mark.parametrize("act", ["silu", "gelu"])
    def test_swiglu_gradients(self, act):
        g = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 128), jnp.float32)
        u = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 128), jnp.float32)
        ref = {"silu": jax.nn.silu,
               "gelu": lambda x: jax.nn.gelu(x, approximate=True)}[act]
        gf = jax.grad(lambda g, u: (fused_norm.swiglu_fused(
            g, u, act=act) ** 2).sum(), argnums=(0, 1))(g, u)
        gr = jax.grad(lambda g, u: ((ref(g) * u) ** 2).sum(),
                      argnums=(0, 1))(g, u)
        assert _tree_maxdiff(gf, gr) <= GRAD_TOL


# -- resolution ----------------------------------------------------------------

class TestResolution:
    def test_auto_is_off_off_tpu(self):
        from kubeflow_tpu.models.layers import fused_kernels_on

        cfg = preset("tiny")                      # fused_kernels="auto"
        assert fused_kernels_on(cfg) is (jax.default_backend() == "tpu")
        assert fused_kernels_on(
            dataclasses.replace(cfg, fused_kernels="on")) is True
        assert fused_kernels_on(
            dataclasses.replace(cfg, fused_kernels="off")) is False
        with pytest.raises(ValueError):
            fused_kernels_on(dataclasses.replace(cfg, fused_kernels="yes"))

    def test_multi_device_mesh_disables(self):
        from kubeflow_tpu.models.layers import fused_kernels_on
        from kubeflow_tpu.runtime.mesh import build_mesh

        cfg = dataclasses.replace(preset("tiny"), fused_kernels="on")
        mesh = build_mesh({"data": len(jax.devices())})
        if mesh.size > 1:
            assert fused_kernels_on(cfg, mesh) is False
        assert fused_kernels_on(
            cfg, build_mesh({"data": 1}, jax.devices()[:1])) is True


# -- model-level parity --------------------------------------------------------

def _f32(cfg, **over):
    return dataclasses.replace(cfg, dtype="float32", **over)


class TestDecoderLossParity:
    @pytest.mark.parametrize("name", [
        "tiny",
        pytest.param("tiny-gemma", marks=pytest.mark.slow),  # tier-1 budget:
        # the gemma variant re-runs the same parity at ~8s; tiny covers it
    ])
    def test_loss_grads_accuracy_match_dense(self, name):
        from kubeflow_tpu.models.decoder import (
            decoder_loss, init_decoder_params,
        )

        cfg_off = _f32(preset(name), fused_kernels="off")
        cfg_on = _f32(preset(name), fused_kernels="on")
        params = init_decoder_params(jax.random.PRNGKey(0), cfg_off)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 49), 0,
                                  cfg_off.vocab_size)
        l0, m0 = decoder_loss(params, toks, cfg_off)
        l1, m1 = decoder_loss(params, toks, cfg_on)
        assert abs(float(l0 - l1)) <= F32_TOL
        assert float(m0["accuracy"]) == float(m1["accuracy"])
        g0 = jax.grad(lambda p: decoder_loss(p, toks, cfg_off)[0])(params)
        g1 = jax.grad(lambda p: decoder_loss(p, toks, cfg_on)[0])(params)
        assert _tree_maxdiff(g0, g1) <= GRAD_TOL

    @pytest.mark.slow  # tier-1 budget: full K-step mesh dispatch, ~15s
    def test_scanned_k_step_dispatch_parity(self):
        """The donated K-step train dispatch (train/step.py multi_step_fn)
        picks the fused kernels up with zero signature churn and stays
        within fp32 tolerance of the unfused path."""
        from kubeflow_tpu.runtime.mesh import build_mesh
        from kubeflow_tpu.train.data import (
            DataConfig, make_data_source, stacked_batches,
        )
        from kubeflow_tpu.train.optim import OptimizerConfig
        from kubeflow_tpu.train.step import setup_train

        mesh = build_mesh({"fsdp": 1}, jax.devices()[:1])
        dc = DataConfig(vocab_size=256, seq_len=32, global_batch=2)
        batch = stacked_batches(make_data_source(dc), 0, 2)
        out = {}
        for fk in ("off", "on"):
            cfg = _f32(preset("tiny"), fused_kernels=fk,
                       remat_policy="dots_flash")
            task = setup_train(cfg, OptimizerConfig(total_steps=100), mesh)
            b = jax.device_put(batch, task.multi_batch_sharding)
            state, m = task.multi_step_fn(task.state, b)
            out[fk] = (float(m["loss"]), state["params"])
        assert abs(out["off"][0] - out["on"][0]) <= 5e-5
        assert _tree_maxdiff(out["off"][1], out["on"][1]) <= 1e-4


class TestLossMemoryFootprint:
    """The acceptance probe: the fused loss path never books a
    [B, S, vocab]-sized buffer, the unfused dense path provably does —
    asserted on every aval in the compiled-out jaxpr (an explicit
    allocation probe that is backend-independent)."""

    @staticmethod
    def _avals(closed):
        import jax.extend.core as core
        seen = []

        def walk(jaxpr):
            for v in list(jaxpr.constvars) + list(jaxpr.invars):
                seen.append(v.aval)
            for eqn in jaxpr.eqns:
                for v in eqn.outvars:
                    seen.append(v.aval)
                for p in eqn.params.values():
                    stack = [p]
                    while stack:
                        item = stack.pop()
                        if isinstance(item, core.ClosedJaxpr):
                            walk(item.jaxpr)
                        elif isinstance(item, core.Jaxpr):
                            walk(item)
                        elif isinstance(item, (tuple, list)):
                            stack.extend(item)

        walk(closed.jaxpr)
        return seen

    def test_fused_never_materializes_logits(self):
        from kubeflow_tpu.models.decoder import (
            decoder_loss, init_decoder_params,
        )

        # Dims chosen so the kernel blocks genuinely subdivide (T=512 >
        # block_rows=256, V=1024 > block_vocab=512): the biggest fused
        # tile is [256, 512] — 4x under the [B*S, V] logits.
        b, s, v = 2, 256, 1024
        base = _f32(preset("tiny"), vocab_size=v, max_seq_len=s,
                    loss_chunk_size=0)
        params = init_decoder_params(
            jax.random.PRNGKey(0), dataclasses.replace(base,
                                                       fused_kernels="off"))
        toks = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0, v)

        def big_logits(cfg):
            jx = jax.make_jaxpr(
                lambda p: decoder_loss(p, toks, cfg)[0])(params)
            return [a for a in self._avals(jx)
                    if getattr(a, "shape", ()) and a.shape[-1] == v
                    and a.size >= b * s * v]

        assert big_logits(dataclasses.replace(base, fused_kernels="off")), \
            "probe broken: the dense path must book [B,S,V] logits"
        assert not big_logits(dataclasses.replace(base, fused_kernels="on"))

    def test_fused_backward_never_materializes_logits(self):
        from kubeflow_tpu.models.decoder import (
            decoder_loss, init_decoder_params,
        )

        b, s, v = 2, 256, 1024
        base = _f32(preset("tiny"), vocab_size=v, max_seq_len=s,
                    loss_chunk_size=0, fused_kernels="on")
        params = init_decoder_params(jax.random.PRNGKey(0), base)
        toks = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0, v)
        jx = jax.make_jaxpr(
            jax.grad(lambda p: decoder_loss(p, toks, base)[0]))(params)
        big = [a for a in self._avals(jx)
               if getattr(a, "shape", ()) and a.shape[-1] == v
               and a.size >= b * s * v]
        assert not big


# -- serve decode identity -----------------------------------------------------

class TestServeDecodeIdentity:
    """The serve engine reuses the RMSNorm kernel through layers.rmsnorm:
    greedy decode must be token-identical with fused norms on vs off."""

    PROMPTS = [[5, 9, 2, 7], [3, 3, 8], [1, 2, 3, 4, 5, 6]]

    def _run(self, fk):
        from kubeflow_tpu.models.decoder import init_decoder_params
        from kubeflow_tpu.serve.engine import (
            BatchingSpec, LLMEngine, SamplingParams,
        )

        cfg = dataclasses.replace(preset("tiny"), fused_kernels=fk)
        params = init_decoder_params(jax.random.PRNGKey(0), cfg)
        eng = LLMEngine(cfg, BatchingSpec(max_batch_size=2, max_seq_len=48,
                                          paged=True, page_size=8),
                        params=params)
        try:
            return [eng.generate(list(p), SamplingParams(max_new_tokens=8))
                    for p in self.PROMPTS]
        finally:
            eng.stop()

    def test_paged_greedy_identical(self):
        assert self._run("off") == self._run("on")


# -- recompile stability -------------------------------------------------------

class TestRecompileStability:
    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~9s; steady-state
    # recompile discipline is also gated by the F6xx sanitizer tests
    def test_warmed_fused_train_step_zero_steady_recompiles(self):
        """KFTPU_SANITIZE=recompile over a warmed fused-kernel train step:
        every compile lands in warmup, none after (the F6xx runtime
        cross-check for the new dispatch surface)."""
        from kubeflow_tpu.runtime.mesh import build_mesh
        from kubeflow_tpu.runtime.sanitize import (
            install_recompile_watchdog, recompile_report,
            uninstall_recompile_watchdog,
        )
        from kubeflow_tpu.train.optim import OptimizerConfig
        from kubeflow_tpu.train.step import setup_train

        wd = install_recompile_watchdog()
        wd.reset()
        try:
            cfg = dataclasses.replace(
                preset("tiny", vocab_size=256, max_seq_len=32),
                fused_kernels="on", remat_policy="dots_flash")
            task = setup_train(cfg, OptimizerConfig(warmup_steps=0),
                               build_mesh({"data": 1}, jax.devices()[:1]))
            batch = np.random.default_rng(0).integers(
                0, cfg.vocab_size, (4, 17), dtype=np.int32)
            put = lambda: jax.device_put(batch, task.batch_sharding)  # noqa: E731
            state, _ = task.step_fn(task.state, put())
            wd.mark_warm()
            state, _ = task.step_fn(state, put())
            state, _ = task.step_fn(state, put())
            assert wd.steady_count() == 0, recompile_report()["steady"]
        finally:
            uninstall_recompile_watchdog()


# -- input staging double buffer -----------------------------------------------

class TestDeviceBatchStager:
    def test_sequential_prefetch_matches_direct(self):
        from kubeflow_tpu.train.staging import DeviceBatchStager

        calls = []

        def fetch(i):
            calls.append(i)
            return i * 10

        with DeviceBatchStager(fetch, start=3, depth=2) as st:
            got = [st.get(i, timeout=5.0) for i in range(3, 9)]
        assert got == [i * 10 for i in range(3, 9)]
        assert calls[:6] == list(range(3, 9))

    def test_out_of_order_consumption_raises(self):
        from kubeflow_tpu.train.staging import DeviceBatchStager

        with DeviceBatchStager(lambda i: i, start=0) as st:
            st.get(0, timeout=5.0)
            with pytest.raises(RuntimeError, match="sequential"):
                st.get(5, timeout=5.0)

    def test_fetch_error_propagates(self):
        from kubeflow_tpu.train.staging import DeviceBatchStager

        def fetch(i):
            if i == 1:
                raise ValueError("boom")
            return i

        with DeviceBatchStager(fetch, start=0) as st:
            assert st.get(0, timeout=5.0) == 0
            with pytest.raises(RuntimeError, match="index 1"):
                st.get(1, timeout=5.0)

    def test_close_unblocks_producer(self):
        from kubeflow_tpu.train.staging import DeviceBatchStager

        st = DeviceBatchStager(lambda i: bytes(16), start=0, depth=1)
        st.get(0, timeout=5.0)
        st.close()                       # producer blocked on put: must exit
        assert not st._thread.is_alive()


# -- XLA perf flag merging -----------------------------------------------------

class TestXlaPerfFlags:
    """The TPU-only flags go where the installed stack accepts them:
    LIBTPU_INIT_ARGS. jaxlib parses XLA_FLAGS itself and aborts the process
    on a flag it does not know, so that variable is never touched."""

    def test_merges_without_overriding(self):
        from kubeflow_tpu.runtime.xla_flags import PERF_FLAGS, xla_perf_flags

        pinned = "--xla_tpu_enable_latency_hiding_scheduler=false"
        merged = xla_perf_flags(pinned)
        assert merged.startswith(pinned)
        assert merged.count("xla_tpu_enable_latency_hiding_scheduler") == 1
        for name in PERF_FLAGS:
            assert name in merged

    def test_escape_hatch(self):
        from kubeflow_tpu.runtime.xla_flags import xla_perf_flags

        assert xla_perf_flags("--a=b", "off") == "--a=b"
        assert xla_perf_flags("--a=b", "0") == "--a=b"
        assert xla_perf_flags("--a=b", "--custom=1") == "--a=b --custom=1"

    def test_apply_idempotent_and_leaves_xla_flags_alone(self, monkeypatch):
        import os

        from kubeflow_tpu.runtime import xla_flags

        monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
        monkeypatch.delenv(xla_flags.ESCAPE_ENV, raising=False)
        assert xla_flags.apply_xla_perf_flags() is True
        first = os.environ["LIBTPU_INIT_ARGS"]
        assert all(name in first for name in xla_flags.PERF_FLAGS)
        assert xla_flags.apply_xla_perf_flags() is False
        assert os.environ["LIBTPU_INIT_ARGS"] == first
        assert os.environ["XLA_FLAGS"] == \
            "--xla_force_host_platform_device_count=8"
