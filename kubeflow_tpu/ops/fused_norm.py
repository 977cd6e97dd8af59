"""Pallas TPU fused RMSNorm(+residual-add) and SwiGLU kernels.

The non-attention memory-bandwidth losses of the train step: RMSNorm reads
its input twice in XLA (reduction pass + scale pass) and the residual add
round-trips the stream separately; the gated-MLP activation keeps
``silu(gate)``/``sigmoid(gate)`` intermediates alive for the backward.
Each kernel here is one VMEM-resident pass with a custom VJP:

- ``rmsnorm_fused(x, w)``: one read of x, fp32 statistics in VMEM, one
  write; saves the per-row ``rstd`` (fp32 [T, 1]) so the backward is a
  single recompute-free pass emitting dx and dw together.
- ``add_rmsnorm_fused(x, res, w)``: fuses the residual add into the same
  pass and returns BOTH the new residual stream ``y = x + res`` and
  ``rmsnorm(y)`` — the decoder-block idiom (models/decoder.py) without a
  separate elementwise dispatch on the stream.
- ``swiglu_fused(gate, up)``: ``act(gate) * up`` (silu or tanh-gelu) in
  one pass; the VJP recomputes the activation derivative from the saved
  primals instead of stashing ``act(gate)`` — residuals are the two
  matmul outputs the remat policy already governs.

Numerics policy (pinned in tests/test_fused_kernels.py): the forward is
the SAME op sequence as the unfused reference (native-dtype add, fp32
statistics/activation math, cast at the write), so in interpret mode it
is bit-identical; backward reductions run in a different (blocked) order
and are pinned to fp32 tolerance instead. ``interpret=`` resolves
automatically off-TPU like ops/flash_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import VMEM_BUDGET_BYTES, auto_interpret
from kubeflow_tpu.ops.fused_xent import _fit_dim

# Row-block preference: bounds fp32 VMEM residency at [rows, D]; fitted
# down to a divisor of the actual row count.
DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 1024    # swiglu only: the mlp dim blocks freely


def norm_supported(rows: int, d: int,
                   interpret: Optional[bool] = None) -> bool:
    """Mosaic tiling guard (interpret takes anything): 128-lane hidden,
    8-sublane rows."""
    interp = interpret if interpret is not None else auto_interpret()
    if interp:
        return True
    return d % 128 == 0 and rows % 8 == 0


# -- RMSNorm -------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps: float,
                    plus_one: bool, r_ref=None, y_ref=None):
    x = x_ref[...]
    if r_ref is not None:
        # Residual add in the NATIVE activation dtype — the same op the
        # unfused path runs, so the stream stays bit-identical.
        x = x + r_ref[...]
        y_ref[...] = x
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    wf = w_ref[...].astype(jnp.float32)
    if plus_one:
        wf = 1.0 + wf
    o_ref[...] = (xf * inv * wf).astype(o_ref.dtype)
    rstd_ref[...] = inv


def _residual_fwd_kernel(x_ref, r_ref, w_ref, y_ref, o_ref, rstd_ref, *,
                         eps: float, plus_one: bool):
    _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, eps=eps,
                    plus_one=plus_one, r_ref=r_ref, y_ref=y_ref)


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, dh_ref, dx_ref, dw_ref,
                    dw_acc, *, plus_one: bool, num_blocks: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    xf = x_ref[...].astype(jnp.float32)
    inv = rstd_ref[...]                               # [br, 1] fp32
    xhat = xf * inv
    dhf = dh_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32)
    if plus_one:
        wf = 1.0 + wf
    dxhat = dhf * wf
    dw_acc[:] += jnp.sum(dhf * xhat, axis=0, keepdims=True)
    c = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((dxhat - xhat * c) * inv).astype(dx_ref.dtype)

    @pl.when(ti == num_blocks - 1)
    def _flush():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)


def _norm_blocks(rows: int, d: int, itemsize: int, tiles: int,
                 block_rows: Optional[int], interpret: bool) -> int:
    """Row block: the caller's, else the largest 8-aligned divisor under
    the default — halved, when compiling for the chip, until the
    ``tiles`` double-buffered ``[br, d]`` in/out blocks of the op's
    hungriest kernel fit fast memory (add-RMSNorm at hidden 4096 wanted
    17.9 MB of the 16 MB scoped limit at 256 rows)."""
    if block_rows:
        return block_rows
    pref = DEFAULT_BLOCK_ROWS
    if not interpret:
        while pref > 8 and 2 * tiles * pref * d * itemsize > VMEM_BUDGET_BYTES:
            pref //= 2
    return _fit_dim(rows, pref, 8)


def _rms_fwd_call(x2, r2, w2, eps, plus_one, br, interpret):
    """Shared pallas_call builder for the plain and residual forwards."""
    rows, d = x2.shape
    nt = rows // br
    row_spec = pl.BlockSpec((br, d), lambda ti: (ti, 0))
    w_spec = pl.BlockSpec((1, d), lambda ti: (0, 0))
    stat_spec = pl.BlockSpec((br, 1), lambda ti: (ti, 0))
    if r2 is None:
        return pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps, plus_one=plus_one),
            name="rmsnorm_fwd",
            grid=(nt,),
            in_specs=[row_spec, w_spec],
            out_specs=(row_spec, stat_spec),
            out_shape=(jax.ShapeDtypeStruct((rows, d), x2.dtype),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
            interpret=interpret,
        )(x2, w2)
    y, o, rstd = pl.pallas_call(
        functools.partial(_residual_fwd_kernel, eps=eps, plus_one=plus_one),
        name="add_rmsnorm_fwd",
        grid=(nt,),
        in_specs=[row_spec, row_spec, w_spec],
        out_specs=(row_spec, row_spec, stat_spec),
        out_shape=(jax.ShapeDtypeStruct((rows, d), x2.dtype),
                   jax.ShapeDtypeStruct((rows, d), x2.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        interpret=interpret,
    )(x2, r2, w2)
    return y, o, rstd


def _rms_bwd_call(x2, w2, rstd, dh2, plus_one, br, interpret):
    rows, d = x2.shape
    nt = rows // br
    row_spec = pl.BlockSpec((br, d), lambda ti: (ti, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, plus_one=plus_one,
                          num_blocks=nt),
        name="rmsnorm_bwd",
        grid=(nt,),
        in_specs=[
            row_spec,
            pl.BlockSpec((1, d), lambda ti: (0, 0)),
            pl.BlockSpec((br, 1), lambda ti: (ti, 0)),
            row_spec,
        ],
        out_specs=(row_spec, pl.BlockSpec((1, d), lambda ti: (0, 0))),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((rows, d), x2.dtype),
                   jax.ShapeDtypeStruct((1, d), w2.dtype)),
        interpret=interpret,
    )(x2, w2, rstd, dh2)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _rmsnorm(x2, w2, eps, plus_one, br, interpret):
    o, _ = _rms_fwd_call(x2, None, w2, eps, plus_one, br, interpret)
    return o


def _rmsnorm_vjp_fwd(x2, w2, eps, plus_one, br, interpret):
    o, rstd = _rms_fwd_call(x2, None, w2, eps, plus_one, br, interpret)
    return o, (x2, w2, rstd)


def _rmsnorm_vjp_bwd(eps, plus_one, br, interpret, res, dh2):
    x2, w2, rstd = res
    return _rms_bwd_call(x2, w2, rstd, dh2, plus_one, br, interpret)


_rmsnorm.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _add_rmsnorm(x2, r2, w2, eps, plus_one, br, interpret):
    y, o, _ = _rms_fwd_call(x2, r2, w2, eps, plus_one, br, interpret)
    return y, o


def _add_rmsnorm_vjp_fwd(x2, r2, w2, eps, plus_one, br, interpret):
    y, o, rstd = _rms_fwd_call(x2, r2, w2, eps, plus_one, br, interpret)
    return (y, o), (y, w2, rstd)


def _add_rmsnorm_vjp_bwd(eps, plus_one, br, interpret, res, cts):
    y, w2, rstd = res
    dy, dh = cts
    dxn, dw = _rms_bwd_call(y, w2, rstd, dh, plus_one, br, interpret)
    # y = x + r feeds both outputs: each input's cotangent is the stream
    # cotangent plus the norm's dx (XLA fuses this elementwise add).
    dx = (dy + dxn).astype(y.dtype)
    return dx, dx, dw


_add_rmsnorm.defvjp(_add_rmsnorm_vjp_fwd, _add_rmsnorm_vjp_bwd)


def rmsnorm_fused(x: jax.Array, w: jax.Array, *, eps: float,
                  plus_one: bool = False,
                  block_rows: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Fused RMSNorm over the last dim; ``x`` [..., D], ``w`` [D]."""
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    interp = interpret if interpret is not None else auto_interpret()
    # Hungriest kernel: the backward's x, dh in and dx out.
    br = _norm_blocks(x2.shape[0], d, x2.dtype.itemsize, 3, block_rows,
                      interp)
    o = _rmsnorm(x2, w.reshape(1, d), eps, plus_one, br, interp)
    return o.reshape(x.shape)


def add_rmsnorm_fused(x: jax.Array, res: jax.Array, w: jax.Array, *,
                      eps: float, plus_one: bool = False,
                      block_rows: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Fused ``y = x + res; h = rmsnorm(y)``; returns ``(y, h)``."""
    d = x.shape[-1]
    x2, r2 = x.reshape(-1, d), res.reshape(-1, d)
    interp = interpret if interpret is not None else auto_interpret()
    # Hungriest kernel: the forward's x, res in and y, h out.
    br = _norm_blocks(x2.shape[0], d, x2.dtype.itemsize, 4, block_rows,
                      interp)
    y, o = _add_rmsnorm(x2, r2, w.reshape(1, d), eps, plus_one, br, interp)
    return y.reshape(x.shape), o.reshape(x.shape)


# -- SwiGLU / GeGLU ------------------------------------------------------------

def _act_and_grad(g: jax.Array, act: str, with_grad: bool):
    """fp32 activation value (and its derivative when ``with_grad``).
    Values go through the jax.nn ops so the forward stays bit-identical
    to the unfused ``_act`` path; derivatives are the closed forms."""
    if act == "silu":
        val = jax.nn.silu(g)
        if not with_grad:
            return val, None
        sg = jax.nn.sigmoid(g)
        return val, sg * (1.0 + g * (1.0 - sg))
    if act == "gelu":
        val = jax.nn.gelu(g, approximate=True)
        if not with_grad:
            return val, None
        # tanh-approximate gelu derivative.
        a = 0.7978845608028654        # sqrt(2 / pi)
        b = 0.044715
        t = jnp.tanh(a * (g + b * g ** 3))
        return val, 0.5 * (1.0 + t) + \
            0.5 * g * (1.0 - t * t) * a * (1.0 + 3.0 * b * g * g)
    raise ValueError(f"unknown activation {act!r}")


def _swiglu_fwd_kernel(g_ref, u_ref, o_ref, *, act: str):
    gf = g_ref[...].astype(jnp.float32)
    val, _ = _act_and_grad(gf, act, with_grad=False)
    o_ref[...] = (val * u_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _swiglu_bwd_kernel(g_ref, u_ref, do_ref, dg_ref, du_ref, *, act: str):
    gf = g_ref[...].astype(jnp.float32)
    uf = u_ref[...].astype(jnp.float32)
    dof = do_ref[...].astype(jnp.float32)
    val, dval = _act_and_grad(gf, act, with_grad=True)
    dg_ref[...] = (dof * uf * dval).astype(dg_ref.dtype)
    du_ref[...] = (dof * val).astype(du_ref.dtype)


def _swiglu_blocks(rows: int, cols: int):
    return (_fit_dim(rows, DEFAULT_BLOCK_ROWS, 8),
            _fit_dim(cols, DEFAULT_BLOCK_COLS, 128))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _swiglu(g2, u2, act, br, bm, interpret):
    rows, m = g2.shape
    spec = pl.BlockSpec((br, bm), lambda ti, mi: (ti, mi))
    return pl.pallas_call(
        functools.partial(_swiglu_fwd_kernel, act=act),
        name="glu_fwd",
        grid=(rows // br, m // bm),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, m), g2.dtype),
        interpret=interpret,
    )(g2, u2)


def _swiglu_vjp_fwd(g2, u2, act, br, bm, interpret):
    return _swiglu(g2, u2, act, br, bm, interpret), (g2, u2)


def _swiglu_vjp_bwd(act, br, bm, interpret, res, do2):
    g2, u2 = res
    rows, m = g2.shape
    spec = pl.BlockSpec((br, bm), lambda ti, mi: (ti, mi))
    dg, du = pl.pallas_call(
        functools.partial(_swiglu_bwd_kernel, act=act),
        name="glu_bwd",
        grid=(rows // br, m // bm),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, m), g2.dtype),
                   jax.ShapeDtypeStruct((rows, m), u2.dtype)),
        interpret=interpret,
    )(g2, u2, do2)
    return dg, du


_swiglu.defvjp(_swiglu_vjp_fwd, _swiglu_vjp_bwd)


def swiglu_fused(gate: jax.Array, up: jax.Array, *, act: str = "silu",
                 interpret: Optional[bool] = None) -> jax.Array:
    """Fused gated activation ``act(gate) * up`` over matching [..., M]
    inputs (``act``: "silu" → SwiGLU, "gelu" → GeGLU)."""
    if gate.shape != up.shape:
        raise ValueError(f"gate {gate.shape} != up {up.shape}")
    m = gate.shape[-1]
    g2, u2 = gate.reshape(-1, m), up.reshape(-1, m)
    interp = interpret if interpret is not None else auto_interpret()
    br, bm = _swiglu_blocks(g2.shape[0], m)
    return _swiglu(g2, u2, act, br, bm, interp).reshape(gate.shape)
