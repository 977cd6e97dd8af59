"""The Mamba-1 selective scan (arXiv:2312.00752): a diagonal state-space
recurrence a channel, whose step, input and output matrices depend on the
token.

For channel ``e`` of ``E`` and state ``n`` of ``N``, with ``A[n, e] < 0``::

    h_t[n, e] = exp(Delta_t[e] A[n, e]) h_(t-1)[n, e] + Delta_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n h_t[n, e] C_t[n] + D[e] x_t[e]

No matrix product at all: per channel, state and token one exponential and a
handful of multiply-adds, so the kernel is bound by the vector and the
transcendental units and by nothing the chip publishes a peak for (the bus
is the nearest published roof: ``x``, ``Delta`` and ``y`` are each read or
written once).

The state is laid out ``[N, E]``: channels on the lanes. ``[E, N]`` would
put 16 states on 128 lanes and fill an eighth of a register.

Three forms of the same sums:

- ``ssm_step_xla``: ONE token a row (the decode step, and the body of the
  form below);
- ``ssm_scan_xla``: a ``lax.scan`` over positions: the CPU tests',
  ``decoder_forward``'s and the gathered chunk's, and what the kernel is
  tested against;
- ``ssm_scan`` with ``impl="pallas"``: the kernel ``ssm_scan``: a grid over
  rows, blocks of 1024 channels (one register of 8 x 128 a state) and blocks
  of positions; the 16 registers of a block's state stay in registers across
  the positions of a block and in VMEM across blocks; ``B_t[n]`` and
  ``C_t[n]`` are SCALARS (prefetched into scalar memory) splat over a
  register, so no step moves a value across lanes; ``y`` and the end state
  are written once.

A position whose ``Delta`` is 0 leaves the state as it was (``exp(0) h + 0``):
how a chunk's padded tail passes through (``layers.ssm_inputs`` zeroes it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import auto_interpret

LANES = 128
# channel rows of 128 lanes a block: 8, one float32 register a state
BLOCK_ROWS = 8
# positions a grid step: x, Delta and y blocks of [256, 8, 128] float32
# (1 MiB each, twice for the pipeline) beside the state
BLOCK_T = 256


def ssm_step_xla(x, delta, bm, cm, a, d, h):
    """One token a row: x, delta [B, E]; bm, cm [B, N]; a [N, E]; d [E];
    h [B, N, E], all float32. Returns (y [B, E], the state after it)."""
    h = jnp.exp(delta[:, None, :] * a) * h \
        + (delta * x)[:, None, :] * bm[:, :, None]
    return jnp.sum(h * cm[:, :, None], axis=1) + d * x, h


def ssm_scan_xla(x, delta, bm, cm, a, d, h0):
    """``T`` tokens a row from ``h0`` [B, N, E]: x, delta [B, T, E]; bm, cm
    [B, T, N]. Returns (y [B, T, E], the state after the last token)."""
    def step(h, xs):
        y, h = ssm_step_xla(*xs, a, d, h)
        return h, y

    h, y = jax.lax.scan(step, h0, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, delta, bm, cm)))
    return jnp.swapaxes(y, 0, 1), h


def scan_supported(channels: int) -> bool:
    """Whether the kernel takes ``channels``: whole rows of 128 lanes."""
    return channels % LANES == 0


def _scan_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref,
                 h_ref, state, *, n_state: int, block_t: int, tokens: int):
    row, tb = pl.program_id(0), pl.program_id(2)

    @pl.when(tb == 0)
    def _():
        state[...] = h0_ref[0]

    base = (row * tokens + tb * block_t) * n_state

    def step(t, hs):
        x, dt = x_ref[0, t], dt_ref[0, t]               # [rows, 128]
        dx = dt * x
        y = d_ref[...] * x
        at = base + t * n_state
        out = []
        for n in range(n_state):
            h = jnp.exp(dt * a_ref[n]) * hs[n] + dx * b_ref[at + n]
            y = y + h * c_ref[at + n]
            out.append(h)
        y_ref[0, t] = y
        return tuple(out)

    hs = jax.lax.fori_loop(0, block_t, step,
                           tuple(state[n] for n in range(n_state)))
    for n in range(n_state):
        state[n] = hs[n]

    @pl.when(tb == pl.num_programs(2) - 1)
    def _():
        h_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(x, delta, bm, cm, a, d, h0, *, interpret: bool):
    b, t, e = x.shape
    n = a.shape[0]
    rows = e // LANES
    br = BLOCK_ROWS if rows % BLOCK_ROWS == 0 else rows
    bt = BLOCK_T if t % BLOCK_T == 0 else t

    def lanes(v):       # [..., E] -> [..., E/128, 128]
        return v.reshape(*v.shape[:-1], rows, LANES)

    def over_t(ri, ei, ti, *_):
        return ri, ti, ei, 0

    def state_map(ri, ei, ti, *_):
        return ri, 0, ei, 0

    tokens_spec = pl.BlockSpec((1, bt, br, LANES), over_t)
    state_spec = pl.BlockSpec((1, n, br, LANES), state_map)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n, block_t=bt, tokens=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, rows // br, t // bt),
            in_specs=[
                tokens_spec, tokens_spec,
                pl.BlockSpec((n, br, LANES), lambda ri, ei, ti, *_: (0, ei, 0)),
                pl.BlockSpec((br, LANES), lambda ri, ei, ti, *_: (ei, 0)),
                state_spec],
            out_specs=[tokens_spec, state_spec],
            scratch_shapes=[pltpu.VMEM((n, br, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(bm.reshape(-1), cm.reshape(-1), lanes(x), lanes(delta), lanes(a),
      lanes(d), lanes(h0))
    return y.reshape(b, t, e), h.reshape(b, n, e)


def ssm_scan(x, delta, bm, cm, a, d, h0, *, impl: str = "xla",
             interpret: Optional[bool] = None):
    """The selective scan over ``T`` tokens a row from a start state to an
    end state. x, delta [B, T, E]; bm, cm [B, T, N]; a [N, E] (negative); d
    [E]; h0 [B, N, E]; float32 throughout. ``impl`` "pallas": the kernel
    ``ssm_scan`` where it takes the channels (``scan_supported``), "xla": the
    scan over positions. Returns (y [B, T, E], the end state [B, N, E])."""
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown ssm impl {impl!r}; one of xla|pallas")
    f32 = jnp.float32
    args = tuple(v.astype(f32) for v in (x, delta, bm, cm, a, d, h0))
    if impl == "pallas" and scan_supported(x.shape[-1]):
        return _scan_call(
            *args, interpret=auto_interpret() if interpret is None
            else interpret)
    return ssm_scan_xla(*args)
