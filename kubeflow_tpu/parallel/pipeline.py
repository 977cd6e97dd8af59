"""Pipeline parallelism: microbatch streaming over the ``pipeline`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.6: 'not implemented'
— user images bring Megatron/DeepSpeed). TPU-natively, stages live on
ICI-neighbor devices and activations hop stage→stage with `lax.ppermute`
inside `shard_map` — the collective-pipelining recipe (cf. the public
scaling-book/praxis pattern), not an NCCL p2p translation.

Two schedules:

- **GPipe** — m microbatches through n stages in m+n-1 ticks; at tick t
  stage s runs microbatch t-s (bubble ticks are masked compute, fraction
  (n-1)/(m+n-1)). The whole schedule is a `lax.scan`, so it jits once and
  differentiates (reverse-mode produces the mirrored backward pipeline).
  Autodiff stashes one boundary activation per microbatch per stage, so m
  is capped at 2·stages — bubble floor ≈ ⅓.
- **1F1B** (``schedule="1f1b"``) — the forward is the same streaming scan,
  but the backward is a hand-written interleaved schedule (custom_vjp): per
  super-tick each stage runs one forward (recompute) and one backward of an
  *earlier* microbatch, with activations hopping forward and cotangents
  hopping backward in the same tick. Live stage-inputs are bounded by a
  ring buffer of depth 2n-1 — **independent of m** — so microbatch count
  (and thus bubble fraction (n-1)/(m+n-1)) is no longer memory-capped.
  FLOPs: 3 forwards + 1 backward per microbatch per stage (the fwd lane
  regenerates ring inputs and the vjp's primal re-runs the stage), ~25%
  over checkpointed GPipe's 2 fwd + 1 bwd — the price of the
  m-independent ring.

Both compose with the data axes in the same mesh (``batch_axes`` shards the
batch dim of the streamed pytree). Stage weights: leading dim sharded over
``pipeline``.

Composition beyond data axes goes through ``x_specs`` / ``param_specs``:
callers may shard additional dims of the streamed pytree (e.g. the sequence
dim over ``seq`` for PP×SP ring attention) or of the stage params (e.g. the
expert dim over ``expert`` for PP×EP MoE), and run the matching collectives
inside ``stage_fn`` — every mesh axis is a named collective axis inside the
worker. The GPipe schedule differentiates through shard_map (psums for
replicated operands are inserted by the transpose); the hand-written 1F1B
backward derives its gradient-sync psums from the specs: parameter grads
psum over every axis the streamed pytree is sharded on but the param is not,
and input cotangents psum over every axis the params are sharded on (minus
the pipeline axis itself) but the stream is not.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

StageFn = Callable[[Any, Any], Any]


def stack_stage_params(per_stage: list[Any]) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def pipeline_apply(
    stage_fn: StageFn,
    stage_params: Any,                # leaves [n_stages, ...], pipeline-sharded
    xs: Any,                          # pytree; every leaf [batch, ...]
    *,
    mesh: Mesh,
    num_microbatches: int | None = None,
    axis_name: str = "pipeline",
    batch_axes: tuple = ("dcn", "data", "fsdp"),
    checkpoint_stages: bool = True,
    schedule: str = "gpipe",
    x_specs: Any = None,              # pytree of PartitionSpec matching xs
    param_specs: Any = None,          # pytree of PartitionSpec, dim0=pipeline
) -> Any:
    """Run ``y = stage_{n-1}(... stage_0(xs))`` pipelined over microbatches.

    ``stage_fn(params_one_stage, xs_mb) -> ys_mb`` must preserve the pytree
    structure and leaf shapes (the transformer-stack contract). Every leaf
    streams with the microbatch; the batch dim may additionally be sharded
    over ``batch_axes``. With ``schedule="gpipe"``, ``num_microbatches=None``
    auto-picks the largest m ≤ 2·stages dividing the local batch (autodiff
    stashes per-microbatch activations — bubble ≤ ⅓); with ``"1f1b"`` the
    stash is a fixed 2n-1 ring so auto-m rises to ≤ 4·stages and any m is
    legal (every leaf must then be inexact — stream ints via closure).
    Returns the same pytree, [batch, ...] per leaf."""
    n_stages = mesh.shape[axis_name]
    leaves = jax.tree.leaves(xs)
    batch = leaves[0].shape[0]
    data_shards = 1
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    for a in batch_axes:
        data_shards *= mesh.shape[a]
    local_batch = batch // data_shards
    if num_microbatches is None:
        m_cap = (4 if schedule == "1f1b" else 2) * n_stages
        num_microbatches = next(
            (m for m in range(min(m_cap, max(local_batch, 1)), 0, -1)
             if local_batch % m == 0), 1)
    if batch % data_shards or local_batch % num_microbatches:
        raise ValueError(
            f"batch {batch} must be divisible by data shards {data_shards} × "
            f"num_microbatches {num_microbatches}")
    if param_specs is None:
        param_specs = jax.tree.map(
            lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    if x_specs is None:
        x_specs = jax.tree.map(
            lambda a: P(batch_axes or None, *([None] * (a.ndim - 1))), xs)
    if schedule == "1f1b":
        return _pipeline_1f1b(
            stage_fn, stage_params, xs, mesh=mesh,
            num_microbatches=num_microbatches, axis_name=axis_name,
            local_batch=local_batch, x_specs=x_specs, param_specs=param_specs)
    if schedule != "gpipe":
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    mb = local_batch // num_microbatches
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    def worker(params, xs_local):
        # params leaves: [1, ...] (this stage's slice); xs leaves [local_b,...]
        params = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis_name)
        m = num_microbatches
        xs_mb = jax.tree.map(
            lambda a: a.reshape(m, mb, *a.shape[1:]), xs_local)
        send_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, out = carry
            mb_idx = t - s
            active = jnp.logical_and(mb_idx >= 0, mb_idx < m)
            feed = jax.tree.map(lambda a: a[jnp.clip(t, 0, m - 1)], xs_mb)
            x_in = jax.tree.map(
                lambda f, b: jnp.where(s == 0, f, b), feed, buf)
            y = fn(params, x_in)
            y = jax.tree.map(
                lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
            # Last stage deposits its finished microbatch.
            write = jnp.logical_and(active, s == n_stages - 1)
            idx = jnp.clip(mb_idx, 0, m - 1)
            out = jax.tree.map(
                lambda o, a: jnp.where(
                    write, jax.lax.dynamic_update_index_in_dim(o, a, idx, 0),
                    o),
                out, y)
            # Hop to the next stage (stage n-1 sends to nobody; ppermute
            # without a wrap edge delivers zeros to stage 0, which ignores it)
            buf_next = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis_name, send_perm), y)
            return (buf_next, out), None

        out0 = jax.tree.map(
            lambda a: jnp.zeros((m, mb, *a.shape[1:]), a.dtype), xs_local)
        buf0 = jax.tree.map(
            lambda a: jnp.zeros((mb, *a.shape[1:]), a.dtype), xs_local)
        (_, out), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(m + n_stages - 1))
        # Replicate the result off the last stage (psum of one-hot owner).
        def collect(o):
            owner = (s == n_stages - 1).astype(o.dtype)
            o = jax.lax.psum(o * owner, axis_name)
            return o.reshape(local_batch, *o.shape[2:])

        return jax.tree.map(collect, out)

    return jax.shard_map(
        worker, mesh=mesh,
        in_specs=(param_specs, x_specs),
        out_specs=x_specs,
        check_vma=False,
    )(stage_params, xs)


def _spec_axes(spec) -> set:
    """Mesh axes a PartitionSpec shards over."""
    axes: set = set()
    for entry in spec:
        if entry is None:
            continue
        axes.update((entry,) if isinstance(entry, str) else entry)
    return axes


def _pipeline_1f1b(stage_fn, stage_params, xs, *, mesh, num_microbatches,
                   axis_name, local_batch, x_specs, param_specs):
    """1F1B: GPipe-style streaming forward + a hand-scheduled interleaved
    backward under ``jax.custom_vjp``.

    Backward super-tick t at stage s (n stages, m microbatches):
      - forward-recompute lane: microbatch ``fi = t - s`` (the GPipe wave);
      - backward lane: microbatch ``bi = t - (2n - 2 - s)`` — the last stage
        backprops a microbatch in the same tick its recompute lands, earlier
        stages 2·(n-1-s) ticks later, exactly the 1F1B pattern.
    Both lanes run every tick (masked when out of range): activations hop
    s→s+1 and cotangents hop s+1→s in the same tick, so no device ever
    waits on a branch. A stage holds at most 2n-1 microbatch inputs
    (fi - bi = 2(n-1-s)), so the ring buffer — not m — bounds memory. Cost:
    3 forwards + 1 backward per microbatch per stage (the fwd lane refills
    the ring AND the vjp's primal re-runs the stage) — one extra forward
    over checkpointed GPipe, the price of the m-independent ring.

    Gradient sync, derived from the specs (the hand-written vjp must do what
    shard_map's transpose would have):
      - ``x_axes`` (stream sharded, params replicated — data/seq axes):
        parameter grads psum over them after the scan.
      - ``vjp_axes`` (params sharded, stream replicated — e.g. ``expert``):
        stage_fn psums its partial outputs over these in the forward, and
        ``jax.vjp`` *inside* the worker transposes that psum to a psum, so
        every cotangent below such a site is inflated by the axis size while
        carrying only the local branch's mixing. The exact fix (inductively:
        psum of local cotangents = axis_size × true cotangent at every
        level): pmean local vjp outputs over these axes — for param leaves
        *sharded* on such an axis, divide by the axis size instead (pmean
        would average different experts' grads)."""
    n = mesh.shape[axis_name]
    m = num_microbatches
    mb = local_batch // m
    ring_depth = 2 * n - 1
    send_perm = [(i, i + 1) for i in range(n - 1)]
    recv_perm = [(i + 1, i) for i in range(n - 1)]

    is_spec = lambda s: isinstance(s, P)
    x_axes: set = set()
    for spec in jax.tree.leaves(x_specs, is_leaf=is_spec):
        x_axes |= _spec_axes(spec)
    p_axes: set = set()
    for spec in jax.tree.leaves(param_specs, is_leaf=is_spec):
        p_axes |= _spec_axes(spec)
    # Axes whose collectives jax.vjp mis-transposes inside the worker (see
    # docstring): params sharded there, the stream not.
    vjp_axes = tuple(a for a in mesh.axis_names
                     if a in p_axes and a != axis_name and a not in x_axes)

    for leaf in jax.tree.leaves(xs):
        if not jnp.issubdtype(leaf.dtype, jnp.inexact):
            raise TypeError(
                "1f1b pipeline streams cotangents; every xs leaf must be "
                f"inexact (got {leaf.dtype}) — close over integer inputs "
                "in stage_fn instead")

    def fwd_worker(params, xs_local):
        params1 = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis_name)
        xs_mb = jax.tree.map(
            lambda a: a.reshape(m, mb, *a.shape[1:]), xs_local)

        def tick(carry, t):
            buf, out = carry
            fi = t - s
            active = jnp.logical_and(fi >= 0, fi < m)
            feed = jax.tree.map(lambda a: a[jnp.clip(fi, 0, m - 1)], xs_mb)
            x_in = jax.tree.map(
                lambda f, b: jnp.where(s == 0, f, b), feed, buf)
            y = stage_fn(params1, x_in)
            y = jax.tree.map(
                lambda a: jnp.where(active, a, jnp.zeros_like(a)), y)
            write = jnp.logical_and(active, s == n - 1)
            idx = jnp.clip(fi, 0, m - 1)
            out = jax.tree.map(
                lambda o, a: jnp.where(
                    write, jax.lax.dynamic_update_index_in_dim(o, a, idx, 0),
                    o),
                out, y)
            buf_next = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis_name, send_perm), y)
            return (buf_next, out), None

        out0 = jax.tree.map(
            lambda a: jnp.zeros((m, mb, *a.shape[1:]), a.dtype), xs_local)
        buf0 = jax.tree.map(
            lambda a: jnp.zeros((mb, *a.shape[1:]), a.dtype), xs_local)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(m + n - 1))

        def collect(o):
            owner = (s == n - 1).astype(o.dtype)
            o = jax.lax.psum(o * owner, axis_name)
            return o.reshape(local_batch, *o.shape[2:])

        return jax.tree.map(collect, out)

    def bwd_worker(params, xs_local, gys_local):
        params1 = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis_name)
        xs_mb = jax.tree.map(
            lambda a: a.reshape(m, mb, *a.shape[1:]), xs_local)
        gys_mb = jax.tree.map(
            lambda a: a.reshape(m, mb, *a.shape[1:]), gys_local)

        def tick(carry, t):
            ring, fbuf, gbuf, dparams, dxs = carry
            # -- forward-recompute lane: microbatch fi enters this stage
            fi = t - s
            f_active = jnp.logical_and(fi >= 0, fi < m)
            feed = jax.tree.map(lambda a: a[jnp.clip(fi, 0, m - 1)], xs_mb)
            x_in = jax.tree.map(
                lambda f, b: jnp.where(s == 0, f, b), feed, fbuf)
            fslot = jnp.clip(fi, 0, m - 1) % ring_depth
            ring = jax.tree.map(
                lambda r, x: jnp.where(
                    f_active,
                    jax.lax.dynamic_update_index_in_dim(r, x, fslot, 0), r),
                ring, x_in)
            y = stage_fn(params1, x_in)
            # -- backward lane: microbatch bi leaves this stage
            bi = t - (2 * n - 2 - s)
            b_active = jnp.logical_and(bi >= 0, bi < m)
            bslot = jnp.clip(bi, 0, m - 1) % ring_depth
            x_saved = jax.tree.map(lambda r: r[bslot], ring)
            g_in = jax.tree.map(
                lambda g, b: jnp.where(s == n - 1,
                                       g[jnp.clip(bi, 0, m - 1)], b),
                gys_mb, gbuf)
            _, vjp_fn = jax.vjp(stage_fn, params1, x_saved)
            dp, dx = vjp_fn(g_in)
            if vjp_axes:
                # Restore the exact (replicated) input cotangent before it
                # hops to the previous stage or deposits (docstring: sync).
                dx = jax.tree.map(
                    lambda d: jax.lax.pmean(d, vjp_axes), dx)
            dparams = jax.tree.map(
                lambda acc, d: acc + jnp.where(b_active, d,
                                               jnp.zeros_like(d)),
                dparams, dp)
            deposit = jnp.logical_and(b_active, s == 0)
            dxs = jax.tree.map(
                lambda o, d: jnp.where(
                    deposit,
                    jax.lax.dynamic_update_index_in_dim(
                        o, d, jnp.clip(bi, 0, m - 1), 0),
                    o),
                dxs, dx)
            # -- hops: activations forward, cotangents backward, every tick
            fbuf = jax.tree.map(
                lambda a: jax.lax.ppermute(
                    jnp.where(f_active, a, jnp.zeros_like(a)),
                    axis_name, send_perm), y)
            gbuf = jax.tree.map(
                lambda a: jax.lax.ppermute(
                    jnp.where(b_active, a, jnp.zeros_like(a)),
                    axis_name, recv_perm), dx)
            return (ring, fbuf, gbuf, dparams, dxs), None

        ring0 = jax.tree.map(
            lambda a: jnp.zeros((ring_depth, mb, *a.shape[1:]), a.dtype),
            xs_local)
        fbuf0 = jax.tree.map(
            lambda a: jnp.zeros((mb, *a.shape[1:]), a.dtype), xs_local)
        gbuf0 = jax.tree.map(jnp.zeros_like, fbuf0)
        dparams0 = jax.tree.map(jnp.zeros_like, params1)
        dxs0 = jax.tree.map(
            lambda a: jnp.zeros((m, mb, *a.shape[1:]), a.dtype), xs_local)
        (_, _, _, dparams, dxs), _ = jax.lax.scan(
            tick, (ring0, fbuf0, gbuf0, dparams0, dxs0),
            jnp.arange(m + 2 * n - 2))

        def collect(o):
            owner = (s == 0).astype(o.dtype)
            o = jax.lax.psum(o * owner, axis_name)
            return o.reshape(local_batch, *o.shape[2:])

        def sync_param_grad(d, spec):
            leaf_axes = _spec_axes(spec)
            pmean_axes, scale = [], 1.0
            for a in vjp_axes:
                if a in leaf_axes:
                    scale /= mesh.shape[a]   # sharded leaf: undo inflation
                else:
                    pmean_axes.append(a)     # replicated leaf: exact pmean
            if pmean_axes:
                d = jax.lax.pmean(d, tuple(pmean_axes))
            if scale != 1.0:
                d = d * jnp.asarray(scale, d.dtype)
            # Stream-sharded axes the leaf is replicated over (data/seq):
            # every shard contributes gradient; out_specs claims replication,
            # so the sum happens here (autodiff would have inserted it as
            # the transpose of the implicit broadcast).
            psum_axes = tuple(a for a in mesh.axis_names
                              if a in x_axes and a not in leaf_axes)
            return jax.lax.psum(d, psum_axes) if psum_axes else d

        dparams = jax.tree.map(sync_param_grad, dparams, param_specs)
        return (jax.tree.map(lambda d: d[None], dparams),
                jax.tree.map(collect, dxs))

    fwd_sm = jax.shard_map(fwd_worker, mesh=mesh,
                       in_specs=(param_specs, x_specs),
                       out_specs=x_specs, check_vma=False)
    bwd_sm = jax.shard_map(bwd_worker, mesh=mesh,
                       in_specs=(param_specs, x_specs, x_specs),
                       out_specs=(param_specs, x_specs), check_vma=False)

    @jax.custom_vjp
    def apply(params, xs):
        return fwd_sm(params, xs)

    def apply_fwd(params, xs):
        return fwd_sm(params, xs), (params, xs)

    def apply_bwd(res, gys):
        params, xs_in = res
        dparams, dxs = bwd_sm(params, xs_in, gys)
        return dparams, dxs

    apply.defvjp(apply_fwd, apply_bwd)
    return apply(stage_params, xs)


def sequential_apply(stage_fn: StageFn, stage_params: Any, xs: Any) -> Any:
    """Numerics oracle: same stages, no pipelining."""
    n = jax.tree.leaves(stage_params)[0].shape[0]
    for i in range(n):
        params_i = jax.tree.map(lambda p: p[i], stage_params)
        xs = stage_fn(params_i, xs)
    return xs
