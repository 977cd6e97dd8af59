"""Sharded train step construction.

``setup_train`` builds everything a worker needs from (model cfg, optimizer
cfg, mesh): sharded param/optimizer-state initialization (params materialize
directly in their target sharding — no host round-trip), and a donated,
jit-compiled ``step(state, batch) -> (state, metrics)``.

XLA inserts the cross-device collectives (gradient psum over data axes,
all-gather/reduce-scatter for FSDP params) from the shardings alone —
the GSPMD path that replaces the reference's NCCL allreduce world.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import (
    decoder_loss, decoder_param_specs, init_decoder_params,
)
from kubeflow_tpu.parallel.sharding import (
    DEFAULT_RULES, LogicalRules, logical_to_mesh_axes, shard_params,
)
from kubeflow_tpu.train.optim import (
    OptimizerConfig, apply_optimizer, make_optimizer,
)


@dataclasses.dataclass
class TrainTask:
    """Everything a worker needs to run steps."""

    cfg: DecoderConfig
    mesh: Mesh
    optimizer: optax.GradientTransformation
    state: Any                      # {"params", "opt_state", "step"}
    state_shardings: Any
    batch_sharding: NamedSharding
    step_fn: Callable[[Any, jax.Array], tuple[Any, dict]]
    # K steps per device dispatch: scan over stacked [K, ...] batches,
    # returning the last step's metrics. The per-dispatch host cost
    # amortizes across K.
    multi_step_fn: Callable[[Any, jax.Array], tuple[Any, dict]] = None
    multi_batch_sharding: NamedSharding = None

    @property
    def params(self):
        return self.state["params"]


def _state_shardings(cfg: DecoderConfig, mesh: Mesh, rules: LogicalRules,
                     optimizer) -> Any:
    param_specs = decoder_param_specs(cfg)
    params_shape = jax.eval_shape(
        lambda: init_decoder_params(jax.random.PRNGKey(0), cfg))
    param_sh = shard_params(params_shape, param_specs, mesh, rules)
    # Optimizer state mirrors param shape (adam mu/nu); derive by eval_shape.
    opt_shape = jax.eval_shape(optimizer.init, params_shape)

    # Walk the opt state: any leaf whose shape matches a param leaf gets that
    # param's sharding; scalars/counters are replicated.
    flat_params, ptree = jax.tree.flatten(params_shape)
    flat_psh = jax.tree.leaves(param_sh)
    shape_to_sh = {}
    for p, sh in zip(flat_params, flat_psh):
        shape_to_sh.setdefault((p.shape, p.dtype), sh)

    def map_opt(leaf):
        key = (leaf.shape, leaf.dtype)
        if key in shape_to_sh and len(leaf.shape) > 0:
            return shape_to_sh[key]
        return NamedSharding(mesh, PartitionSpec())

    opt_sh = jax.tree.map(map_opt, opt_shape)
    return {
        "params": param_sh,
        "opt_state": opt_sh,
        "step": NamedSharding(mesh, PartitionSpec()),
    }


def make_state_init(cfg: DecoderConfig, optimizer, seed: int = 0):
    """The single source of truth for the train-state structure — used both
    for sharded init and as the abstract restore target (keeping the two in
    sync is what makes checkpoints forward-compatible with new fields)."""

    def init_fn(key=None):
        params = init_decoder_params(
            key if key is not None else jax.random.PRNGKey(seed), cfg)
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.int32(0),
        }

    return init_fn


def setup_train(
    cfg: DecoderConfig,
    opt_cfg: OptimizerConfig,
    mesh: Mesh,
    *,
    rules: LogicalRules = DEFAULT_RULES,
    seed: int = 0,
    attn_impl: str = "xla",
    init_state: bool = True,
) -> TrainTask:
    optimizer = make_optimizer(opt_cfg)
    if dict(mesh.shape).get("pipeline", 1) > 1:
        # Pipeline parallelism stages the layer stack: shard the stacked
        # layer dim over the pipeline axis (parallel/pipeline.py streams
        # microbatches through it).
        from kubeflow_tpu.parallel.sharding import with_rule

        rules = with_rule(rules, "layers", "pipeline")
    shardings = _state_shardings(cfg, mesh, rules, optimizer)
    batch_sharding = NamedSharding(
        mesh, logical_to_mesh_axes(("batch", None), rules))

    init_fn = make_state_init(cfg, optimizer, seed)
    sharded_init = jax.jit(init_fn, out_shardings=shardings)
    state = sharded_init(jax.random.PRNGKey(seed)) if init_state else None

    def step_impl(state, batch):
        def loss_fn(params):
            return decoder_loss(params, batch, cfg, attn_impl=attn_impl,
                                mesh=mesh, rules=rules)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])
        new_params, new_opt, grad_norm = apply_optimizer(
            optimizer, grads, state["opt_state"], state["params"])
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["grad_norm"] = grad_norm
        new_state = {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }
        return new_state, metrics

    step_fn = jax.jit(
        step_impl,
        in_shardings=(shardings, batch_sharding),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )

    def multi_step_impl(state, batches):   # batches [K, B, S+1]
        state, ms = jax.lax.scan(step_impl, state, batches)
        return state, jax.tree.map(lambda x: x[-1], ms)

    multi_batch_sharding = NamedSharding(
        mesh, PartitionSpec(None, *batch_sharding.spec))
    multi_step_fn = jax.jit(
        multi_step_impl,
        in_shardings=(shardings, multi_batch_sharding),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )

    return TrainTask(
        cfg=cfg, mesh=mesh, optimizer=optimizer, state=state,
        state_shardings=shardings, batch_sharding=batch_sharding,
        step_fn=step_fn, multi_step_fn=multi_step_fn,
        multi_batch_sharding=multi_batch_sharding,
    )
