"""Weights from ``--seed``, made on the device in ONE jitted call, in the
type they are served or trained in, laid out as the program's model expects
its parameters: the tree is the architecture's
(``benchmark/architectures/<name>/weights.py::param_tree``), the seed's key,
the blockwise draw and the one jitted call are every architecture's. Both
the program and the plain reference are given THESE arrays; neither is given
anything the other made.

Scales are the usual ones (1/sqrt(fan_in); the embedding at 1): what matters
to a benchmark is that activations stay O(1) through the depth, so that a
lower-precision run is told apart by its rounding and not drowned by scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import architecture


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number, beyond 32 bits too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def stacked_normal(key, lead: tuple, shape: tuple, scale: float, dtype):
    """[*lead, *shape] normal(0, scale), one ``shape`` block at a time so the
    float32 draw of a whole stacked leaf (5.6 GB for Mixtral's experts) is
    never alive at once."""
    n = math.prod(lead)
    keys = jax.random.split(key, n)
    blocks = jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32)
                   * scale).astype(dtype), keys)
    return blocks.reshape(*lead, *shape)


def param_shapes(c: dict, dtype) -> dict:
    """The tree's shapes and types, nothing made."""
    tree = architecture.part(c, "weights").param_tree
    return jax.eval_shape(
        lambda: tree(c, jax.random.PRNGKey(0), jnp.dtype(dtype)))


def make_params(c: dict, seed: int, dtype, shardings=None) -> dict:
    """One jitted call; with ``shardings`` every leaf is born in its shards."""
    tree = architecture.part(c, "weights").param_tree
    fn = jax.jit(lambda k: tree(c, k, jnp.dtype(dtype)),
                 out_shardings=shardings)
    return fn(seed_key(seed))
