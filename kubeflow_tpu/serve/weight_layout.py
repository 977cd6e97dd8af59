"""Where a serving engine's weights lie in device memory.

A parameter's LOGICAL shape is what every reader of the tree sees (the
einsums of ``serve/paged.py`` and ``models/layers.py``, LoRA, the
quantizer, ``decoder_param_specs``). Its PHYSICAL layout, which dimension
is minor and how the tiles run, is the compiler's default unless the array
is put on the device with another. Where the default is not the layout a
program's matrix unit reads the operand in, the compiled program copies
the whole weight into that layout first: once a dispatch in the decode
program (hoisted out of its step loop), once a layer in every chunk
program. The engine calls ``relay`` once, as the last step of its load
path, so that no program lays a weight out again.

The rule reads the leaf, not a model's name and not an option. A stacked
per-head projection ``wq`` / ``wk`` / ``wv`` of shape ``[L, D, heads,
head_dim]`` (an attention layer's output gate ``wgate`` and a linear
layer's three projections are such leaves too) is contracted over ``D`` a
head at a time (``"bsd,dhk->bshk"``):
the matrix unit wants each head's ``[D, head_dim]`` plane contiguous, which
is ``major_to_minor=(0, 2, 1, 3)``. That holds where a head's row fills
whole 128-lane tiles (``head_dim % 128 == 0``); at heads of 64 the
compiler folds two heads into a tile and the default layout is already
what it reads (forcing heads-major there ADDS copies). Every other leaf
stays as it is: ``wo`` and the MLP and expert stacks are read in their
default layout, latent projections (``wqa`` / ``wqb`` / ``wkva`` / ``wkvb``)
and a conv layer's ``win`` have preferences under ``Layout.AUTO`` that
remove no standing copy, int8 leaves are dequantized in the operand read.
Under a mesh GSPMD owns the operands' layouts, and off the Pallas path the
programs are not the ones this was sized for. The evidence for each
clause is a compile for a described chip: ``scripts/aot_weight_copies.py``
prints a cell's parameter-sized copies, ``tests/test_chip_compile.py``
holds the serving cells' programs free of them.

Two things a relaid leaf brings with it, both JAX's (0.9.0). It is a
COMMITTED array: a plain ``jax.jit`` reads a layout off a committed
argument only, and what a program returns is committed where any argument
is, so the engine commits what it allocates too and warms its own
first-token widths (``serve/engine.py``, at the call of ``relay``). And the
program that lays it out must not come from the persistent compile cache
(``_compiled_afresh``).
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.models.config import DecoderConfig

Params = Any

# The lanes of one tile of the TPU's vector memory: a head's row has to
# fill whole tiles for the heads-major plane to be what the matrix unit
# reads.
LANES = 128
# [layers, hidden, heads, head_dim] with each head's [hidden, head_dim]
# plane contiguous.
HEADS_MAJOR = (0, 2, 1, 3)
_PER_HEAD = ("wq", "wk", "wv", "wgate")


def _leaf_layout(path, leaf, cfg: DecoderConfig) -> Optional[Layout]:
    """The layout a leaf is to take, or None where it stays as it lies."""
    name = getattr(path[-1], "key", None)
    if name not in _PER_HEAD or len(leaf.shape) != 4:
        return None
    _, hidden, heads, head_dim = leaf.shape
    # a linear layer's three projections have its own heads, all alike; an
    # attention layer's output gate has the queries'
    linear = len(path) > 1 and getattr(path[-2], "key", None) == "linear"
    want_heads, want_dim = (cfg.linear_heads, cfg.linear_head_dim) \
        if linear else (cfg.n_heads if name in ("wq", "wgate")
                        else cfg.n_kv_heads, cfg.head_dim)
    if hidden != cfg.hidden or head_dim != want_dim or heads != want_heads:
        return None
    if head_dim % LANES or not jnp.issubdtype(leaf.dtype, jnp.floating):
        return None
    return Layout(HEADS_MAJOR)


def weight_formats(params: Params, cfg: DecoderConfig, *,
                   one_chip_pallas: bool) -> Params:
    """A tree like ``params``: for each leaf the ``Format`` (layout and the
    leaf's own sharding) it is to be held in, or None where it stays as it
    is. ``params`` may be arrays or ``jax.ShapeDtypeStruct``s with a
    sharding: only shapes, dtypes and shardings are read.
    ``one_chip_pallas``: the engine serves from one TPU chip and its
    attention runs the Pallas kernels; anywhere else (a mesh, a CPU, the
    gathered form) every leaf stays."""
    def rule(path, leaf):
        layout = _leaf_layout(path, leaf, cfg) if one_chip_pallas else None
        if layout is None:
            return None
        # A host array (a checkpoint's numpy leaf) goes where an engine
        # without a mesh computes: the default device.
        sharding = getattr(leaf, "sharding", None) or SingleDeviceSharding(
            jax.devices()[0])
        return Format(layout, sharding)

    return jax.tree_util.tree_map_with_path(rule, params)


@contextlib.contextmanager
def _compiled_afresh():
    """JAX's persistent compile cache off for the programs compiled inside.
    A program whose RESULT lies in a ``Layout`` of the caller's choosing
    (``jax.device_put`` to a ``Format`` is one: a jitted identity) must not
    come from it: an executable read back from the cache (JAX 0.9.0, the
    chip and the CPU backend alike) hands its result over marked with the
    DEFAULT layout while the bytes lie as compiled: every reader of the array
    then reads it scrambled (my chip run, PR 39: the second run of a cell
    read logit errors of 0.9 where the first read 0.014). Compiled afresh
    the identity is right, and the programs that take the array as a
    PARAMETER come back from the cache sound. The switch is process-wide and
    memoized by JAX, hence the two resets; a compile another thread makes
    meanwhile misses the cache once, nothing else."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def relay(params: Params, formats: Params) -> Params:
    """``params`` with the leaves that have a ``Format`` put on their device
    in it, by ONE program compiled afresh (0.25 s at Mistral's shapes on a
    v5e where a program a leaf took 0.6-1.1 s: my chip runs, PR 39); the
    others are the arrays that came in, and the caller's own tree is left
    whole (nothing is donated). Logical shapes and values do not change. A
    tree of ``jax.ShapeDtypeStruct``s (a compile for a described chip) comes
    back described in the same formats."""
    leaves, tree = jax.tree.flatten(params)
    wanted = tree.flatten_up_to(formats)
    moving = [i for i, f in enumerate(wanted) if f is not None]
    if not moving:
        return params
    if isinstance(leaves[moving[0]], jax.ShapeDtypeStruct):
        moved = [jax.ShapeDtypeStruct(leaves[i].shape, leaves[i].dtype,
                                      sharding=wanted[i]) for i in moving]
    else:
        with _compiled_afresh():
            moved = jax.jit(
                lambda *xs: xs,
                out_shardings=tuple(wanted[i] for i in moving))(
                    *(leaves[i] for i in moving))
    for i, x in zip(moving, moved):
        leaves[i] = x
    return jax.tree.unflatten(tree, leaves)


def relaid_bytes(params: Params, formats: Params) -> int:
    """Bytes of the leaves of ``params`` that ``formats`` gave a layout and
    that lie in it, read off the arrays themselves. (Not "the leaves that
    do not lie row-major": the chip's DEFAULT layout of a small trailing
    dimension, a router's ``[L, 4096, 8]``, is not row-major either.)"""
    def held(x, f) -> int:
        if f is None:
            return 0
        lies = getattr(getattr(x, "format", None), "layout", None)
        same = lies is not None and tuple(lies.major_to_minor) == tuple(
            f.layout.major_to_minor)
        return int(x.nbytes) if same else 0

    return sum(jax.tree.leaves(jax.tree.map(held, params, formats)))
