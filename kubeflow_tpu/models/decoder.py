"""The decoder LLM: init, forward, loss — scan-over-layers, remat, logical
sharding specs.

Covers Llama-3 (RoPE+GQA+RMSNorm+SwiGLU), Gemma ((1+w) norms, embed scale,
GeGLU, tied embeddings, logit softcap) and Mixtral (MoE blocks) through
DecoderConfig flags. Layers are stacked on a leading axis and traversed with
`lax.scan` so compile time is depth-independent; the block is rematerialized
per the config policy (trades HBM for FLOPs — SURVEY.md task guidance).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from kubeflow_tpu.models.config import ALSO_HOLDS, DecoderConfig
from kubeflow_tpu.models import layers as L
from kubeflow_tpu.parallel.sharding import (
    LogicalRules, DEFAULT_RULES, _is_spec_leaf, with_logical_constraint,
)

Params = dict[str, Any]


# A layer's operator, by its kind: the block's key in the parameter tree and
# the cache planes it holds (every plane not named here is attention's). A
# window layer's operator has an attention layer's leaves under a key of its
# own, and K/V planes of its own (a pool keeps a bounded ring of them a
# sequence where a global layer keeps every page, serve/paged.py).
OPERATOR = {"attention": "attn", "window": "window", "conv": "conv",
            "linear": "linear", "ssm": "ssm", "gmu": "gmu", "cross": "cross",
            "parallel": "parallel", "ssd": "ssd"}
# a window layer's plane -> the name attention knows it by
WINDOW_PLANES = {"window_k": "k", "window_v": "v"}
# a linear layer's planes: a sequence's recurrent matrices and the tails of
# its three convolutions (one entry a SEQUENCE, serve/paged.py)
LINEAR_PLANES = ("kda_state", "kda_conv")
# an ssm layer's planes: a sequence's recurrent state [N, E] and the tail of
# its convolution (one entry a SEQUENCE, as a linear layer's)
SSM_PLANES = ("ssm_state", "ssm_conv")
# an ssd layer's planes: a sequence's SSD state [H, N, P] and the tail of its
# convolution (one entry a SEQUENCE). A parallel layer keeps them too, beside
# its K and V, the attention planes' rows (``config.ALSO_HOLDS``)
SSD_PLANES = ("ssd_state", "ssd_conv")
PLANE_KINDS = {"conv": "conv", **dict.fromkeys(WINDOW_PLANES, "window"),
               **dict.fromkeys(LINEAR_PLANES, "linear"),
               **dict.fromkeys(SSM_PLANES, "ssm"),
               **dict.fromkeys(SSD_PLANES, "ssd")}


def plane_kind(name: str) -> str:
    return PLANE_KINDS.get(name, "attention")


def holds(kind: str, plane: str) -> bool:
    """Whether a layer of ``kind`` keeps the cache plane ``plane``: its own
    kind's, and those it holds beside them (a parallel layer its K and
    V)."""
    of = plane_kind(plane)
    return of == kind or of in ALSO_HOLDS.get(kind, ())


def block_kind(bp: dict) -> str:
    """A block's kind, read off its parameters: the operator it holds."""
    return next(kind for kind, key in OPERATOR.items() if key in bp)


def _init_operator(key, cfg: DecoderConfig, kind: str):
    init = {"conv": L.init_conv, "linear": L.init_linear,
            "ssm": L.init_ssm, "gmu": L.init_gmu,
            "parallel": L.init_parallel, "ssd": L.init_ssd,
            "cross": lambda k, c: L.init_diff_attention(k, c, cross=True),
            }.get(kind, L.init_attention)
    return init(jax.random.split(key)[0], cfg)


def _init_started(key, cfg: DecoderConfig):
    """The expert layer the first block of a pair starts under a shortcut
    (``cfg.moe_shortcut``), the block's "moe" beside its dense "mlp"."""
    return L.init_moe(jax.random.fold_in(key, 2), cfg)


# what a block WITH a feed-forward part has beside its operator and first norm
FED_LEAVES = ("mlp", "ln2", "ln2_b")


def _init_ffn(key, cfg: DecoderConfig, starts: bool = False,
              fed: bool = True):
    """What every kind of block has beside its operator: the feed-forward
    (or expert) layer and the two norms. Under a shortcut the feed-forward
    is the dense MLP, and a block that ``starts`` an expert layer has that
    too. A block that is not ``fed`` (``cfg.ffn_free``) is its operator
    behind the first norm and nothing else."""
    ln1, ln1_s = L.init_norm(cfg, "ln1")
    if not fed:
        return ln1, ln1_s
    k_mlp = jax.random.split(key)[1]
    dense = not cfg.is_moe or cfg.moe_shortcut
    mlp_p, mlp_s = (L.init_mlp if dense else L.init_moe)(k_mlp, cfg)
    ln2, ln2_s = L.init_norm(cfg, "ln2")
    params, specs = {"mlp": mlp_p, **ln1, **ln2}, {"mlp": mlp_s, **ln1_s,
                                                   **ln2_s}
    if starts:
        params["moe"], specs["moe"] = _init_started(key, cfg)
    return params, specs


def _init_block(key, cfg: DecoderConfig, kind: str = "attention",
                starts: bool = False, fed: bool = True):
    op_p, op_s = _init_operator(key, cfg, kind)
    ffn_p, ffn_s = _init_ffn(key, cfg, starts, fed)
    return ({OPERATOR[kind]: op_p, **ffn_p}, {OPERATOR[kind]: op_s, **ffn_s})


def _period(kinds: tuple) -> int:
    """The shortest period of ``kinds`` (its last period may be cut)."""
    return next(p for p in range(1, len(kinds) + 1)
                if all(kinds[i] == kinds[i - p]
                       for i in range(p, len(kinds))))


def _marks(cfg: DecoderConfig) -> tuple:
    """What tells one layer of the stack from another when it is cut into
    groups: its kind and, where some blocks have no feed-forward part
    (``cfg.ffn_free``), whether it has one."""
    return tuple(zip(cfg.kinds, cfg.fed)) if cfg.ffn_free else cfg.kinds


def _periodic(cfg: DecoderConfig, first: int, n: int) -> list:
    """Layers [first, first + n) as whole periods of their shortest pattern,
    and what is left behind them (a cut period) as a group of its own: (the
    group's config, its first layer)."""
    kinds, fed = cfg.kinds[first:first + n], cfg.fed[first:first + n]
    p = _period(_marks(cfg)[first:first + n])
    whole = n // p * p

    def group(lo: int, hi: int, count: int):
        return dataclasses.replace(
            cfg, n_layers=count, layer_kinds=kinds[lo:hi],
            ffn_free=tuple(i for i, f in enumerate(fed[lo:hi]) if not f))

    out = [(group(0, p, whole), first)]
    if whole < n:
        out.append((group(whole, n, n - whole), first + whole))
    return out


def _stretches(kinds: tuple) -> list:
    """``kinds`` cut where a RUN starts and ends: a pattern of two or more
    kinds that stands at least twice in a row (``(ssm, window) x 8``), taken
    from the left, the shortest pattern first. [(start, length)]: the runs
    and what lies between them; one stretch, the whole, where there is no
    run or the stack is one (``(a, c, c, c) x 9`` and its cut period)."""
    n, s, loose, out = len(kinds), 0, 0, []
    while s < n:
        run = 0
        for p in range(2, (n - s) // 2 + 1):
            pattern = kinds[s:s + p]
            r = 1
            while kinds[s + r * p:s + (r + 1) * p] == pattern:
                r += 1
            if r >= 2 and len(set(pattern)) >= 2:
                run = r * p
                break
        if not run:
            s += 1
            continue
        # the cut period behind a run that reaches the end stays with it
        if n - s - run < p and kinds[s + run:] == pattern[:n - s - run]:
            run = n - s
        if s > loose:
            out.append((loose, s - loose))
        out.append((s, run))
        s = loose = s + run
    if n > loose:
        out.append((loose, n - loose))
    return out


def _grouped(name: str, cfg: DecoderConfig, first: int, n: int) -> list:
    """Layers [first, first + n) as groups that are each one scan, named
    ``name``, ``name_rest``, ``name_rest2`` ... in order. The layers of the
    stack's stateless tail (``cfg.stateless_tail``) never share a group with
    a layer in front of them: a program may run them at other positions."""
    head = max(first, min(first + n, cfg.n_layers - cfg.stateless_tail))
    found = []
    for lo, hi in ((first, head), (head, first + n)):
        for at, length in _stretches(_marks(cfg)[lo:hi]):
            found += _periodic(cfg, lo + at, length)
    return [(name + ("" if i == 0 else "_rest" + (str(i) if i > 1 else "")),
             gcfg, at) for i, (gcfg, at) in enumerate(found)]


def layer_groups(cfg: DecoderConfig) -> list[tuple[str, DecoderConfig, int]]:
    """The stack as groups that are each ONE scan, in order: (the group's
    key in the parameter tree, the config its blocks run under, its first
    layer's index). A model whose layers are all alike is the one group
    "layers" under its own config, as it always was; ``leading_dense_layers``
    puts a group "dense_layers" of plain-MLP blocks before the expert layers.

    Where the layers' kinds differ (``cfg.layer_kinds``) a group is whole
    PERIODS of its pattern: its config's ``layer_kinds`` is one period, the
    unit its scan walks (``period_units`` / ``unit_blocks``), and
    ``n_layers`` the layers it holds. A stack that is several patterns in a
    row (``(ssm, window) x 8, (ssm, attention), (gmu, cross) x 7``) is a
    group a pattern (``_stretches``), and its stateless tail always groups
    of its own (``_grouped``). In the tree a group's norms and
    feed-forward leaves are stacked over its layers in order, an operator's
    over the layers of its kind (``OPERATOR``)."""
    k = cfg.leading_dense_layers
    if not k:
        if not cfg.layer_kinds:
            return [("layers", cfg, 0)]
        return _grouped("layers", cfg, 0, cfg.n_layers)
    if not cfg.is_moe or not 0 < k < cfg.n_layers:
        raise ValueError(
            f"leading_dense_layers={k} needs an expert model of more than "
            f"{k} layers (n_layers={cfg.n_layers})")
    dense = dataclasses.replace(cfg, num_experts=0, leading_dense_layers=0)
    experts = dataclasses.replace(cfg, leading_dense_layers=0)
    return _grouped("dense_layers", dense, 0, k) \
        + _grouped("layers", experts, k, cfg.n_layers - k)


def period_units(stack, gcfg: DecoderConfig):
    """A group's stacked leaves as its scan takes them: one period of the
    pattern an iteration, so a leaf [n, ...] becomes [periods, n / periods,
    ...] (merging and splitting a leading axis moves nothing). A group of
    alike layers is scanned as it is."""
    p = len(gcfg.period)
    if p == 1:
        return stack
    m = gcfg.n_layers // p
    return jax.tree.map(
        lambda a: a.reshape(m, a.shape[0] // m, *a.shape[1:]), stack)


def split_dense_stack(stack, gcfg: DecoderConfig):
    """A stacked group as (what its scan slices a period at a time, the
    dense MLP's leaves taken WHOLE). In a group of more than one kind of
    layer a scan unit holds ``p`` layers' feed-forward leaves, ``[p, D, M]``,
    and a layer's is a slice of that slice: the chip's compiler copies the
    unit's ``p`` matrices out of the stack before every layer uses one (0.32
    ms a matrix a period at 2560 x 10240: 12 ms of a decode step of 32
    layers, my chip run, PR 47). Left out of the scan and indexed by the
    LAYER inside the body (``unit_blocks(whole=, u=)``), each use is one
    slice of the stack that fuses into its product, as a scan over alike
    layers gives it. (An expert layer's stack has its own way:
    ``layers.split_expert_stack``; those groups stay as they were.) Under a
    shortcut a scan unit is a PAIR of alike blocks, and every leaf of a
    block (its attention, its dense "mlp", its norms) is taken whole: the
    scan keeps the pair's one expert layer alone (scanned as a pair the
    decode step copied 1.9 GB of weights out of their stacks, 13 of its 27
    ms: my chip run, PR 57)."""
    if gcfg.moe_shortcut:
        return ({k: v for k, v in stack.items() if k == "moe"},
                {k: v for k, v in stack.items() if k != "moe"})
    if len(gcfg.period) == 1 or gcfg.is_moe:
        return stack, None
    return {k: v for k, v in stack.items() if k != "mlp"}, \
        {"mlp": stack["mlp"]}


def unit_blocks(unit, gcfg: DecoderConfig, whole=None, u=None) -> list:
    """The layers of one scan unit, in order: (kind, the layer's place among
    the unit's layers of its kind, its block's parameters). ``unit``: one
    iteration's slice of ``period_units``; ``whole`` / ``u``
    (``split_dense_stack``): the leaves not scanned, by their key in a
    block, and the iteration's index. Under a shortcut the unit is a pair of
    blocks and its one expert layer ("moe") is the FIRST block's, which
    starts it."""
    period = gcfg.period
    if len(period) == 1:
        return [(period[0], 0, unit)]
    out, seen = [], {}
    # A leaf of the feed-forward part (``FED_LEAVES``) is stacked over the
    # blocks that HAVE one: block ``j``'s lies at ``at[j]`` of the unit's,
    # and a block without one (``gcfg.ffn_free``) takes none.
    fed = gcfg.fed[:len(period)]
    at = [sum(fed[:j]) for j in range(len(period))]
    for j, kind in enumerate(period):
        i = seen[kind] = seen.get(kind, -1) + 1
        op = OPERATOR[kind]

        def own(n):     # (whether block j has leaf n, where in a unit's)
            return (fed[j], at[j]) if n in FED_LEAVES else (True, j)

        out.append((kind, i, {
            **({"moe": jax.tree.map(lambda a: a[0], unit["moe"])}
               if j == 0 and "moe" in unit else {}),
            **{n: jax.tree.map(lambda a, k=own(n)[1]: a[k], unit[n])
               for n in ("mlp", "ln1", "ln2", "ln1_b", "ln2_b")
               if n in unit and own(n)[0]},
            **{n: jax.tree.map(
                lambda a, k=own(n)[1]: a[u * sum(fed) + k], leaves)
               for n, leaves in (whole or {}).items() if own(n)[0]},
            **({op: jax.tree.map(lambda a, i=i: a[i], unit[op])}
               if op in unit else {})}))
    return out


def _init_group(keys, gcfg: DecoderConfig):
    """One group's stacked parameters (``layer_groups``) from a key a
    layer."""
    kinds = gcfg.kinds
    if len(set(kinds)) == 1:
        stack = jax.vmap(lambda k: _init_block(k, gcfg, kinds[0])[0])(keys)
        if gcfg.moe_shortcut:   # an expert layer a pair, by its first block
            stack["moe"] = jax.vmap(
                lambda k: _init_started(k, gcfg)[0])(keys[::2])
        return stack
    stack = jax.vmap(lambda k: _init_ffn(k, gcfg)[0])(keys)
    if gcfg.ffn_free:   # the feed-forward parts over the blocks that have one
        fed = jnp.asarray([i for i, f in enumerate(gcfg.fed) if f])
        stack = {n: jax.tree.map(lambda a: a[fed], leaf)
                 if n in FED_LEAVES else leaf for n, leaf in stack.items()}
    for kind in sorted(set(kinds)):
        own = jnp.asarray([i for i, x in enumerate(kinds) if x == kind])
        stack[OPERATOR[kind]] = jax.vmap(
            lambda k, kind=kind: _init_operator(k, gcfg, kind)[0])(keys[own])
    return stack


def init_decoder_params(key: jax.Array, cfg: DecoderConfig) -> Params:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    tok, _ = L.init_embedding(k_embed, cfg)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacks = {}
    for name, gcfg, first in layer_groups(cfg):
        keys = layer_keys[first:first + gcfg.n_layers]
        if cfg.scan_layers:
            # Stack per-layer params on a leading axis via vmapped init.
            stacks[name] = _init_group(keys, gcfg)
        else:
            stacks[name] = [
                _init_block(k, gcfg, kind,
                            gcfg.moe_shortcut and i % 2 == 0, fed)[0]
                for i, (k, kind, fed) in enumerate(
                    zip(keys, gcfg.kinds, gcfg.fed))]
        if cfg.diff_attention:
            _set_lambda_init(stacks[name], gcfg, first)

    final_norm, _ = L.init_norm(cfg, "final_norm")
    params: Params = {"embed": tok, **stacks, **final_norm}
    if not cfg.tie_embeddings:
        params["lm_head"] = L._init(k_head, (cfg.hidden, cfg.vocab_size),
                                    cfg.weight_dtype)
    return params


def _set_lambda_init(group, gcfg: DecoderConfig, first: int) -> None:
    """Every differential attention operator of ``group`` (a stacked tree or
    a list of blocks, whose first layer is the stack's ``first``) given the
    ``lambda_init`` of its layer's depth."""
    for kind in set(gcfg.kinds) & {"attention", "window", "cross"}:
        depths = [first + i for i, k in enumerate(gcfg.kinds) if k == kind]
        if isinstance(group, list):
            for d in depths:
                group[d - first][OPERATOR[kind]]["lambda_init"] = \
                    L.diff_lambda_init(d)
        else:
            group[OPERATOR[kind]]["lambda_init"] = L.diff_lambda_init(
                jnp.asarray(depths))


def _block_specs(cfg: DecoderConfig, kind: str = "attention"):
    """Logical-axis spec tree for one decoder block (no params materialize:
    llama3-70b's block is ~GBs — trace under eval_shape, capture the static
    spec tree on the side)."""
    captured = {}

    def _shape_only():
        params, specs = _init_block(jax.random.PRNGKey(0), cfg, kind,
                                    cfg.moe_shortcut)
        captured["specs"] = specs
        return params

    jax.eval_shape(_shape_only)
    return captured["specs"]


def decoder_param_specs(cfg: DecoderConfig) -> Params:
    """Logical-axis spec tree mirroring init_decoder_params' structure.

    The stacked layer axis prepends the "layers" logical axis to every
    per-layer leaf when scanning."""
    def stack_spec(s):
        return ("layers",) + s

    stacks = {}
    for name, gcfg, _ in layer_groups(cfg):
        by_kind = {kind: _block_specs(gcfg, kind) for kind in set(gcfg.kinds)}
        if cfg.scan_layers:
            # Every kind's block has the same norms and feed-forward; each
            # brings its own operator.
            merged = {k: v for specs in by_kind.values()
                      for k, v in specs.items()}
            stacks[name] = jax.tree.map(stack_spec, merged,
                                        is_leaf=_is_spec_leaf)
        else:   # (under a shortcut a pair's first block alone has "moe")
            stacks[name] = [
                {k: v for k, v in by_kind[kind].items()
                 if (k != "moe" or i % 2 == 0)
                 and (fed or k not in FED_LEAVES)}
                for i, (kind, fed) in enumerate(zip(gcfg.kinds, gcfg.fed))]

    specs: Params = {
        "embed": ("vocab", "embed_table"),
        **stacks,
        **L.init_norm(cfg, "final_norm")[1],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    return specs


def _block_forward(block_params, x, positions, cfg: DecoderConfig,
                   kv_cache=None, attn_impl="xla", mesh=None,
                   rules=DEFAULT_RULES,
                   expert_axis=None, seq_axis=None, tp_axis=None,
                   valid_len=None, lora=None, expert_stack=None,
                   moe_capacity_per_row=False, cache_len=None):
    # A stack with a stateless tail carries, beside ``x``, what the tail's
    # layers read of the layers in front: ``shared`` = {"m": the last ssm
    # layer's scan output, "k" / "v": what the last attention layer attended
    # over} (``decoder_forward``).
    x, shared = x if isinstance(x, tuple) else (x, None)
    # Under a shortcut (``cfg.moe_shortcut``) what rides beside ``x`` is the
    # result of the expert layer the block in front STARTED: it rides from
    # the first block of a pair to the second alone, and joins the stream
    # there, behind that block's attention and dense MLP.
    joining = None
    if cfg.moe_shortcut:
        joining, shared = shared, None
    h = L.rmsnorm(x, block_params["ln1"], cfg, mesh=mesh,
                  bias=block_params.get("ln1_b"))
    if set(block_params) & {"ssm", "gmu", "cross", "parallel", "ssd"} and (
            tp_axis is not None or lora is not None):
        raise NotImplementedError(
            "an ssm, gmu, cross, parallel or ssd layer under in-stage "
            "tensor parallelism or with LoRA adapters")
    if "ssd" in block_params:
        # The SSD mixer alone: its cache is its state before ``x`` (the
        # recurrent state and the convolution's tail); it hands back the
        # state after the last valid position.
        attn_out, state = L.ssd_block(
            block_params["ssd"], h, cfg,
            None if kv_cache is None else tuple(
                kv_cache[n] for n in SSD_PLANES), valid_len)
        attn_out = checkpoint_name(attn_out, "attn_out")
        new_cache = None if kv_cache is None else dict(zip(SSD_PLANES, state))
    elif "parallel" in block_params:
        # Two operators on the one normed input: attention over the K and V
        # planes, the SSD mixer from the state before ``x``; both caches
        # come back, the state as it stands after the last valid position.
        attn_out, kv, state = L.parallel_block(
            block_params["parallel"], h, positions, cfg,
            None if kv_cache is None else {
                n: kv_cache[n] for n in ("k", "v", "len")},
            None if kv_cache is None else tuple(
                kv_cache[n] for n in SSD_PLANES),
            valid_len, attn_impl, mesh)
        new_cache = None if kv_cache is None else {
            "k": kv["k"], "v": kv["v"], **dict(zip(SSD_PLANES, state))}
    elif "ssm" in block_params:
        # An ssm layer's cache is its state before ``x`` (the recurrent
        # state and the convolution's tail); it hands back the state after
        # the last valid position, and its scan output to the carry.
        attn_out, state, y = L.ssm_block(
            block_params["ssm"], h, cfg,
            None if kv_cache is None else tuple(
                kv_cache[n] for n in SSM_PLANES), valid_len)
        new_cache = None if kv_cache is None else dict(zip(SSM_PLANES, state))
        if shared is not None:
            shared = {**shared, "m": y}
    elif "gmu" in block_params:
        attn_out, new_cache = L.gmu_block(block_params["gmu"], h,
                                          shared["m"], cfg), None
    elif "cross" in block_params:
        attn_out, new_cache = L.attention_block(
            block_params["cross"], h, positions, cfg,
            kv_cache=None if cache_len is None else {"len": cache_len},
            cross_kv=(shared["k"], shared["v"]))
    elif "conv" in block_params:
        # A conv layer's cache is the tail of gated rows before ``x``; what
        # it hands back is that tail followed by its own rows, of which the
        # caller keeps the windows it needs (serve/paged.py).
        if tp_axis is not None or lora is not None:
            raise NotImplementedError(
                "a conv layer under in-stage tensor parallelism or with "
                "LoRA adapters")
        attn_out, zs = L.conv_block(
            block_params["conv"], h, cfg,
            None if kv_cache is None else kv_cache["conv"])
        new_cache = None if kv_cache is None else {"conv": zs}
    elif "linear" in block_params:
        # A linear layer's cache is its state before ``x`` (the matrices and
        # the convolutions' tails); it hands back the state after the last
        # valid position.
        if tp_axis is not None or lora is not None:
            raise NotImplementedError(
                "a linear-attention layer under in-stage tensor parallelism "
                "or with LoRA adapters")
        attn_out, state = L.kda_block(
            block_params["linear"], h, cfg,
            None if kv_cache is None else tuple(
                kv_cache[n] for n in LINEAR_PLANES),
            valid_len)
        new_cache = None if kv_cache is None else dict(
            zip(LINEAR_PLANES, state))
    elif "window" in block_params:
        # Attention over the last ``attn_window`` keys; its cache planes are
        # its own kind's, which attention takes under its names.
        if kv_cache is not None:
            kv_cache = {WINDOW_PLANES.get(n, n): a
                        for n, a in kv_cache.items()}
        attn_out, new_cache = L.attention_block(
            block_params["window"], h, positions, cfg,
            kv_cache=kv_cache, attn_impl=attn_impl, mesh=mesh,
            tp_axis=tp_axis, lora=lora, window=cfg.attn_window)
        if new_cache is not None:
            new_cache = {n: new_cache[a] for n, a in WINDOW_PLANES.items()}
    else:
        attn_out, new_cache = L.attention_block(
            block_params["attn"], h, positions, cfg,
            kv_cache=kv_cache, attn_impl=attn_impl, mesh=mesh,
            tp_axis=tp_axis, lora=lora)
        if shared is not None:      # what the cross layers behind it read
            k, v = (new_cache["k"], new_cache["v"]) \
                if new_cache is not None \
                else L.diff_kv(block_params["attn"], h, cfg)
            shared = {**shared, "k": k, "v": v}
    if "mlp" not in block_params:
        # A block of ONE sublayer (``cfg.ffn_free``): no second norm, no
        # feed-forward part.
        x = x + attn_out
        if mesh is not None:
            x = with_logical_constraint(
                x, ("batch", "act_seq", "act_embed"), mesh, rules)
        return (x if shared is None else (x, shared)), new_cache, \
            jnp.float32(0)
    # Residual add + second norm as ONE op: fused kernels run it in a
    # single pass over the stream (layers.add_rmsnorm).
    x, h = L.add_rmsnorm(x, attn_out, block_params["ln2"], cfg, mesh=mesh,
                         bias=block_params.get("ln2_b"))
    def experts(at: str):
        return L.moe_block(block_params[at], h, cfg,
                           expert_axis=expert_axis, seq_axis=seq_axis,
                           valid_len=valid_len, tp_axis=tp_axis,
                           expert_stack=expert_stack,
                           capacity_per_row=moe_capacity_per_row)

    if cfg.is_moe and not cfg.moe_shortcut:
        mlp_out, aux = experts("mlp")
    else:
        mlp_out, aux = (L.mlp_block(block_params["mlp"], h, cfg,
                                    tp_axis=tp_axis, mesh=mesh),
                        jnp.float32(0))
    if "moe" in block_params:   # started on ``h``, joined a block later
        shared, aux = experts("moe")
    x = x + mlp_out
    if joining is not None:
        x = x + joining
    if mesh is not None:
        x = with_logical_constraint(x, ("batch", "act_seq", "act_embed"), mesh, rules)
    return (x if shared is None else (x, shared)), new_cache, aux


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn, policy=None)
    if policy == "nothing_saveable":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    if policy == "block_outs":
        # Save post-rope Q/K/V + attention/MLP block outputs (named in
        # models/layers.py) — ~1/4 of dots_no_batch's footprint; backward
        # recomputes only norms, the S×S attention einsums, and the MLP
        # interior.
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "q_rope", "k_rope", "v_proj", "attn_out", "mlp_out"))
    if policy == "dots_no_batch":
        # The classic transformer policy: save every weight matmul (QKV/out
        # projections, MLP) but recompute the attention einsums — their dots
        # carry batch dims, so the O(S²) score/prob tensors are never stashed.
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "dots_flash":
        # dots_no_batch + save the flash kernel's (o, lse): the custom-VJP
        # residuals that dots_no_batch would otherwise rebuild by replaying
        # the forward kernel in the backward. Costs [B,H,S,D] bf16 + lse
        # per layer of HBM; wins when that fits (the headline config's
        # round-4 default — see ops/flash_attention.py note).
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse")))
    raise ValueError(f"unknown remat policy {policy!r}")


def _run_layers(layers, x, positions, cfg: DecoderConfig, planes: tuple,
                plane_names: tuple, cache_len, *, attn_impl, mesh, rules,
                valid_len, lora, moe_capacity_per_row=False):
    """One group of layers (``layer_groups``) over ``x``: scanned a period
    of its pattern at a time when stacked, looped when a list. ``planes``:
    this group's slices of the cache's stacked planes, in ``plane_names``'
    order (empty without a cache), each over the layers of its plane's kind
    (``plane_kind``). Returns (x, the planes as written, the group's summed
    aux)."""
    def block(bp, x, cache, lr, expert_stack=None):
        return _block_forward(
            bp, x, positions, cfg, kv_cache=cache, attn_impl=attn_impl,
            mesh=mesh, rules=rules, valid_len=valid_len,
            lora=lr, expert_stack=expert_stack,
            moe_capacity_per_row=moe_capacity_per_row, cache_len=cache_len)

    def cache_of(kind, layer_planes):
        own = {n: pl for n, pl in zip(plane_names, layer_planes)
               if holds(kind, n)}
        return {**own, "len": cache_len} if own else None

    def written_by(new_cache, kind, out):
        for n in plane_names:
            if holds(kind, n):
                out[n].append(new_cache[n])

    if cfg.scan_layers:
        # Per-layer adapter slices ride the scan xs alongside the layer
        # params (leading L axis); aidx/scale are loop invariants the
        # body closes over (layers.layer_view).
        # The expert leaves of a sorted expert group are not scanned: the
        # body takes them whole with the layer's index (no slice of the
        # stack is copied out for the grouped matmul).
        layers, experts = L.split_expert_stack(layers, cfg)
        layers, dense = split_dense_stack(layers, cfg)
        p = len(cfg.period)

        def scan_body(carry, scan_in):
            unit, unit_planes, lora_sl, u = scan_in
            out = {n: [] for n in plane_names}
            aux_sum = jnp.float32(0)
            for j, (kind, i, bp) in enumerate(
                    unit_blocks(unit, cfg, dense, u)):
                carry, new_cache, aux = block(
                    bp, carry,
                    cache_of(kind, unit_planes if p == 1
                             else tuple(pl[i] for pl in unit_planes)),
                    L.layer_view(lora, lora_sl),
                    None if experts is None
                    else (experts, cfg.expert_layer(u * p + j)))
                written_by(new_cache, kind, out)
                aux_sum = aux_sum + aux
            return carry, (tuple(out[n][0] if p == 1 else jnp.stack(out[n])
                                 for n in plane_names), aux_sum)

        # scan consumes the stacked [L, ...] cache leaves alongside params
        x, (planes, auxs) = jax.lax.scan(
            _remat(scan_body, cfg.remat_policy), x,
            (period_units(layers, cfg), period_units(planes, cfg),
             L.slice_layers(lora),
             jnp.arange(cfg.n_layers // p, dtype=jnp.int32)))
        if p > 1:       # [periods, layers of the kind a period, ...] again
            planes = tuple(pl.reshape(-1, *pl.shape[2:]) for pl in planes)
        return x, planes, jnp.sum(auxs)

    block_fn = _remat(block, cfg.remat_policy)
    auxs, out, seen = [], {n: [] for n in plane_names}, {}
    for i, (block_params, kind) in enumerate(zip(layers, cfg.kinds)):
        at = seen[kind] = seen.get(kind, -1) + 1
        x, new_cache, aux = block_fn(
            block_params, x, cache_of(kind, tuple(pl[at] for pl in planes)),
            L.index_layer(lora, i))
        auxs.append(aux)
        written_by(new_cache, kind, out)
    return x, tuple(jnp.stack(out[n]) for n in plane_names), \
        jnp.sum(jnp.stack(auxs))


def decoder_forward(
    params: Params,
    tokens: jax.Array,                 # [B, S] int32
    cfg: DecoderConfig,
    *,
    positions: Optional[jax.Array] = None,
    kv_caches: Optional[dict] = None,  # {"k","v": [La,B,Smax,K,Dh], "conv": [Lc,B,taps-1,D], "len": scalar | [B]}
    attn_impl: str = "xla",
    mesh=None,
    rules: LogicalRules = DEFAULT_RULES,
    skip_head: bool = False,
    valid_len: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    lora: Optional[dict] = None,
    moe_capacity_per_row: bool = False,
):
    """Returns (logits [B,S,V] float32, new_kv_caches|None, aux_loss).
    With ``skip_head``, returns the final-norm hidden states [B,S,D] instead
    of logits (the chunked-CE loss applies the head blockwise).
    ``valid_len`` (traced scalar or [B]): marks trailing positions as
    padding for the MoE dispatch path (a serving chunk's tail) — see
    layers.moe_block. ``inputs_embeds`` [B,S,D] replaces the embedding
    lookup (pre-scale) — the differentiable-input path attribution
    explainers need (serve/explain.py); ``tokens`` still supplies shapes
    and positions. ``lora`` (multi-tenant serving, serve/lora.py):
    ``{"targets": {t: (a [L,S,din,r], b [L,S,r,dout])}, "aidx": [B],
    "scale": [S]}`` — each row's adapter delta applies inside every
    attention block (rows with aidx = -1 add exact zero).
    ``kv_caches["len"]`` may be one start a row ([B]: the serving chunk of
    several prompts, each at its own position; layers.attention_block). A
    plane of the cache is stacked over the layers of ITS kind
    (``plane_kind``): K and V over the attention layers, "conv" (the gated
    rows just before ``tokens``) over the conv layers, which hand back that
    tail followed by their own rows ([Lc,B,taps-1+S,D]).
    ``moe_capacity_per_row``: the dispatch MoE path's capacity is taken
    within each row (layers.moe_block), which that chunk sets."""
    custom_positions = positions is not None
    if positions is None:
        # Decode with a cache: absolute positions continue from the cache
        # length (RoPE angles and the causal mask must agree on the offset).
        offset = kv_caches["len"] if kv_caches is not None else 0
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
            + jnp.reshape(offset, (-1, 1)), tokens.shape)

    dt = cfg.activation_dtype
    table = params["embed"]
    if mesh is not None:
        # The table stores fsdp-sharded on the hidden dim (ZeRO-3); gather
        # that dim explicitly before the token gather (sharding.py rationale
        # at the embed_table rule) — vocab stays model-sharded, the gather
        # of a vocab-sharded operand GSPMD handles natively.
        table = with_logical_constraint(table, ("vocab", None), mesh, rules)
    if inputs_embeds is not None:
        x = inputs_embeds.astype(dt)
    else:
        x = table.astype(dt)[tokens]
    if mesh is not None:
        x = with_logical_constraint(x, ("batch", "act_seq", "act_embed"), mesh, rules)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden ** 0.5, dt)
    x = L.scaled(x, cfg.embed_multiplier)

    aux_total = jnp.float32(0)
    new_caches = None
    # The cache's planes (k and v per head): every stacked [L, ...] leaf
    # beside the length.
    plane_names = tuple(n for n in (kv_caches or {}) if n != "len")
    groups = layer_groups(cfg)

    pp = dict(mesh.shape).get("pipeline", 1) if mesh is not None else 1
    if pp > 1 and kv_caches is None:
        if custom_positions:
            raise NotImplementedError(
                "pipeline parallelism computes contiguous positions inside "
                "the stage (1F1B streams inexact leaves only); custom "
                "positions are not supported under pp>1")
        if len(groups) > 1 or cfg.moe_shortcut:
            raise NotImplementedError(
                "pipeline parallelism stages one stack of alike layers; "
                "leading dense layers and an expert layer on a shortcut "
                "are not supported under pp>1")
        # Pipeline parallelism: the layer stack is staged over the
        # ``pipeline`` mesh axis and microbatches stream through via
        # ppermute (parallel/pipeline.py). Decode (kv_caches) stays on the
        # non-pp path — serving shards differently.
        x, aux_total = _pipeline_layers(params["layers"], x, positions, cfg,
                                        mesh, attn_impl)
        groups = []

    if cfg.stateless_tail:
        # what the tail's layers read of the layers in front rides beside x
        kv = (tokens.shape[0],
              kv_caches["k"].shape[2] if kv_caches is not None
              else tokens.shape[1], cfg.n_kv_heads // 2, 2 * cfg.head_dim)
        x = (x, {"m": jnp.zeros((*tokens.shape, cfg.ssm_inner), jnp.float32),
                 "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)})
    new_planes: dict[str, list] = {n: [] for n in plane_names}
    for name, gcfg, first in groups:
        last = first + gcfg.n_layers
        whole = len(groups) == 1        # one group: nothing is sliced
        # The group's planes: those of the kinds it has, each sliced over
        # the layers of its kind that lie in the group.
        def held(kinds, n):     # layers of ``kinds`` that keep plane ``n``
            return sum(holds(kind, n) for kind in kinds)

        names = tuple(n for n in plane_names if held(gcfg.kinds, n))
        at = {n: held(cfg.kinds[:first], n) for n in names}
        planes = tuple(
            kv_caches[n] if whole else kv_caches[n][
                at[n]:at[n] + held(gcfg.kinds, n)]
            for n in names)
        lora_g = lora if whole or lora is None else {
            **lora, "targets": {t: (a[first:last], b[first:last])
                                for t, (a, b) in lora["targets"].items()}}
        x, planes, aux = _run_layers(
            params[name], x, positions, gcfg, planes, names,
            kv_caches["len"] if kv_caches is not None else None,
            attn_impl=attn_impl, mesh=mesh, rules=rules,
            valid_len=valid_len, lora=lora_g,
            moe_capacity_per_row=moe_capacity_per_row)
        aux_total = aux_total + aux
        for n, plane in zip(names, planes):
            new_planes[n].append(plane)
    if kv_caches is not None:
        new_caches = {n: ps[0] if len(ps) == 1 else jnp.concatenate(ps)
                      for n, ps in new_planes.items()}
        new_caches["len"] = kv_caches["len"] + tokens.shape[1]

    if cfg.stateless_tail:
        x = x[0]
    x = L.rmsnorm(x, params["final_norm"], cfg, mesh=mesh,
                  bias=params.get("final_norm_b"))
    if skip_head:
        return x, new_caches, aux_total
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", L.scaled(x, cfg.head_multiplier),
                        head.astype(dt), preferred_element_type=jnp.float32)
    if cfg.logits_softcap is not None:
        logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
    return logits, new_caches, aux_total


def _pipeline_layers(layer_params, x, positions, cfg: DecoderConfig, mesh,
                     attn_impl: str = "xla"):
    """Apply the [L, ...] layer stack as pipeline stages.

    Compositions (the SURVEY.md §2.6 beyond-reference axis):
    - **PP×EP (MoE)**: expert weights keep their ``expert`` sharding inside
      the stage shard_map; each device runs its local experts and psums the
      combined output over the axis (layers.moe_block ``expert_axis``). The
      microbatch-local aux losses stream with the batch and average — the
      standard pipelined-MoE semantics (full-batch fractions aren't visible
      to a microbatch).
    - **PP×SP (ring/Ulysses)**: the streamed activation is additionally
      sharded on the sequence dim over ``seq``; attention runs the
      collective form over that axis inside the stage.
    - **PP×TP**: head/mlp dims keep their Megatron sharding over ``model``
      inside the stage; layers.py runs the output-projection psums (the
      manual form of the GSPMD split the non-pp path derives from rules).
    Positions are computed inside the stage from the seq-shard offset
    (contiguous training positions only — the decode/kv path never takes
    this branch), which keeps every streamed leaf inexact so the 1F1B
    schedule (``cfg.pipeline_schedule``) is legal."""
    from kubeflow_tpu.parallel.pipeline import pipeline_apply
    from jax.sharding import PartitionSpec as P

    axis_sizes = dict(mesh.shape)
    n_stages = axis_sizes["pipeline"]
    sp = (attn_impl in ("ring", "ring_flash", "ulysses")
          and axis_sizes.get("seq", 1) > 1)
    ep = cfg.is_moe and axis_sizes.get("expert", 1) > 1
    tp = axis_sizes.get("model", 1)
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp
                   or cfg.mlp_dim % tp):
        raise ValueError(
            f"model={tp} must divide n_heads={cfg.n_heads}, "
            f"n_kv_heads={cfg.n_kv_heads} and mlp_dim={cfg.mlp_dim} "
            "for pipeline x TP")
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"pipeline={n_stages} must divide n_layers={cfg.n_layers}")
    per = cfg.n_layers // n_stages
    if not cfg.scan_layers:
        # List-of-blocks layout: stack to the scan layout first.
        from kubeflow_tpu.parallel.pipeline import stack_stage_params

        layer_params = stack_stage_params(layer_params)
    stage_params = jax.tree.map(
        lambda p: p.reshape(n_stages, per, *p.shape[1:]), layer_params)

    # Per-leaf partition specs: stage dim over pipeline; the expert dim keeps
    # its sharding for local-EP compute; head/mlp dims keep their Megatron
    # sharding for in-stage TP (layers.py runs the matching psums).
    # PP×TP×MoE composes the two: experts shard over `expert`, each
    # expert's mlp dim over `model` — one combined psum in the moe block.
    tp_logical = ({"heads", "kv_heads", "mlp", "expert_mlp"}
                  if tp > 1 else set())

    def leaf_spec(spec):
        rest = tuple("expert" if (ep and name == "expert")
                     else "model" if name in tp_logical
                     else None
                     for name in spec)
        return P("pipeline", None, *rest)

    param_specs = jax.tree.map(leaf_spec, _block_specs(cfg),
                               is_leaf=_is_spec_leaf)
    batch_axes = tuple(a for a in ("dcn", "data", "fsdp")
                       if a in mesh.axis_names)
    xs = {"x": x}
    x_specs = {"x": P(batch_axes or None, "seq" if sp else None,
                      *([None] * (x.ndim - 2)))}
    if cfg.is_moe:
        xs["aux"] = jnp.zeros((x.shape[0], 1), jnp.float32)
        x_specs["aux"] = P(batch_axes or None, None)

    impl = {"ring": "ring_local", "ring_flash": "ring_flash_local",
            "ulysses": "ulysses_local"}.get(attn_impl, attn_impl)

    def stage_fn(blocks, xs_mb):
        h = xs_mb["x"]
        s_local = h.shape[1]
        offset = jax.lax.axis_index("seq") * s_local if sp else 0
        pos = jnp.broadcast_to(
            jnp.arange(s_local, dtype=jnp.int32)[None, :] + offset,
            (h.shape[0], s_local))

        def body(h, bp):
            # No logical-constraint mesh inside shard_map: the activation is
            # a local shard there and GSPMD annotations don't apply.
            out, _, aux = _block_forward(
                bp, h, pos, cfg, attn_impl=impl,
                expert_axis="expert" if ep else None,
                seq_axis="seq" if sp else None,
                tp_axis="model" if tp > 1 else None)
            return out, aux

        h, auxs = jax.lax.scan(body, h, blocks)
        out = {"x": h}
        if cfg.is_moe:
            out["aux"] = xs_mb["aux"] + jnp.sum(auxs)
        return out

    out = pipeline_apply(stage_fn, stage_params, xs,
                         mesh=mesh, num_microbatches=None,
                         batch_axes=batch_axes,
                         x_specs=x_specs, param_specs=param_specs,
                         schedule=cfg.pipeline_schedule,
                         # Honor the config's remat knob like the scan path
                         # (_remat); "none" really means no recompute.
                         checkpoint_stages=cfg.remat_policy != "none")
    aux = jnp.mean(out["aux"]) if cfg.is_moe else jnp.float32(0)
    return out["x"], aux


def _chunked_ce(hidden: jax.Array, head: jax.Array, targets: jax.Array,
                cfg: DecoderConfig):
    """Blockwise softmax-CE: scan over sequence chunks so only
    [B, chunk, V] logits are live at once. Under remat the backward
    recomputes per chunk (same O(S·V) flops, O(chunk·V) memory).
    Returns (nll [B,S] f32, correct [B,S] f32)."""
    b, s, d = hidden.shape
    chunk = min(cfg.loss_chunk_size, s)
    if s % chunk:
        chunk = s  # odd tails: fall back to one chunk
    n = s // chunk
    h = hidden.reshape(b, n, chunk, d).swapaxes(0, 1)      # [n,B,c,D]
    t = targets.reshape(b, n, chunk).swapaxes(0, 1)        # [n,B,c]

    @jax.checkpoint
    def body(_, ht):
        hc, tc = ht
        logits = jnp.einsum("bcd,dv->bcv", hc, head,
                            preferred_element_type=jnp.float32)
        if cfg.logits_softcap is not None:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        correct = (logits.argmax(-1) == tc).astype(jnp.float32)
        return None, (logz - picked, correct)

    _, (nll, correct) = jax.lax.scan(body, None, (h, t))
    return (nll.swapaxes(0, 1).reshape(b, s),
            correct.swapaxes(0, 1).reshape(b, s))


def decoder_loss(
    params: Params,
    tokens: jax.Array,        # [B, S+1]: input = [:, :-1], target = [:, 1:]
    cfg: DecoderConfig,
    *,
    loss_mask: Optional[jax.Array] = None,   # [B, S] 1.0 = count this target
    attn_impl: str = "xla",
    mesh=None,
    rules: LogicalRules = DEFAULT_RULES,
    aux_loss_weight: float = 0.01,
):
    """Next-token cross-entropy in fp32. Returns (loss, metrics).

    Loss-path selection, cheapest first: with fused kernels on
    (``cfg.fused_kernels``, layers.fused_kernels_on) the blockwise Pallas
    kernel (ops/fused_xent.py) fuses the output projection, log-softmax
    and NLL — the [B,S,V] logits tensor never exists in HBM, forward OR
    backward. Otherwise ``cfg.loss_chunk_size`` streams the head in
    sequence chunks ([B,chunk,V] live at once), and the dense fallback
    materializes full logits (the usual LLM-training memory hog at large
    vocab)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    fused = L.fused_kernels_on(cfg, mesh)
    if fused:
        from kubeflow_tpu.ops import fused_xent

        fused = fused_xent.supported(
            inputs.shape[0] * inputs.shape[1], cfg.hidden, cfg.vocab_size,
            dtype=cfg.activation_dtype)
    if fused:
        hidden, _, aux = decoder_forward(
            params, inputs, cfg, attn_impl=attn_impl, mesh=mesh, rules=rules,
            skip_head=True)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        hidden = L.scaled(hidden, cfg.head_multiplier)
        nll, correct = fused_xent.fused_cross_entropy(
            hidden, head.astype(hidden.dtype), targets,
            logits_softcap=cfg.logits_softcap)
    elif cfg.loss_chunk_size:
        hidden, _, aux = decoder_forward(
            params, inputs, cfg, attn_impl=attn_impl, mesh=mesh, rules=rules,
            skip_head=True)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        nll, correct = _chunked_ce(L.scaled(hidden, cfg.head_multiplier),
                                   head.astype(hidden.dtype), targets, cfg)
    else:
        logits, _, aux = decoder_forward(
            params, inputs, cfg, attn_impl=attn_impl, mesh=mesh, rules=rules)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        correct = (logits.argmax(-1) == targets).astype(jnp.float32)
    if loss_mask is None:
        loss_mask = jnp.ones_like(nll)
    denom = jnp.maximum(loss_mask.sum(), 1.0)
    ce = (nll * loss_mask).sum() / denom
    loss = ce + (aux_loss_weight * aux if cfg.is_moe else 0.0)
    metrics = {
        "ce_loss": ce,
        "aux_loss": aux,
        "tokens": denom,
        "accuracy": (correct * loss_mask).sum() / denom,
    }
    return loss, metrics
