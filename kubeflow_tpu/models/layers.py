"""Decoder building blocks: RMSNorm, RoPE, GQA attention, (Swi/Ge)GLU MLP,
MoE block — pure functions over param dicts with logical-axis spec helpers.

Every init returns ``(params, specs)`` where ``specs`` mirrors the param tree
with tuples of logical axis names (consumed by parallel.sharding). Compute
follows the TPU dtype policy: params in ``param_dtype`` (fp32), activations
and matmuls in ``dtype`` (bf16, MXU-native), reductions/softmax/norms in fp32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.ops.attention import multi_head_attention


def _init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal init with 1/sqrt(fan_in) default scale."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def scaled(x: jax.Array, m: float) -> jax.Array:
    """``x`` times a model's fixed multiplier ``m`` in ``x``'s own type (1:
    ``x`` as it came)."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


# -- Fused-kernel resolution ---------------------------------------------------

def fused_kernels_on(cfg: DecoderConfig, mesh=None) -> bool:
    """Resolve ``cfg.fused_kernels`` ("auto"|"on"|"off") to a static bool.
    "auto" follows the backend (TPU → Pallas kernels, elsewhere → XLA ops),
    the same resolution rule bench.py applies to ``attn_impl``. A
    multi-device GSPMD mesh disables them: Mosaic kernels cannot be
    auto-partitioned (the flash kernel goes through shard_map instead;
    these run per-shard only where the caller is already inside one)."""
    if mesh is not None and mesh.size > 1:
        return False
    fk = cfg.fused_kernels
    if fk == "on":
        return True
    if fk == "off":
        return False
    if fk != "auto":
        raise ValueError(f"unknown fused_kernels {fk!r} (auto|on|off)")
    return jax.default_backend() == "tpu"


# -- RMSNorm -------------------------------------------------------------------

def init_rmsnorm(cfg: DecoderConfig):
    w = jnp.zeros((cfg.hidden,), cfg.weight_dtype) if cfg.norm_plus_one \
        else jnp.ones((cfg.hidden,), cfg.weight_dtype)
    return w, ("norm",)


def init_norm(cfg: DecoderConfig, name: str):
    """A norm of the stack under ``name``: its weight and, where the stack's
    norms are LayerNorms (``norm_kind`` "layer"), its bias under ``name +
    "_b"``. Returns (leaves, specs)."""
    w, spec = init_rmsnorm(cfg)
    if cfg.norm_kind != "layer":
        return {name: w}, {name: spec}
    return ({name: w, name + "_b": jnp.zeros_like(w)},
            {name: spec, name + "_b": spec})


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, cfg: DecoderConfig,
            mesh=None, bias: Optional[jax.Array] = None) -> jax.Array:
    """The stack's norm: RMSNorm, or LayerNorm with ``bias`` where
    ``cfg.norm_kind`` says so (a norm over a head's values has no bias and
    stays an RMSNorm)."""
    if cfg.norm_kind == "layer" and bias is not None:
        return layernorm(x, w, bias, cfg.norm_eps)
    if fused_kernels_on(cfg, mesh):
        from kubeflow_tpu.ops import fused_norm

        if fused_norm.norm_supported(x.size // x.shape[-1], x.shape[-1]):
            return fused_norm.rmsnorm_fused(
                x, w, eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + cfg.norm_eps)
    wf = (1.0 + w.astype(jnp.float32)) if cfg.norm_plus_one else w.astype(jnp.float32)
    return (xf * wf).astype(x.dtype)


def add_rmsnorm(x: jax.Array, res: jax.Array, w: jax.Array,
                cfg: DecoderConfig, mesh=None,
                bias: Optional[jax.Array] = None):
    """The decoder-block residual idiom ``y = x + res; h = rmsnorm(y)``
    as one op — fused into a single Pallas pass when the kernels are on
    (the stream is read/written once), the two XLA ops otherwise.
    Returns ``(y, h)``."""
    if cfg.norm_kind == "layer" and bias is not None:
        y = x + res
        return y, layernorm(y, w, bias, cfg.norm_eps)
    if fused_kernels_on(cfg, mesh):
        from kubeflow_tpu.ops import fused_norm

        if fused_norm.norm_supported(x.size // x.shape[-1], x.shape[-1]):
            return fused_norm.add_rmsnorm_fused(
                x, res, w, eps=cfg.norm_eps, plus_one=cfg.norm_plus_one)
    y = x + res
    return y, rmsnorm(y, w, cfg, mesh)


# -- RoPE ----------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [B,S,H,D], positions: [B,S] (absolute)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)   # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs        # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]                             # [B,S,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- LoRA (multi-tenant adapters; serve/lora.py owns the registry) -------------

def lora_contrib(h: jax.Array, a_l: jax.Array, b_l: jax.Array,  # traced
                 aidx: jax.Array, scale: jax.Array) -> jax.Array:
    """Batched per-row low-rank update: one gather + two einsums.

    ``h`` [B, S, d_in] (the SAME hidden the base projection consumes);
    ``a_l`` [S_adapters, d_in, r] / ``b_l`` [S_adapters, r, d_out] —
    ONE layer's packed adapter slices; ``aidx`` [B] adapter slot per
    row; ``scale`` [S_adapters]. Rows with ``aidx < 0`` (base traffic)
    multiply by an exact 0.0, so their output is bit-unchanged when the
    result adds onto the base projection. Shapes are fixed by the
    packed buffer, so adapter churn never retraces (the F6xx fixed-
    trace contract)."""
    nslots = a_l.shape[0]
    safe = jnp.clip(aidx, 0, nslots - 1)
    a = a_l[safe]                                 # [B, d_in, r]
    b = b_l[safe]                                 # [B, r, d_out]
    s = scale[safe] * (aidx >= 0)
    t = jnp.einsum("bsd,bdr->bsr", h, a)
    return jnp.einsum("bsr,bro->bso", t, b) * s[:, None, None]


def apply_lora_layer(lora_layer: Optional[dict], target: str,
                     h: jax.Array, base: jax.Array) -> jax.Array:  # traced
    """``base + delta`` for one projection (identity when the layer
    dict is None or the target isn't packed). ``lora_layer`` is
    ``{"targets": {t: (a_l, b_l)}, "aidx": [B], "scale": [S]}`` with
    per-LAYER [S, ...] slices; ``base`` is the projection output in its
    headed shape [B, S, H, Dh] (or [B, S, D] for wo) — the contrib
    reshapes to match."""
    if lora_layer is None or target not in lora_layer["targets"]:
        return base
    a_l, b_l = lora_layer["targets"][target]
    delta = lora_contrib(h, a_l, b_l, lora_layer["aidx"],
                         lora_layer["scale"])
    # The f32 scale promotes the delta; cast back so the cache write /
    # residual keep the activation dtype.
    return base + delta.reshape(base.shape).astype(base.dtype)


def slice_layers(lora: Optional[dict]) -> Optional[dict]:
    """The per-layer scan pytree of a packed-buffer dict: target ->
    (a [L,S,din,r], b [L,S,r,dout]) with the L axis leading, ready to
    be scanned alongside ``params['layers']``. None passes through."""
    if lora is None:
        return None
    return {t: (lora["targets"][t][0], lora["targets"][t][1])
            for t in lora["targets"]}


def layer_view(lora: Optional[dict], scanned_targets: Optional[dict],
               ) -> Optional[dict]:  # traced
    """Rebind one scan step's [S, ...] target slices to the invariant
    aidx/scale operands (closed over by the scan body)."""
    if lora is None:
        return None
    return {"targets": scanned_targets, "aidx": lora["aidx"],
            "scale": lora["scale"]}


def index_layer(lora: Optional[dict], i: int) -> Optional[dict]:
    """Per-layer view for the non-scanned (list-of-blocks) forward."""
    if lora is None:
        return None
    return {"targets": {t: (a[i], b[i])
                        for t, (a, b) in lora["targets"].items()},
            "aidx": lora["aidx"], "scale": lora["scale"]}


# -- Attention block -----------------------------------------------------------

def init_attention(key, cfg: DecoderConfig):
    if cfg.is_latent:
        return init_latent_attention(key, cfg)
    if cfg.diff_attention:
        return init_diff_attention(key, cfg)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.hidden
    params = {
        "wq": _init(kq, (d, cfg.n_heads, cfg.head_dim), cfg.weight_dtype),
        "wk": _init(kk, (d, cfg.n_kv_heads, cfg.head_dim), cfg.weight_dtype),
        "wv": _init(kv, (d, cfg.n_kv_heads, cfg.head_dim), cfg.weight_dtype),
        "wo": _init(ko, (cfg.n_heads, cfg.head_dim, d), cfg.weight_dtype,
                    scale=(cfg.n_heads * cfg.head_dim) ** -0.5),
    }
    specs = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qk_norm:
        # One weight vector for all heads, over each head's values.
        params["q_norm"] = jnp.ones((cfg.head_dim,), cfg.weight_dtype)
        params["k_norm"] = jnp.ones((cfg.head_dim,), cfg.weight_dtype)
        specs["q_norm"] = specs["k_norm"] = ("norm",)
    if cfg.attn_output_gate:
        params["wgate"] = _init(jax.random.fold_in(key, 2),
                                (d, cfg.n_heads, cfg.head_dim),
                                cfg.weight_dtype)
        specs["wgate"] = ("embed", "heads", "head_dim")
    return params, specs


def gate_attention(p: dict, x: jax.Array, attn: jax.Array,
                   cfg: DecoderConfig, heads_axis: int = 2) -> jax.Array:
    """The attention output as ``wo`` takes it: times ``sigmoid(x Wgate)``,
    a value a head channel, where the layer has an output gate
    (``attn_output_gate``); as it came otherwise. ``x`` [B,S,D] is the
    block's normed input, ``attn`` [B,S,H,Dh] (``heads_axis`` 2) or
    [B,H,S,Dh] (1). One function for the forward pass and the paged
    programs."""
    if "wgate" not in p:
        return attn
    spec = "bsd,dhk->bshk" if heads_axis == 2 else "bsd,dhk->bhsk"
    gate = jax.nn.sigmoid(jnp.einsum(
        spec, x, p["wgate"].astype(cfg.activation_dtype)).astype(jnp.float32))
    return (attn.astype(jnp.float32) * gate).astype(attn.dtype)


def qk_rope(p: dict, q: jax.Array, k: jax.Array, positions: jax.Array,
            cfg: DecoderConfig, window: int = 0):
    """Queries and keys [B,S,H,Dh] as attention takes them: each head's
    values through its RMSNorm where the model has one (``qk_norm``), then
    RoPE. One function for the forward pass and the paged programs.
    ``window``: the layer's window (0: a global layer), which decides
    whether it rotates at all where ``rope_window_only``. A model with
    ``attn_multipliers`` scales its keys first."""
    k = scaled(k, (cfg.attn_multipliers or (1.0,) * 3)[2])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg)
        k = rmsnorm(k, p["k_norm"], cfg)
    if not cfg.use_rope or (cfg.rope_window_only and not window):
        return q, k
    return rope(q, positions, cfg.rope_theta), \
        rope(k, positions, cfg.rope_theta)


def _cached_attention_by_row(q, k, v, kv_cache: dict, window: int = 0):  # traced
    """The cache path for ONE START A ROW (``kv_cache["len"]`` [B]: the
    serving chunk of several prompts, each at its own position). Every row
    writes its K/V at its own start and attends as it would alone: a row's
    attention cannot depend on its neighbours.

    The rows share one cache length (the longest context's), and masked
    scores cost what live ones cost. So a row attends over the SHORTEST
    span of a fixed ladder that holds its context and its chunk's window
    (``C * 2**j + C`` positions, the whole cache last: the lengths the
    one-row chunk program is built at, serve/paged.py::context_bucket),
    chosen on the device from its own start: a young prompt beside an old
    one pays for its own context, and its sums are the ones it computes
    alone. One row has one span, its own. Returns (out, the cache as
    written)."""
    start = kv_cache["len"]
    ck, cv = kv_cache["k"], kv_cache["v"]
    b, c = q.shape[:2]
    for r in range(b):
        at = (r, start[r], 0, 0)
        ck = jax.lax.dynamic_update_slice(ck, k[r:r + 1], at)
        cv = jax.lax.dynamic_update_slice(cv, v[r:r + 1], at)
    spans, n = [], 2 * c
    while b > 1 and n < ck.shape[1]:
        spans.append(n)
        n = 2 * (n - c) + c
    spans.append(ck.shape[1])

    def attend(span):
        return lambda qr, kr, vr, at: multi_head_attention(
            qr, kr[:, :span], vr[:, :span], causal=True, q_offset=at,
            impl="xla", window=window)

    branches = [attend(s) for s in spans]
    out = []
    for r in range(b):
        too_short = sum((start[r] + 2 * c > s).astype(jnp.int32)
                        for s in spans[:-1])
        out.append(jax.lax.switch(too_short, branches, q[r:r + 1],
                                  ck[r:r + 1], cv[r:r + 1], start[r]))
    return jnp.concatenate(out), {"k": ck, "v": cv,
                                  "len": start + q.shape[1]}


def attention_block(
    p: dict,
    x: jax.Array,                       # [B,S,D]
    positions: jax.Array,               # [B,S]
    cfg: DecoderConfig,
    *,
    kv_cache: Optional[dict] = None,    # {"k","v": [B,Smax,K,Dh]}, + "len": scalar | [B]
    attn_impl: str = "xla",
    mesh=None,
    tp_axis: Optional[str] = None,      # inside shard_map: heads sharded here
    lora: Optional[dict] = None,        # per-layer adapter view (apply_lora_layer)
    window: int = 0,                    # static: a window layer's length
    cross_kv: Optional[tuple] = None,   # a cross layer: another layer's (K, V)
):
    """Returns (out [B,S,D], new_kv_cache|None).

    ``cfg.diff_attention``: the projections and the output are differential
    attention's (``diff_q`` / ``diff_kv`` / ``diff_output``); what lies
    between them, the cache and the attention itself, is GQA over paired
    heads and takes every path below. ``cross_kv`` (a "cross" layer): queries
    only, over the K and V another layer attended over ([B,Skv,KV/2,2 Dh],
    that layer's cache where there is one), each query seeing the positions
    up to its own; nothing is written.

    ``window`` > 0: a window layer, whose query ``i`` sees keys ``i - window
    < j <= i``; it takes the XLA attention (the flash kernel and the
    sequence-parallel forms have no lower bound on the keys).

    ``tp_axis`` (Megatron-style TP inside shard_map — the pipeline×TP
    composition): ``wq/wk/wv/wo`` hold this device's head shard, attention
    runs over local heads (heads are independent), and the output
    projection's partial sum psums over the axis — the manual form of the
    split GSPMD derives from the sharding rules outside shard_map."""
    if cfg.is_latent:
        if tp_axis is not None or lora is not None:
            raise NotImplementedError(
                "latent attention under in-stage tensor parallelism or with "
                "LoRA adapters")
        return latent_attention_block(p, x, positions, cfg,
                                      kv_cache=kv_cache, attn_impl=attn_impl)
    dt = cfg.activation_dtype
    if cfg.diff_attention:
        if tp_axis is not None or lora is not None:
            raise NotImplementedError(
                "differential attention under in-stage tensor parallelism "
                "or with LoRA adapters")
        q = diff_q(p, x, cfg)
        if cross_kv is not None:
            start = 0 if kv_cache is None else kv_cache["len"]
            k, v = cross_kv
            seen = jnp.arange(k.shape[1])[None, None, :] <= (
                jnp.reshape(start, (-1, 1, 1)) + jnp.arange(x.shape[1])[
                    None, :, None])                          # [B|1,S,Skv]
            out = multi_head_attention(q, k, v, causal=False,
                                       mask=seen[:, None])
            return checkpoint_name(diff_output(p, out, cfg), "attn_out"), None
        k, v = diff_kv(p, x, cfg)
        return _attend(p, x, q, k, v, cfg, kv_cache, attn_impl, mesh,
                       tp_axis, lora, window)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if lora is not None:
        # Multi-tenant adapters: each row's low-rank delta adds onto the
        # shared base projection (gather + two einsums per target; rows
        # with adapter_idx = -1 add an exact zero).
        q = apply_lora_layer(lora, "wq", x, q)
        k = apply_lora_layer(lora, "wk", x, k)
        v = apply_lora_layer(lora, "wv", x, v)
    # Names feed the "block_outs" remat policy: saving post-rope Q/K/V plus
    # the block outputs skips reprojecting + re-rotating in the backward
    # while staying far under dots_no_batch's save footprint.
    q, k = qk_rope(p, q, k, positions, cfg, window)
    return _attend(p, x, q, k, v, cfg, kv_cache, attn_impl, mesh, tp_axis,
                   lora, window)


def _attend(p, x, q, k, v, cfg: DecoderConfig, kv_cache, attn_impl, mesh,
            tp_axis, lora, window):
    """``attention_block`` behind its projections: the cache, the attention
    of whichever path, the output projection."""
    dt = cfg.activation_dtype
    q = checkpoint_name(q, "q_rope")
    k = checkpoint_name(k, "k_rope")
    v = checkpoint_name(v, "v_proj")
    if window:
        if attn_impl not in ("xla", "pallas"):
            raise NotImplementedError(
                f"a window layer under attn_impl={attn_impl!r}")
        attn_impl = "xla"

    new_cache = None
    if kv_cache is not None and jnp.ndim(kv_cache["len"]):
        out, new_cache = _cached_attention_by_row(q, k, v, kv_cache, window)
    elif kv_cache is not None:
        # Contiguous cache decode path: write new K/V at position `len`.
        start = kv_cache["len"]
        ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k, start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v, start, axis=1)
        new_cache = {"k": ck, "v": cv, "len": start + x.shape[1]}
        # A traced cache offset: the masked XLA path (the pallas kernel
        # needs a static q_offset).
        impl = "xla" if attn_impl in ("pallas", "ring", "ring_flash",
                                      "ulysses") else attn_impl
        out = multi_head_attention(
            q, ck, cv, causal=True, q_offset=start, impl=impl,
            window=window)
    elif attn_impl in ("ring", "ring_flash", "ulysses"):
        # Sequence-parallel attention over the mesh 'seq' axis (SURVEY.md
        # §2.6 SP/CP rows). Degenerates to XLA attention when the mesh has
        # no seq sharding (keeps tiny/test configs running unchanged).
        # "ring" resolves its inner block impl by backend (flash kernels on
        # TPU); "ring_flash" forces the kernels (interpret off-TPU) — the
        # dryrun's way of exercising the kernel ring without chips.
        if mesh is None or dict(mesh.shape).get("seq", 1) == 1:
            out = multi_head_attention(q, k, v, causal=True, impl="xla")
        else:
            from kubeflow_tpu.parallel.ring_attention import (
                ring_attention_sharded, ulysses_attention_sharded,
            )

            if attn_impl == "ulysses":
                out = ulysses_attention_sharded(q, k, v, mesh, causal=True)
            else:
                out = ring_attention_sharded(
                    q, k, v, mesh, causal=True,
                    impl="pallas" if attn_impl == "ring_flash" else "auto")
    elif attn_impl in ("ring_local", "ring_flash_local", "ulysses_local"):
        # Already inside shard_map with Q/K/V sharded on dim 1 over 'seq'
        # (the pipeline×SP composition): call the collective form directly.
        from kubeflow_tpu.parallel.ring_attention import (
            ring_attention, ulysses_attention,
        )

        if attn_impl == "ulysses_local":
            out = ulysses_attention(q, k, v, causal=True)
        else:
            out = ring_attention(
                q, k, v, causal=True,
                impl="pallas" if attn_impl == "ring_flash_local" else "auto")
    elif attn_impl == "pallas" and mesh is not None and mesh.size > 1:
        # Mosaic kernels can't be GSPMD-auto-partitioned: run the flash
        # kernel per-shard via shard_map (block-diagonal over batch/heads);
        # shapes that don't shard cleanly fall back to XLA attention.
        from kubeflow_tpu.ops.flash_attention import flash_sharded_or_xla

        out = flash_sharded_or_xla(q, k, v, mesh, causal=True)
    else:
        out = multi_head_attention(q, k, v, causal=True, impl=attn_impl,
                                   window=window)
    if cfg.diff_attention:
        return checkpoint_name(diff_output(p, out, cfg), "attn_out"), \
            new_cache
    out = gate_attention(p, x, out, cfg)
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    if lora is not None and "wo" in lora["targets"]:
        b, s = out.shape[0], out.shape[1]
        proj = apply_lora_layer(lora, "wo", out.reshape(b, s, -1), proj)
    if tp_axis is not None:
        proj = jax.lax.psum(proj, tp_axis)
    return checkpoint_name(proj, "attn_out"), new_cache


# -- Differential attention -----------------------------------------------------

def init_diff_attention(key, cfg: DecoderConfig, cross: bool = False):
    """A differential attention operator (arXiv:2410.05258, the
    ``multihead_flashdiff_2`` form): ``wq`` [H Dh, D], ``wk`` / ``wv`` [KV
    Dh, D] (OUT by IN: how the chip's compiler lays a projection whose result
    is parted into heads, so no program copies one; none where ``cross``: the
    layer reads another layer's K and V), ``wo`` [H Dh, D], their biases
    where ``attn_bias``; the four lambda
    vectors of ``head_dim``; ``subln`` [2 Dh], the weight of the RMSNorm over
    a pair's output; ``lambda_init``, a constant of the layer's depth
    (``diff_lambda_init``: set by whoever knows the depth). The matrices are
    flat: a head of 64 values in a per-head leaf is half a lane tile."""
    ks = iter(jax.random.split(key, 8))
    d, wdt, dh = cfg.hidden, cfg.weight_dtype, cfg.head_dim
    widths = {"q": cfg.q_dim} if cross else {
        "q": cfg.q_dim, "k": cfg.kv_dim, "v": cfg.kv_dim}
    params = {"w" + n: _init(next(ks), (w, d), wdt, scale=d ** -0.5)
              for n, w in widths.items()}
    specs = {"w" + n: ("heads" if n == "q" else "kv_heads", "embed")
             for n in widths}
    params["wo"] = _init(next(ks), (cfg.q_dim, d), wdt)
    specs["wo"] = ("heads", "embed")
    if cfg.attn_bias:
        widths["o"] = d
        for n, w in widths.items():
            params["b" + n] = jnp.zeros((w,), wdt)
            specs["b" + n] = ("norm",)
    for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        params[n] = (0.1 * jax.random.normal(next(ks), (dh,),
                                             jnp.float32)).astype(wdt)
        specs[n] = ("norm",)
    params["subln"] = jnp.ones((2 * dh,), wdt)
    params["lambda_init"] = jnp.float32(diff_lambda_init(0))
    specs["subln"], specs["lambda_init"] = ("norm",), ()
    return params, specs


def diff_lambda_init(depth):
    """``lambda_init`` of the layer at ``depth`` in the stack."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def _project(p: dict, name: str, x: jax.Array, cfg: DecoderConfig):
    """``x W + b`` in float32: [B,S,D] -> [B,S,W] (q, k and v lie OUT by
    IN, the output projection IN by OUT)."""
    y = jnp.einsum("bsd,de->bse" if name == "o" else "bsd,ed->bse", x,
                   p["w" + name].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if "b" + name in p:
        y = y + p["b" + name].astype(jnp.float32)
    return y


def diff_q(p: dict, x: jax.Array, cfg: DecoderConfig) -> jax.Array:
    """Differential attention's queries as the attention paths and the
    paged kernels take them: [B,S,H,2 Dh]. Query pair ``i`` is heads ``2i``
    (``q1``) and ``2i + 1`` (``q2``) of ``head_dim``; over K rows ``[k[2j] |
    k[2j+1]]`` (``diff_kv``) the padded queries ``[q1 | 0]`` and ``[0 | q2]``
    score as ``q1 . k[2j]`` and ``q2 . k[2j+1]``, which is GQA of ``H`` heads
    over ``KV / 2`` of twice the width: head ``h`` reads KV pair ``h // (2 H
    / KV)``, its own pair's. The paths scale by ``(2 Dh) ** -0.5`` where the
    model scales by ``Dh ** -0.5``: the queries carry the ``sqrt 2``."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = (_project(p, "q", x, cfg) * 2.0 ** 0.5).astype(
        cfg.activation_dtype).reshape(b, s, cfg.n_heads // 2, 2, dh)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([q[..., 0, :], zero], axis=-1),
         jnp.concatenate([zero, q[..., 1, :]], axis=-1)],
        axis=3).reshape(b, s, cfg.n_heads, 2 * dh)


def diff_kv(p: dict, x: jax.Array, cfg: DecoderConfig):
    """A token's K and V as the cache keeps them: [B,S,KV/2,2 Dh] each, the
    adjacent heads ``2j`` and ``2j + 1`` side by side."""
    b, s, _ = x.shape
    shape = (b, s, cfg.n_kv_heads // 2, 2 * cfg.head_dim)
    return tuple(_project(p, n, x, cfg).astype(cfg.activation_dtype)
                 .reshape(shape) for n in ("k", "v"))


def diff_output(p: dict, attn: jax.Array, cfg: DecoderConfig) -> jax.Array:
    """From the padded queries' attention [B,S,H,2 Dh] (``A1 V`` at the even
    heads, ``A2 V`` at the odd) to the block's output [B,S,D]: ``(A1 - lambda
    A2) V`` a pair, its RMSNorm over the pair's ``2 Dh`` values times ``1 -
    lambda_init``, the output projection."""
    b, s = attn.shape[:2]
    f32 = jnp.float32
    lam0 = jax.lax.stop_gradient(p["lambda_init"].astype(f32))
    lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                          * p["lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                          * p["lambda_k2"].astype(f32))) + lam0
    a = attn.astype(f32).reshape(b, s, cfg.n_heads // 2, 2, -1)
    o = a[..., 0, :] - lam * a[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["subln"].astype(f32)
    o = (o * (1.0 - lam0)).astype(cfg.activation_dtype).reshape(b, s, -1)
    return _project(p, "o", o, cfg).astype(cfg.activation_dtype)


# -- Mamba-1 selective scan and the gated memory unit ---------------------------

SSM_STEP_RANGE = (1e-3, 1e-1)


def init_ssm(key, cfg: DecoderConfig):
    """An ssm layer's operator (``ops/ssm.py`` has the recurrence): ``wu`` /
    ``wz`` [D, E], the in projection's two halves; ``conv`` [taps, E]
    (``[-1]`` multiplies the current position) and ``conv_b`` [E]; ``wx`` [E,
    R + 2 N]: the step's low-rank input, B and C; ``wdt`` [R, E] and
    ``dt_bias`` [E]; ``a_log`` [N, E] (``A = -exp(a_log)``, states on the
    leading axis as the scan lays them); ``d_skip`` [E]; ``wout`` [E, D].
    ``a_log`` and ``dt_bias`` start as Mamba's: ``A = 1 .. N`` a channel, the
    bias the inverse softplus of a step log-uniform in [1e-3, 1e-1]."""
    ks = iter(jax.random.split(key, 7))
    d, e, n, r = cfg.hidden, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    wdt, taps = cfg.weight_dtype, cfg.conv_taps
    step = jnp.exp(jax.random.uniform(
        next(ks), (e,), jnp.float32, *jnp.log(jnp.asarray(SSM_STEP_RANGE))))
    params = {
        "wu": _init(next(ks), (d, e), wdt), "wz": _init(next(ks), (d, e), wdt),
        "conv": _init(next(ks), (taps, e), wdt, scale=taps ** -0.5),
        "conv_b": jnp.zeros((e,), wdt),
        "wx": _init(next(ks), (e, r + 2 * n), wdt),
        "wdt": _init(next(ks), (r, e), wdt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(wdt),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, e)).astype(wdt),
        "d_skip": jnp.ones((e,), wdt),
        "wout": _init(next(ks), (e, d), wdt),
    }
    specs = {"wu": ("embed", "mlp"), "wz": ("embed", "mlp"),
             "conv": (None, "mlp"), "conv_b": ("mlp",), "wx": ("mlp", None),
             "wdt": (None, "mlp"), "dt_bias": ("mlp",),
             "a_log": (None, "mlp"), "d_skip": ("mlp",),
             "wout": ("mlp", "embed")}
    return params, specs


def ssm_inputs(p: dict, x: jax.Array, cfg: DecoderConfig,
               tail: Optional[jax.Array] = None, valid_len=None):
    """What the scan takes of ``x`` [B,S,D]: (c [B,S,E] float32: the
    convolved, SiLU'd channels; z [B,S,E]: the gate's input; delta [B,S,E]
    float32; bm, cm [B,S,N] float32; the convolution's tail after the last
    valid position [B,taps-1,E]). ``tail``: the ``taps - 1`` projected rows
    before ``x`` (zeros at a sequence's start and when None). A position ``>=
    valid_len`` ([B]) is padding: its convolution input and its ``delta`` are
    0, so the state passes through it unchanged."""
    dt = cfg.activation_dtype
    b, s, _ = x.shape
    taps, n, r = cfg.conv_taps, cfg.ssm_state, cfg.ssm_dt_rank
    u = jnp.einsum("bsd,de->bse", x, p["wu"].astype(dt))
    z = jnp.einsum("bsd,de->bse", x, p["wz"].astype(dt))
    valid = None
    if valid_len is not None:
        valid = jnp.arange(s)[None, :] < jnp.reshape(valid_len, (-1, 1))
        u = jnp.where(valid[..., None], u, 0)
    if tail is None:
        tail = jnp.zeros((b, taps - 1, cfg.ssm_inner), dt)
    us = jnp.concatenate([tail.astype(dt), u], axis=1)   # [B,taps-1+S,E]
    w = p["conv"].astype(jnp.float32)
    c = jax.nn.silu(sum(w[j] * us[:, j:j + s].astype(jnp.float32)
                        for j in range(taps))
                    + p["conv_b"].astype(jnp.float32))
    if valid is None:
        tail = us[:, s:]
    else:           # the rows before position ``valid_len``
        at = jnp.reshape(valid_len, (-1, 1)) + jnp.arange(taps - 1)
        tail = jnp.take_along_axis(
            us, jnp.broadcast_to(at, (b, taps - 1))[..., None], axis=1)
    dbc = jnp.einsum("bse,er->bsr", c.astype(dt), p["wx"].astype(dt),
                     preferred_element_type=jnp.float32)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dbc[..., :r].astype(dt),
                   p["wdt"].astype(dt), preferred_element_type=jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    if valid is not None:
        delta = jnp.where(valid[..., None], delta, 0.0)
    return c, z, delta, dbc[..., r:r + n], dbc[..., r + n:], tail


def ssm_decay(p: dict) -> jax.Array:
    """``A`` [N, E] float32, negative."""
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def ssm_output(p: dict, y: jax.Array, z: jax.Array,
               cfg: DecoderConfig) -> jax.Array:
    """``(y * SiLU(z)) Wout``: y [B,S,E] float32, the scan's output."""
    dt = cfg.activation_dtype
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    return jnp.einsum("bse,ed->bsd", gated, p["wout"].astype(dt))


def ssm_block(p: dict, x: jax.Array, cfg: DecoderConfig,
              state: Optional[tuple] = None, valid_len=None,
              impl: str = "xla"):
    """The Mamba-1 mixer over ``x`` [B,S,D] from ``state`` to a state: (the
    recurrent state [B,N,E] float32, the convolution's tail [B,taps-1,E]);
    zeros when None (a sequence's start). Returns (out [B,S,D], the state
    after the last valid position, the scan's output ``y`` [B,S,E] float32
    BEFORE its gate: the memory a gated memory unit reads). Rows never
    mix."""
    from kubeflow_tpu.ops import ssm

    h, tail = state if state is not None else (None, None)
    if h is None:
        h = jnp.zeros((x.shape[0], cfg.ssm_state, cfg.ssm_inner),
                      jnp.float32)
    c, z, delta, bm, cm, tail = ssm_inputs(p, x, cfg, tail, valid_len)
    y, h = ssm.ssm_scan(c, delta, bm, cm, ssm_decay(p),
                        p["d_skip"].astype(jnp.float32), h, impl=impl)
    return checkpoint_name(ssm_output(p, y, z, cfg), "attn_out"), \
        (h, tail), y


def init_gmu(key, cfg: DecoderConfig):
    """A gated memory unit's two matrices: ``w1`` [D, E], ``w2`` [E, D]."""
    k1, k2 = jax.random.split(key)
    d, e = cfg.hidden, cfg.ssm_inner
    return ({"w1": _init(k1, (d, e), cfg.weight_dtype),
             "w2": _init(k2, (e, d), cfg.weight_dtype)},
            {"w1": ("embed", "mlp"), "w2": ("mlp", "embed")})


def gmu_block(p: dict, x: jax.Array, memory: jax.Array,
              cfg: DecoderConfig) -> jax.Array:
    """``(SiLU(x W1) * m) W2``: ``memory`` [B,S,E] float32 is an ssm layer's
    scan output at the same positions."""
    dt = cfg.activation_dtype
    gate = jax.nn.silu(jnp.einsum(
        "bsd,de->bse", x, p["w1"].astype(dt),
        preferred_element_type=jnp.float32))
    out = jnp.einsum("bse,ed->bsd", (gate * memory).astype(dt),
                     p["w2"].astype(dt))
    return checkpoint_name(out, "attn_out")


# -- Mamba-2 (SSD) mixer, the second branch of a parallel block -----------------

def init_ssd(key, cfg: DecoderConfig):
    """An SSD mixer's leaves (``ops/ssd.py`` has the recurrence): the
    in-projection's three column blocks as three leaves, ``w_z`` [D, E] (the
    gate; E = ``ssd_inner``), ``w_xbc`` [D, C] (``[x | B | C]``; C =
    ``ssd_conv_dim``) and ``w_dt`` [D, H] (a step a head): ONE matrix [D, E +
    C + H] is no whole number of 128-lane tiles at the published widths
    (9248 columns), and the chip's compiler then copies all of it in front
    of every decode step (0.47 GB at five layers: a compile for a described
    v5e, PR 50); ``conv`` [taps, C] (``[-1]`` multiplies the current
    position) and ``conv_b`` [C]; ``a_log``, ``d_skip``, ``dt_bias`` [H];
    ``ssd_norm`` [E], the gated group norm's weight; ``w_out`` [E, D].
    ``a_log`` and ``dt_bias`` start as Mamba-2's: ``A`` uniform in [1, 16],
    the bias the inverse softplus of a step log-uniform in [1e-3, 1e-1]."""
    ks = iter(jax.random.split(key, 7))
    d, e, c, h = cfg.hidden, cfg.ssd_inner, cfg.ssd_conv_dim, cfg.ssd_heads
    wdt, taps = cfg.weight_dtype, cfg.conv_taps
    step = jnp.exp(jax.random.uniform(
        next(ks), (h,), jnp.float32, *jnp.log(jnp.asarray(SSM_STEP_RANGE))))
    params = {
        "w_z": _init(next(ks), (d, e), wdt),
        "w_xbc": _init(next(ks), (d, c), wdt),
        "w_dt": _init(next(ks), (d, h), wdt),
        "conv": _init(next(ks), (taps, c), wdt, scale=taps ** -0.5),
        "conv_b": jnp.zeros((c,), wdt),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (h,), jnp.float32, 1.0, 16.0)).astype(wdt),
        "d_skip": jnp.ones((h,), wdt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(wdt),
        "ssd_norm": jnp.ones((e,), wdt),
        "w_out": _init(next(ks), (e, d), wdt),
    }
    specs = {"w_z": ("embed", "mlp"), "w_xbc": ("embed", "mlp"),
             "w_dt": ("embed", None), "conv": (None, "mlp"),
             "conv_b": ("mlp",), "a_log": (None,), "d_skip": (None,),
             "dt_bias": (None,), "ssd_norm": ("mlp",),
             "w_out": ("mlp", "embed")}
    return params, specs


def init_parallel(key, cfg: DecoderConfig):
    """A parallel block's operator: an attention operator's leaves and an
    SSD mixer's, side by side in one dict (no name is in both)."""
    ka, ks = jax.random.split(key)
    attn_p, attn_s = init_attention(ka, cfg)
    ssd_p, ssd_s = init_ssd(ks, cfg)
    return {**attn_p, **ssd_p}, {**attn_s, **ssd_s}


def ssd_inputs(p: dict, x: jax.Array, cfg: DecoderConfig,
               tail: Optional[jax.Array] = None, valid_len=None,
               follows=None):
    """What the recurrence takes of ``x`` [B,S,D], the block's normed input:
    (xs [B,S,H,P]: the convolved, SiLU'd values a head, in the activation
    type; z [B,S,E]: the gate's input; dt [B,S,H] float32, after its
    softplus; bm, cm [B,S,G,N]; the convolution's tail after the last valid
    position [B,taps-1,C]). ``[z | xBC | dt] = ((in x) [W_z | W_xbc | W_dt])
    * m`` with ``m`` the multipliers of the column blocks ``z, x, B, C, dt``
    (``cfg.ssd_multipliers``); ``xBC`` through a causal depthwise
    convolution of ``conv_taps`` taps and its bias (``tail``: the ``taps -
    1`` rows of ``xBC`` before ``x``, zeros at a sequence's start and when
    None), SiLU. A position ``>= valid_len`` ([B])
    is padding: its convolution input and its ``dt`` are 0, so the state
    passes through it unchanged. ``follows`` ([B] bool, with ``tail`` and
    ``S >= taps - 1``): row ``r`` is the chunk behind row ``r - 1``'s, a
    FULL one, and its tail is that row's last ``taps - 1`` rows of ``xBC``
    as ``tail``'s type would have kept them, not ``tail[r]``."""
    dt_ = cfg.activation_dtype
    b, s, _ = x.shape
    taps, h, g, n = cfg.conv_taps, cfg.ssd_heads, cfg.ssd_groups, \
        cfg.ssd_state
    e, c = cfg.ssd_inner, cfg.ssd_conv_dim
    m_in, _, m_z, m_x, m_b, m_c, m_dt = cfg.ssd_multipliers or (1.0,) * 7
    x = scaled(x, m_in)
    z = scaled(jnp.einsum("bsd,de->bse", x, p["w_z"].astype(dt_)), m_z)
    xbc = jnp.einsum("bsd,de->bse", x, p["w_xbc"].astype(dt_))
    if not m_x == m_b == m_c == 1.0:
        xbc = xbc * jnp.concatenate([
            jnp.full((w,), m, dt_)
            for w, m in ((e, m_x), (g * n, m_b), (g * n, m_c))])
    dt = scaled(jnp.einsum("bsd,dh->bsh", x, p["w_dt"].astype(dt_),
                           preferred_element_type=jnp.float32), m_dt)
    valid = None
    if valid_len is not None:
        valid = jnp.arange(s)[None, :] < jnp.reshape(valid_len, (-1, 1))
        xbc = jnp.where(valid[..., None], xbc, 0)
    if tail is None:
        tail = jnp.zeros((b, taps - 1, c), dt_)
    if follows is not None:     # (row 0 never follows: what rolls in is unread)
        front = jnp.roll(xbc[:, s - (taps - 1):], 1, axis=0)
        tail = jnp.where(follows[:, None, None], front.astype(tail.dtype),
                         tail)
    us = jnp.concatenate([tail.astype(dt_), xbc], axis=1)  # [B,taps-1+S,C]
    w = p["conv"].astype(jnp.float32)
    conv = jax.nn.silu(sum(w[j] * us[:, j:j + s].astype(jnp.float32)
                           for j in range(taps))
                       + p["conv_b"].astype(jnp.float32)).astype(dt_)
    if valid is None:
        tail = us[:, s:]
    else:           # the rows before position ``valid_len``
        at = jnp.reshape(valid_len, (-1, 1)) + jnp.arange(taps - 1)
        tail = jnp.take_along_axis(
            us, jnp.broadcast_to(at, (b, taps - 1))[..., None], axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    return (conv[..., :e].reshape(b, s, h, -1), z, dt,
            conv[..., e:e + g * n].reshape(b, s, g, n),
            conv[..., e + g * n:].reshape(b, s, g, n), tail)


def ssd_decay(p: dict) -> jax.Array:
    """``A`` [H] float32, negative."""
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def ssd_output(p: dict, y: jax.Array, z: jax.Array,
               cfg: DecoderConfig) -> jax.Array:
    """``out GroupRMSNorm(y * SiLU(z)) W_out``: y [B,S,H,P] float32, the
    recurrence's output; the norm over each of ``ssd_groups`` groups of
    channels, one weight a channel (the gate BEFORE the norm)."""
    dt = cfg.activation_dtype
    b, s = y.shape[:2]
    gated = y.reshape(b, s, -1) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(b, s, cfg.ssd_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
    normed = (grouped.reshape(b, s, -1)
              * p["ssd_norm"].astype(jnp.float32)).astype(dt)
    out = jnp.einsum("bse,ed->bsd", normed, p["w_out"].astype(dt))
    return scaled(out, (cfg.ssd_multipliers or (1.0, 1.0))[1])


def ssd_block(p: dict, x: jax.Array, cfg: DecoderConfig,
              state: Optional[tuple] = None, valid_len=None,
              impl: str = "xla"):
    """The SSD mixer over ``x`` [B,S,D] from ``state`` to a state: (the
    recurrent state [B,H,N,P] float32, or as a pool's plane holds it,
    ``ssd.pack_state``; the convolution's tail [B,taps-1,C]);
    zeros when None (a sequence's start). Returns (out [B,S,D], the state
    after the last valid position, laid as it came). Rows never mix."""
    from kubeflow_tpu.ops import ssd

    mat, tail = state if state is not None else (None, None)
    if mat is None:
        mat = jnp.zeros((x.shape[0], cfg.ssd_heads, cfg.ssd_state,
                         cfg.ssd_head_dim), jnp.float32)
    packed = cfg.ssd_heads // mat.shape[1]
    xs, z, dt, bm, cm, tail = ssd_inputs(p, x, cfg, tail, valid_len)
    y, mat = ssd.ssd_chunk(xs, dt, ssd_decay(p), bm, cm,
                           p["d_skip"].astype(jnp.float32),
                           ssd.unpack_state(mat, cfg.ssd_heads), impl=impl,
                           block=cfg.ssd_chunk)
    return ssd_output(p, y, z, cfg), (ssd.pack_state(mat, packed), tail)


def parallel_block(p: dict, x: jax.Array, positions: jax.Array,
                   cfg: DecoderConfig, kv_cache: Optional[dict] = None,
                   state: Optional[tuple] = None, valid_len=None,
                   attn_impl: str = "xla", mesh=None):
    """A parallel block's two branches on ONE normed input ``x`` [B,S,D]:
    attention (``attention_block`` over ``kv_cache``) and the SSD mixer
    (``ssd_block`` from ``state``), each between its multipliers. Returns
    (their sum [B,S,D], the K/V cache as written | None, the SSD state
    after)."""
    a_in, a_out, _ = cfg.attn_multipliers or (1.0, 1.0, 1.0)
    with jax.named_scope("parallel_attention"):
        attn, new_cache = attention_block(
            p, scaled(x, a_in), positions, cfg, kv_cache=kv_cache,
            attn_impl=attn_impl, mesh=mesh)
    with jax.named_scope("parallel_ssd"):
        mixed, state = ssd_block(p, x, cfg, state, valid_len)
    return checkpoint_name(scaled(attn, a_out) + mixed, "attn_out"), \
        new_cache, state


# -- Latent attention (MLA) ----------------------------------------------------

def init_latent_attention(key, cfg: DecoderConfig):
    """Queries through a ``q_lora_rank`` bottleneck (``wqa``, a norm,
    ``wqb``); keys and values from ONE ``kv_lora_rank`` latent row a token
    (``wkva``'s first columns, a norm) expanded per head by ``wkvb`` into
    ``qk_nope_dim`` key values and ``v_head_dim`` value values, beside
    ``qk_rope_dim`` rotary key values (``wkva``'s last columns) that every
    head shares."""
    if cfg.q_lora_rank <= 0:
        raise ValueError("latent attention needs q_lora_rank > 0")
    kqa, kqb, kkva, kkvb, ko = jax.random.split(key, 5)
    d, h, wdt = cfg.hidden, cfg.n_heads, cfg.weight_dtype
    r, q = cfg.kv_lora_rank, cfg.q_lora_rank
    params = {
        "wqa": _init(kqa, (d, q), wdt),
        "q_norm": jnp.ones((q,), wdt),
        "wqb": _init(kqb, (q, h, cfg.qk_nope_dim + cfg.qk_rope_dim), wdt),
        "wkva": _init(kkva, (d, r + cfg.qk_rope_dim), wdt),
        "kv_norm": jnp.ones((r,), wdt),
        "wkvb": _init(kkvb, (r, h, cfg.qk_nope_dim + cfg.v_head_dim), wdt),
        "wo": _init(ko, (h, cfg.v_head_dim, d), wdt,
                    scale=(h * cfg.v_head_dim) ** -0.5),
    }
    specs = {
        "wqa": ("embed", None), "q_norm": ("norm",),
        "wqb": (None, "heads", "head_dim"),
        "wkva": ("embed", None), "kv_norm": ("norm",),
        "wkvb": (None, "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.index_topk:
        # The indexer (``index_qkw``): its queries from the latent query,
        # ONE key a token (a LayerNorm with a bias), a weight a head.
        kqi, kki, kwi = jax.random.split(jax.random.fold_in(key, 1), 3)
        hi, di = cfg.index_heads, cfg.index_head_dim
        params.update({
            "wq_idx": _init(kqi, (hi * di, q), wdt, scale=q ** -0.5),
            "wk_idx": _init(kki, (d, di), wdt),
            "k_idx_norm": jnp.ones((di,), wdt),
            "k_idx_bias": jnp.zeros((di,), wdt),
            "w_idx": _init(kwi, (d, hi), wdt),
        })
        specs.update({
            "wq_idx": (None, None), "wk_idx": ("embed", None),
            "k_idx_norm": ("norm",), "k_idx_bias": ("norm",),
            "w_idx": ("embed", None),
        })
    return params, specs


def latent_row_width(cfg: DecoderConfig) -> int:
    """Width of the ONE row a token keeps in a layer of the cache: the
    latent and the rotary values side by side, padded with zeros to whole
    128-value lanes (576 -> 640 at the published ranks), so that a page is
    one aligned block for the device and for a kernel's one DMA."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // 128) * 128


def _as_latent_row(parts: list, cfg: DecoderConfig) -> jax.Array:
    """[.., r] and [.., rope] side by side, zeros up to the row's width."""
    pad = latent_row_width(cfg) - cfg.kv_lora_rank - cfg.qk_rope_dim
    zeros = jnp.zeros((*parts[0].shape[:-1], pad), parts[0].dtype)
    return jnp.concatenate([*parts, zeros], axis=-1)


def latent_qkv(p: dict, x: jax.Array, positions: jax.Array,
               cfg: DecoderConfig):
    """The block's projections of ``x`` [B,S,D]: per-head queries split
    into (q_nope [B,S,H,nope], q_rope [B,S,H,rope], rotated), this token's
    CACHE row [B,S,W]: ``ckv`` (r values, after its norm), then ``k_rope``
    (after RoPE), then zeros up to ``latent_row_width``; and the latent
    query ``cq`` [B,S,q_lora_rank] the queries were projected from (an
    indexer's are projected from it too).

    ``cfg.latent_rank_scale``: the queries times ``sqrt(hidden /
    q_lora_rank)`` and the normed latent times ``sqrt(hidden /
    kv_lora_rank)``. The latent's factor stands in front of its expansion
    into keys and values; it is applied HERE, when the row is written, so
    the cache holds the scaled latent and every reader (``latent_query``,
    ``latent_output``, the kernels) is what it was."""
    dt = cfg.activation_dtype
    r = cfg.kv_lora_rank
    cq = rmsnorm(jnp.einsum("bsd,dq->bsq", x, p["wqa"].astype(dt)),
                 p["q_norm"], cfg)
    q = jnp.einsum("bsq,qhk->bshk", cq, p["wqb"].astype(dt))
    kv_norm = p["kv_norm"]
    if cfg.latent_rank_scale:
        # (in float32, each rounded once: the latent's factor rides in its
        # norm's weight)
        q = (q.astype(jnp.float32)
             * (cfg.hidden / cfg.q_lora_rank) ** 0.5).astype(dt)
        kv_norm = kv_norm.astype(jnp.float32) * (cfg.hidden / r) ** 0.5
    q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
    kva = jnp.einsum("bsd,dr->bsr", x, p["wkva"].astype(dt))
    ckv = rmsnorm(kva[..., :r], kv_norm, cfg)
    k_rope = rope(kva[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return (q_nope, rope(q_rope, positions, cfg.rope_theta),
            _as_latent_row([ckv, k_rope], cfg), cq)


# The indexer's key norm: a LayerNorm with a bias, its own eps whatever the
# stack's norms are (DeepSeek-V3.2-Exp's ``Indexer.k_norm``).
INDEX_NORM_EPS = 1e-6


def index_qkw(p: dict, x: jax.Array, cq: jax.Array, positions: jax.Array,
              cfg: DecoderConfig):
    """The indexer's projections of the block's input ``x`` [B,S,D] and the
    latent query ``cq`` [B,S,q]: queries [B,S,Hi,Di] (RoPE on a head's first
    ``qk_rope_dim`` values), this token's KEY [B,S,Di] (one for all heads:
    LayerNorm, then RoPE on the same values; what the cache's ``idx`` plane
    holds) and the heads' weights [B,S,Hi] float32, both constant factors
    ``Hi ** -0.5`` and ``Di ** -0.5`` folded in."""
    dt = cfg.activation_dtype
    rd, theta = cfg.qk_rope_dim, cfg.rope_theta

    def rotated(v):     # [B,S,H,Di]: RoPE on the first rd values
        return jnp.concatenate(
            [rope(v[..., :rd], positions, theta), v[..., rd:]], axis=-1)

    # (one [Hi x Di, q] matrix, the latent query's values along its rows: as
    # [q, Hi, Di] or [q, Hi x Di] the chip's compiler copies the stacked
    # leaf whole, transposed, in front of every decode step)
    q = jnp.einsum("bsq,nq->bsn", cq, p["wq_idx"].astype(dt))
    q = rotated(q.reshape(*q.shape[:2], cfg.index_heads, cfg.index_head_dim))
    k = layernorm(jnp.einsum("bsd,dk->bsk", x, p["wk_idx"].astype(dt)),
                  p["k_idx_norm"], p["k_idx_bias"], INDEX_NORM_EPS)
    w = jnp.einsum("bsd,dh->bsh", x, p["w_idx"].astype(dt)).astype(
        jnp.float32) * (cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return q, rotated(k[:, :, None])[:, :, 0], w


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """The indexer's score of every (query, key) pair in plain XLA (the
    kernel ``ops/paged_attention.py::paged_index_scores`` computes the same
    sums page by page): ``I(t, s) = sum_j w[t,j] ReLU(q[t,j] . keys[s])``,
    float32, ``-inf`` where key ``s`` lies behind query ``t``'s position.
    q [B,T,Hi,Di], w [B,T,Hi], keys [B,S,Di] (position ``s`` at index
    ``s``), positions [B,T] -> [B,T,S]. A head at a time, so that nothing
    ``[B,T,Hi,S]`` is alive."""
    def head(acc, qw):
        q_j, w_j = qw                               # [B,T,Di], [B,T]
        dots = jnp.einsum("btd,bsd->bts", q_j, keys,
                          preferred_element_type=jnp.float32)
        return acc + w_j[..., None] * jax.nn.relu(dots), None

    b, t = q.shape[:2]
    total, _ = jax.lax.scan(
        head, jnp.zeros((b, t, keys.shape[1]), jnp.float32),
        (jnp.moveaxis(q, 2, 0), jnp.moveaxis(w, 2, 0)))
    seen = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, None, :] \
        <= positions[:, :, None]
    return jnp.where(seen, total, -jnp.inf)


def select_keys(scores: jax.Array, k: int) -> jax.Array:
    """The selection, EXACT: of each query's visible keys (``scores``
    [..,T,S] float32, ``-inf`` where a key is not visible) the ``k`` of
    largest score, a tie going to the lower position; every visible key
    where there are at most ``k``. Returns a mask [..,T,S], True = attend.

    No sort: what attends under a mask needs each query's ``k``-th largest
    score alone, and that is found bit by bit: the scores' bit patterns,
    folded so that they order as the numbers do, against a threshold built
    from the top bit down in 32 counting passes (the largest value that at
    least ``k`` scores reach). Scores above it are in; of those AT it the
    first ``k - (scores above)`` by position."""
    if k >= scores.shape[-1]:
        return scores > -jnp.inf
    x = jnp.where(scores == 0, 0.0, scores)         # -0.0 ties with 0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    folded = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    order = jax.lax.bitcast_convert_type(folded, jnp.uint32) \
        ^ jnp.uint32(0x80000000)                    # unsigned, monotone in x

    def bit(i, thr):
        cand = thr | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        reach = jnp.sum(order >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, ties = order > thr, order == thr
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
    return (above | (ties & first)) & (scores > -jnp.inf)


def latent_scale(cfg: DecoderConfig) -> float:
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def latent_query(p: dict, q_nope: jax.Array, q_rope: jax.Array,
                 cfg: DecoderConfig) -> jax.Array:
    """The ABSORBED query [..., H, W], laid out like a cache row: the key
    expansion folded into the query (``q_lat = q_nope Wkb^T``), then
    ``q_rope``, then zeros. Its product with a cache row is the head's
    whole score ``q_nope . k_nope + q_rope . k_rope``."""
    wkb = p["wkvb"].astype(cfg.activation_dtype)[..., :cfg.qk_nope_dim]
    q_lat = jnp.einsum("...hk,rhk->...hr", q_nope, wkb)
    return _as_latent_row([q_lat, q_rope], cfg)


def latent_output(p: dict, o_row: jax.Array, cfg: DecoderConfig) -> jax.Array:
    """The attended row [..., H, W] (softmax-weighted sum of cache rows)
    expanded into per-head values [..., H, v_head_dim]: ``o_lat Wvb`` over
    the row's latent part."""
    wvb = p["wkvb"].astype(cfg.activation_dtype)[..., cfg.qk_nope_dim:]
    return jnp.einsum("...hr,rhk->...hk", o_row[..., :cfg.kv_lora_rank], wvb)


def latent_absorbed_attention(p: dict, q_nope, q_rope, rows, mask,
                              cfg: DecoderConfig) -> jax.Array:
    """Attention over cached rows WITHOUT expanding them per head, in plain
    XLA (the kernels of ops/paged_attention.py compute the same sums page by
    page). q_* [B,S,H,.]; rows [B,T,W]; ``mask`` broadcastable to
    [B,H,S,T], True = attend. Returns [B,S,H,v_head_dim]."""
    q = latent_query(p, q_nope, q_rope, cfg)                  # [B,S,H,W]
    scores = jnp.einsum("bshw,btw->bhst", q, rows,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask, scores * latent_scale(cfg), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    o_row = jnp.einsum("bhst,btw->bshw", probs, rows)
    return latent_output(p, o_row, cfg)


def latent_attention_block(p: dict, x: jax.Array, positions: jax.Array,
                           cfg: DecoderConfig, *,
                           kv_cache: Optional[dict] = None,
                           attn_impl: str = "xla"):
    """Latent attention in its EXPANDED form (training, a whole prompt):
    every token's keys and values per head, through the attention the other
    models use. The cache of a latent model is paged and attended ABSORBED
    (serve/paged.py: the chunk prefill and the decode step, which give the
    same numbers); there is no contiguous one. Returns (out [B,S,D],
    None)."""
    if kv_cache is not None:
        raise NotImplementedError(
            "a latent cache is a page pool (serve/paged.py); the contiguous "
            "cache holds K and V per head")
    dt = cfg.activation_dtype
    r = cfg.kv_lora_rank
    q_nope, q_rope, row, cq = latent_qkv(p, x, positions, cfg)
    if cfg.index_topk:
        # An indexer selects each query's keys: attention under a mask, in
        # the absorbed form (the selection composes with the causal mask).
        with jax.named_scope("dsa.index"):
            qi, ki, wi = index_qkw(p, x, cq, positions, cfg)
            scores = index_scores(qi, wi, ki, positions)
        with jax.named_scope("dsa.select"):
            mask = select_keys(scores, cfg.index_topk)
        with jax.named_scope("dsa.attend"):
            out = latent_absorbed_attention(p, q_nope, q_rope, row,
                                            mask[:, None], cfg)
        proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        return checkpoint_name(proj, "attn_out"), None
    kv = jnp.einsum("bsr,rhk->bshk", row[..., :r], p["wkvb"].astype(dt))
    k = jnp.concatenate(
        [kv[..., :cfg.qk_nope_dim],
         jnp.broadcast_to(row[:, :, None, r:r + cfg.qk_rope_dim],
                          q_rope.shape)], axis=-1)
    q = checkpoint_name(jnp.concatenate([q_nope, q_rope], -1), "q_rope")
    k = checkpoint_name(k, "k_rope")
    v = checkpoint_name(kv[..., cfg.qk_nope_dim:], "v_proj")
    impl = attn_impl if attn_impl in ("xla", "pallas") else "xla"
    if impl == "pallas" and v.shape[-1] != q.shape[-1]:
        impl = "xla"                # the flash kernel takes one head width
    out = multi_head_attention(q, k, v, causal=True, impl=impl)
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return checkpoint_name(proj, "attn_out"), None


# -- Gated short convolution ----------------------------------------------------

def init_conv(key, cfg: DecoderConfig):
    """A conv layer's operator: ``win`` projects to the three ``hidden``-wide
    parts B, C, u (in that order on its middle axis), ``taps`` [taps, D] is
    the depthwise filter (``taps[-1]`` multiplies the current position),
    ``wout`` projects back. No biases."""
    ki, kt, ko = jax.random.split(key, 3)
    d, wdt = cfg.hidden, cfg.weight_dtype
    params = {"win": _init(ki, (d, 3, d), wdt),
              "taps": _init(kt, (cfg.conv_taps, d), wdt,
                            scale=cfg.conv_taps ** -0.5),
              "wout": _init(ko, (d, d), wdt)}
    specs = {"win": ("embed", None, "mlp"), "taps": (None, "norm"),
             "wout": ("mlp", "embed")}
    return params, specs


def conv_block(p: dict, x: jax.Array, cfg: DecoderConfig,
               tail: Optional[jax.Array] = None):
    """The gated short convolution over ``x`` [B,S,D]: ``[B|C|u] = x Win``,
    ``z = B * u``, ``c_t = sum_j taps[j] * z_{t-(taps-1)+j}`` (causal,
    depthwise), ``out = (C * c) Wout``. ``tail`` [B,taps-1,D]: the ``z`` rows
    just before ``x`` (zeros at a sequence's start, and when None): the
    whole state a sequence carries. Returns (out [B,S,D], ``zs``
    [B,taps-1+S,D]: the tail followed by this call's ``z`` rows, so the
    state as it stands after position ``i`` of ``x`` is ``zs[:, i+1 :
    i+taps]``). Rows never mix: the convolution runs along a row's own
    time axis."""
    dt = cfg.activation_dtype
    b, s, d = x.shape
    k = cfg.conv_taps
    bcu = jnp.einsum("bsd,dgk->bsgk", x, p["win"].astype(dt))
    z = bcu[:, :, 0] * bcu[:, :, 2]
    if tail is None:
        tail = jnp.zeros((b, k - 1, d), dt)
    zs = jnp.concatenate([tail.astype(dt), z], axis=1)
    taps = p["taps"].astype(jnp.float32)
    c = sum(taps[j] * zs[:, j:j + s].astype(jnp.float32) for j in range(k))
    out = jnp.einsum("bsd,de->bse", bcu[:, :, 1] * c.astype(dt),
                     p["wout"].astype(dt))
    return checkpoint_name(out, "attn_out"), zs


# -- Gated delta-rule linear attention (KDA) -------------------------------------

# beside the squared norm a head's q and k are divided by
KDA_L2_EPS = 1e-6


def init_linear(key, cfg: DecoderConfig):
    """A linear layer's operator (``ops/kda.py`` has the equations): ``wq``
    / ``wk`` / ``wv`` [D, H, dk] and their depthwise filters ``conv_q`` /
    ``conv_k`` / ``conv_v`` [taps, H, dk] (``[-1]`` multiplies the current
    position); the decay a channel through ``wf1`` [D, r], ``wf2`` [r, H,
    dk], ``a_log`` [H] and ``dt_bias`` [H, dk]; beta through ``wb`` [D, H];
    the output gate through ``wg1`` / ``wg2``; the output norm's weight
    ``o_norm`` [dk] (one vector for all heads) and ``wo`` [H, dk, D]. No
    biases but ``dt_bias``. ``a_log`` and ``dt_bias`` start as the FLA
    initialisation has them: ``A`` uniform in [1, 16], the step's bias the
    inverse softplus of a step log-uniform in [1e-3, 1e-1]."""
    ks = iter(jax.random.split(key, 14))
    d, h, dk = cfg.hidden, cfg.linear_heads, cfg.linear_head_dim
    r, taps, wdt = cfg.linear_gate_rank, cfg.conv_taps, cfg.weight_dtype
    params, specs = {}, {}
    for n in ("q", "k", "v"):
        params["w" + n] = _init(next(ks), (d, h, dk), wdt)
        params["conv_" + n] = _init(next(ks), (taps, h, dk), wdt,
                                    scale=taps ** -0.5)
        specs["w" + n] = ("embed", "heads", "head_dim")
        specs["conv_" + n] = (None, "heads", "head_dim")
    step = jnp.exp(jax.random.uniform(
        next(ks), (h, dk), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    params.update({
        "wf1": _init(next(ks), (d, r), wdt),
        "wf2": _init(next(ks), (r, h, dk), wdt),
        "a_log": jnp.log(jax.random.uniform(
            next(ks), (h,), jnp.float32, 1.0, 16.0)).astype(wdt),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(wdt),
        "wb": _init(next(ks), (d, h), wdt),
        "wg1": _init(next(ks), (d, r), wdt),
        "wg2": _init(next(ks), (r, h, dk), wdt),
        "o_norm": jnp.ones((dk,), wdt),
        "wo": _init(next(ks), (h, dk, d), wdt, scale=(h * dk) ** -0.5),
    })
    specs.update({
        "wf1": ("embed", None), "wf2": (None, "heads", "head_dim"),
        "a_log": ("heads",), "dt_bias": ("heads", "head_dim"),
        "wb": ("embed", "heads"), "wg1": ("embed", None),
        "wg2": (None, "heads", "head_dim"), "o_norm": ("norm",),
        "wo": ("heads", "head_dim", "embed"),
    })
    return params, specs


def kda_conv_rows(cfg: DecoderConfig) -> int:
    """Rows of a sequence's convolution tails: ``conv_taps - 1`` inputs each
    of q, k and v, side by side ([rows, H * dk])."""
    return 3 * (cfg.conv_taps - 1)


def kda_inputs(p: dict, x: jax.Array, cfg: DecoderConfig,
               tails: Optional[jax.Array] = None, valid_len=None):
    """What the recurrence takes of ``x`` [B,S,D]: (q, k, v, g [B,S,H,dk]
    float32, beta [B,S,H] float32, the convolutions' tails after the last
    valid position [B, 3 (taps - 1), H dk]). q, k, v: the projection, a
    causal depthwise convolution of ``conv_taps`` taps over time (``tails``:
    the ``taps - 1`` projected rows before ``x``, zeros at a sequence's
    start and when None), SiLU; q and k L2-normalised a head, q scaled by
    ``dk ** -0.5``. ``g = -exp(a_log) softplus(x Wf1 Wf2 + dt_bias)`` a
    channel, ``beta = 2 sigmoid(x wb)``. A position ``>= valid_len`` ([B])
    is padding: its convolution input is zero, its ``beta`` and ``g`` are 0,
    so the state passes through it unchanged."""
    dt = cfg.activation_dtype
    b, s, _ = x.shape
    h, dk, taps = cfg.linear_heads, cfg.linear_head_dim, cfg.conv_taps
    valid = None
    if valid_len is not None:
        valid = jnp.arange(s)[None, :] < jnp.reshape(valid_len, (-1, 1))
    if tails is None:
        tails = jnp.zeros((b, kda_conv_rows(cfg), h * dk), dt)
    tails = tails.astype(dt).reshape(b, 3, taps - 1, h, dk)
    out, new_tails = [], []
    for i, n in enumerate(("q", "k", "v")):
        proj = jnp.einsum("bsd,dhk->bshk", x, p["w" + n].astype(dt))
        if valid is not None:
            proj = jnp.where(valid[..., None, None], proj, 0)
        xs = jnp.concatenate([tails[:, i], proj], axis=1)   # [B,taps-1+S,..]
        w = p["conv_" + n].astype(jnp.float32)
        c = sum(w[j] * xs[:, j:j + s].astype(jnp.float32)
                for j in range(taps))
        out.append(jax.nn.silu(c))
        if valid is None:
            new_tails.append(xs[:, s:])
        else:       # the rows before position ``valid_len``
            at = jnp.reshape(valid_len, (-1, 1)) + jnp.arange(taps - 1)
            new_tails.append(jnp.take_along_axis(
                xs, jnp.broadcast_to(at, (b, taps - 1))[..., None, None],
                axis=1))
    q, k, v = out

    def unit(a):
        return a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + KDA_L2_EPS)

    f = jnp.einsum("bsr,rhk->bshk",
                   jnp.einsum("bsd,dr->bsr", x, p["wf1"].astype(dt)),
                   p["wf2"].astype(dt)).astype(jnp.float32)
    g = -jnp.exp(p["a_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f + p["dt_bias"].astype(jnp.float32))
    beta = 2.0 * jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", x, p["wb"].astype(dt)).astype(jnp.float32))
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    tails = jnp.stack(new_tails, axis=1).reshape(b, -1, h * dk)
    return unit(q) * dk ** -0.5, unit(k), v, g, beta, tails


def kda_output(p: dict, x: jax.Array, o: jax.Array,
               cfg: DecoderConfig) -> jax.Array:
    """``(RMSNorm_head(o) * sigmoid(x Wg1 Wg2)) Wo``: o [B,S,H,dv] float32,
    ``x`` [B,S,D] the block's normed input. Returns [B,S,D]."""
    dt = cfg.activation_dtype
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["o_norm"].astype(jnp.float32)
    gate = jnp.einsum("bsr,rhk->bshk",
                      jnp.einsum("bsd,dr->bsr", x, p["wg1"].astype(dt)),
                      p["wg2"].astype(dt)).astype(jnp.float32)
    y = (o * jax.nn.sigmoid(gate)).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", y, p["wo"].astype(dt))


def kda_block(p: dict, x: jax.Array, cfg: DecoderConfig,
              state: Optional[tuple] = None, valid_len=None,
              impl: str = "xla"):
    """Gated delta-rule linear attention over ``x`` [B,S,D] from ``state``
    to a state: (the recurrent matrices [B,H,dk,dv] float32, the
    convolutions' tails [B, 3 (taps - 1), H dk]); zeros when None (a
    sequence's start). One token (``S == 1``) takes the recurrence itself,
    anything longer the chunked form (``ops/kda.py``; ``impl`` "xla" |
    "pallas"). Returns (out [B,S,D], the state after the last valid
    position). Rows never mix."""
    from kubeflow_tpu.ops import kda

    b, s, _ = x.shape
    h, dk = cfg.linear_heads, cfg.linear_head_dim
    mat, tails = state if state is not None else (None, None)
    if mat is None:
        mat = jnp.zeros((b, h, dk, dk), jnp.float32)
    q, k, v, g, beta, tails = kda_inputs(p, x, cfg, tails, valid_len)
    if s == 1:
        o, mat = kda.kda_step_xla(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0], mat)
        o = o[:, None]
    else:
        o, mat = kda.kda_chunk(q, k, v, g, beta, mat, impl=impl)
    return checkpoint_name(kda_output(p, x, o, cfg), "attn_out"), \
        (mat, tails)


# -- MLP -----------------------------------------------------------------------

def _mlp_tree(keys, lead: tuple, d: int, m: int, cfg: DecoderConfig,
              axes: tuple):
    """One MLP's matrices (``lead``: an expert axis in front, or none):
    "gate", "up" [d, m] and "down" [m, d]; "up" and "down" alone where the
    activation takes no gate (``cfg.mlp_matrices``). ``axes``: the logical
    names of (lead..., d, m)."""
    *la, ad, am = axes
    wdt = cfg.weight_dtype
    names = ("gate", "up", "down")      # a key each, gate or no gate
    params, specs = {}, {}
    for name, key in zip(names, keys):
        if name == "gate" and cfg.mlp_matrices == 2:
            continue
        fan, out = (m, d) if name == "down" else (d, m)
        params[name] = _init(key, (*lead, fan, out), wdt, scale=fan ** -0.5)
        specs[name] = (*la, am, ad) if name == "down" else (*la, ad, am)
    return params, specs


def init_mlp(key, cfg: DecoderConfig):
    return _mlp_tree(jax.random.split(key, 3), (), cfg.hidden, cfg.mlp_dim,
                     cfg, ("embed", "mlp"))


def _act(x: jax.Array, name: str) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def mlp_block(p: dict, x: jax.Array, cfg: DecoderConfig,
              tp_axis: Optional[str] = None, mesh=None) -> jax.Array:
    """``tp_axis``: gate/up hold this device's slice of the mlp dim and
    down's partial products psum over the axis (Megatron MLP split, manual
    form for inside shard_map)."""
    dt = cfg.activation_dtype
    gate_m, down_m = cfg.mlp_multipliers or (1.0, 1.0)
    if "gate" not in p:     # two matrices: the activation on ``up`` itself
        up = jnp.einsum("bsd,dm->bsm", x, p["up"].astype(dt))
        out = jnp.einsum("bsm,md->bsd", _act(up, cfg.hidden_act),
                         p["down"].astype(dt))
        if tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        return checkpoint_name(scaled(out, down_m), "mlp_out")
    gate_pre = scaled(jnp.einsum("bsd,dm->bsm", x, p["gate"].astype(dt)),
                      gate_m)
    up = jnp.einsum("bsd,dm->bsm", x, p["up"].astype(dt))
    h = None
    if fused_kernels_on(cfg, mesh) and cfg.hidden_act in ("silu", "gelu"):
        from kubeflow_tpu.ops import fused_norm

        if fused_norm.norm_supported(up.size // up.shape[-1], up.shape[-1]):
            # One VMEM pass for act(gate) * up; the custom VJP recomputes
            # the activation derivative from (gate, up) instead of stashing
            # act(gate)/sigmoid(gate) intermediates for the backward.
            h = fused_norm.swiglu_fused(gate_pre, up, act=cfg.hidden_act)
    if h is None:
        h = _act(gate_pre, cfg.hidden_act) * up
    out = jnp.einsum("bsm,md->bsd", h, p["down"].astype(dt))
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return checkpoint_name(scaled(out, down_m), "mlp_out")


# -- MoE -----------------------------------------------------------------------

def init_moe(key, cfg: DecoderConfig):
    kr, kg, ku, kd = jax.random.split(key, 4)
    d, m, e = cfg.hidden, cfg.expert_mlp_dim, cfg.router_width
    eh = cfg.experts_here       # the router scores all, the stack holds these
    # the routed experts' rows: the hidden's width, or the latent's
    experts_p, experts_s = _mlp_tree(
        (kg, ku, kd), (eh,), cfg.moe_latent_dim or d, m, cfg,
        ("expert", "embed", "expert_mlp"))
    params = {"router": _init(kr, (d, e), cfg.weight_dtype), **experts_p}
    specs = {"router": ("embed", None), **experts_s}
    if cfg.moe_latent_dim:
        kdn, kup = jax.random.split(jax.random.fold_in(key, 2))
        r = cfg.moe_latent_dim
        params["latent_down"] = _init(kdn, (d, r), cfg.weight_dtype)
        params["latent_up"] = _init(kup, (r, d), cfg.weight_dtype)
        specs["latent_down"] = ("embed", None)
        specs["latent_up"] = (None, "embed")
    if cfg.router_score != "softmax":
        # The correction bias moves the CHOICE of experts and never their
        # weights; it is balanced outside the loss, so it starts at zero.
        params["router_bias"] = jnp.zeros((e,), jnp.float32)
        specs["router_bias"] = (None,)
    if cfg.shared_experts:
        # Every token's experts: one MLP as wide as all of them together.
        ms = cfg.shared_experts * m
        params["shared"], specs["shared"] = _mlp_tree(
            jax.random.split(jax.random.fold_in(key, 1), 3), (), d, ms, cfg,
            ("embed", "mlp"))
    return params, specs


def route(p: dict, xf: jax.Array, cfg: DecoderConfig):
    """The router on tokens ``xf`` [..., D]: (logits [..., E] float32, the
    chosen experts [..., k], their weights [..., k] float32).

    "softmax" (Mixtral): the top-k logits, softmax over the chosen.
    "sigmoid": ``s = sigmoid(x Wr)`` computed in float32; the top-k of
    ``s + b`` (``b`` the correction bias) are CHOSEN, the weights are ``s``
    of the chosen WITHOUT ``b``, divided by their sum when
    ``router_norm_topk``, times ``router_scale``. "softmax_all": the same
    with ``s = softmax(x Wr)`` over ALL ``E`` outputs (``cfg.router_width``:
    the zero experts' too)."""
    k = cfg.experts_per_token
    if cfg.router_score == "softmax":
        logits = jnp.einsum(
            "...d,de->...e", xf,
            p["router"].astype(cfg.activation_dtype)).astype(jnp.float32)
        top_logits, idx = jax.lax.top_k(logits, k)
        return logits, idx, jax.nn.softmax(top_logits, axis=-1)
    if cfg.router_score not in ("sigmoid", "softmax_all"):
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    logits = jnp.einsum("...d,de->...e", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.router_norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    return logits, idx, w * cfg.router_scale


EXPERT_LEAVES = ("gate", "up", "down")


def split_expert_stack(layers: dict, cfg: DecoderConfig):
    """A stacked group of layers as (what a scan slices a layer at a time,
    the leaves a block takes WHOLE with its layer's index). The sorted
    expert path hands its weights to a grouped-matmul kernel, and a kernel's
    operand is a buffer of its own: a layer's [E, ...] slice of the stack
    would be COPIED out for every layer of every step (1.2 GB a layer at 64
    experts of 2048 x 1536). So those leaves stay whole, viewed
    [L*E, ...], and the block addresses its layer's experts as groups
    ``layer*E ..`` (``_moe_sorted``). (.., None) for every other model.
    Under a shortcut the expert layer is the group's "moe", beside every
    block's dense "mlp"."""
    if not (cfg.is_moe and cfg.moe_impl == "sorted"):
        return layers, None
    at = "moe" if cfg.moe_shortcut else "mlp"
    whole = {k: layers[at][k] for k in EXPERT_LEAVES if k in layers[at]}
    return {**layers, at: {k: v for k, v in layers[at].items()
                           if k not in whole}}, whole


def moe_block(p: dict, x: jax.Array, cfg: DecoderConfig,
              expert_axis: Optional[str] = None,
              seq_axis: Optional[str] = None,
              valid_len: Optional[jax.Array] = None,
              tp_axis: Optional[str] = None,
              expert_stack: Optional[tuple] = None,
              capacity_per_row: bool = False,
              rows_out: bool = False,
              tail: Optional[jax.Array] = None):
    """Top-k MoE (Mixtral semantics: softmax over the selected k logits).

    Dispatches on ``cfg.moe_impl``: "dispatch" (default) routes tokens into
    per-expert capacity buffers so only selected experts compute — k/E of
    the dense FLOPs; "dense" is the drop-free every-expert oracle the
    dispatch path is equivalence-tested against. Returns (out, aux_loss).

    ``valid_len`` (scalar or [B], traced OK): positions >= it are padding
    whose router choices must not claim expert capacity — the serving
    prefill pads prompts to a bucket, and without the mask hundreds of
    identical pad tokens would displace real tokens' choices under
    choice-major priority. Dense ignores it (every expert computes every
    token, pads can't affect real rows).

    ``tp_axis`` (inside shard_map — the PP×TP×MoE composition): weights
    additionally hold this device's slice of the expert-mlp dim (the
    Megatron split applied INSIDE each expert); gate/up produce the local
    m-slice and down's partial products join the expert partials in one
    psum over both axes.

    ``expert_stack`` (``split_expert_stack``): (the expert leaves of the
    whole stacked group, this layer's index in it), where ``p`` holds the
    rest; the sorted path alone takes it.

    ``capacity_per_row`` (static; the serving chunk of several prompts sets
    it): the dispatch path's capacity and claiming order are taken within
    each row of ``x`` and not over the whole block, so a row keeps and drops
    what it would alone (``_moe_dispatch``). The other paths have no
    capacity and ignore it.

    A layer that holds a SHARE of its experts (``cfg.experts_held``) is the
    sorted path's alone. ``rows_out`` (static): a third result, int32 [2]:
    the (token, choice) rows this call routed and those of them whose expert
    is held here (the serving programs sum them: serve/paged.py); [3] where
    the router has zero experts (``cfg.zero_experts``; the sorted and the
    dense path compute them): the rows that chose one, last.

    ``tail`` [N, D] (the dispatch path's alone; the decode rows that ride
    in a serving chunk program): tokens beside ``x``'s that are a dispatch
    group of their own and cannot drop (``_moe_dispatch``); ``out`` is then
    the pair (``x``'s [B,S,D], the tail's [N,D]). The paths without a
    capacity take such tokens as rows of ``x``."""
    if tail is not None and (cfg.moe_impl != "dispatch" or rows_out):
        raise NotImplementedError(
            f"a tail of tokens under moe_impl={cfg.moe_impl!r}: only a "
            "capacity sets tokens apart, hand them in as rows")
    if cfg.experts_held and cfg.moe_impl != "sorted":
        raise NotImplementedError(
            f"experts_held={cfg.experts_held} of {cfg.num_experts} under "
            f"moe_impl={cfg.moe_impl!r}: only the sorted path computes a "
            "share")
    if cfg.zero_experts and (cfg.moe_impl == "dispatch" or (
            rows_out and cfg.moe_impl != "sorted")):
        raise NotImplementedError(
            f"{cfg.zero_experts} zero experts under moe_impl="
            f"{cfg.moe_impl!r}: a capacity buffer has no row for an expert "
            "without weights, and only the sorted path counts their rows")
    rows = None
    latent = jnp.einsum(
        "bsd,dr->bsr", x, p["latent_down"].astype(cfg.activation_dtype)) \
        if cfg.moe_latent_dim else None
    if cfg.moe_impl == "dispatch":
        out, aux = _moe_dispatch(p, x, cfg, expert_axis=expert_axis,
                                 seq_axis=seq_axis, valid_len=valid_len,
                                 tp_axis=tp_axis,
                                 capacity_per_row=capacity_per_row, tail=tail)
    elif cfg.moe_impl == "sorted":
        if expert_axis is not None or tp_axis is not None:
            raise NotImplementedError(
                "moe_impl 'sorted' inside a pipeline stage's shard_map "
                "(expert or tensor parallel)")
        out, aux, rows = _moe_sorted(p, x, cfg, seq_axis=seq_axis,
                                     expert_stack=expert_stack,
                                     latent=latent)
    elif cfg.moe_impl == "dense":
        out, aux = _moe_dense(p, x, cfg, expert_axis=expert_axis,
                              seq_axis=seq_axis, tp_axis=tp_axis,
                              latent=latent)
    else:
        raise ValueError(f"unknown moe_impl {cfg.moe_impl!r}")
    if latent is not None:
        out = jnp.einsum("bsr,rd->bsd", out,
                         p["latent_up"].astype(cfg.activation_dtype))
    if cfg.shared_experts and tail is not None:
        # Every token, the tail's too: one pass over the shared weights.
        n = x.shape[0] * x.shape[1]
        shared = mlp_block(p["shared"], jnp.concatenate(
            [x.reshape(1, n, -1), tail[None]], axis=1), cfg,
            tp_axis=tp_axis)[0]
        out = (out[0] + shared[:n].reshape(x.shape), out[1] + shared[n:])
    elif cfg.shared_experts:
        # Every token, beside whatever it was routed to.
        out = out + mlp_block(p["shared"], x, cfg, tp_axis=tp_axis)
    if rows_out:
        if rows is None:        # every expert held, every routed row computed
            rows = (x.shape[0] * x.shape[1] * cfg.experts_per_token,) * 2
        return out, aux, jnp.stack([jnp.asarray(n, jnp.int32) for n in rows])
    return out, aux


def _moe_aux_loss(router_logits, onehot_sum, cfg: DecoderConfig,
                  seq_axis: Optional[str], valid=None):
    """Switch-style load-balancing loss: E * sum(frac_tokens * frac_probs).
    ``onehot_sum`` [B,S,E] = how many of the k choices hit each expert.
    ``valid`` [B,S] (optional) masks pad rows out of BOTH fractions and
    renormalizes by the valid-token count — pads route to whatever expert
    the null embedding prefers and would otherwise read as imbalance.
    A sigmoid router is balanced through its bias and has no such loss."""
    if cfg.router_score != "softmax":
        return jnp.float32(0)
    probs = jax.nn.softmax(router_logits, axis=-1)                   # [B,S,E]
    if valid is not None:
        # Sum masked numerators and the valid count SEPARATELY across the
        # sequence shards, then divide — pmean of per-shard ratios would
        # weight a shard with 4 valid tokens equally with one holding
        # 1024 (shard-local denominators differ once pads exist).
        m = valid[..., None].astype(probs.dtype)                     # [B,S,1]
        num_t = jnp.sum(onehot_sum * m, axis=(0, 1))                 # [E]
        num_p = jnp.sum(probs * m, axis=(0, 1))                      # [E]
        denom = jnp.sum(m)
        if seq_axis is not None:
            num_t = jax.lax.psum(num_t, seq_axis)
            num_p = jax.lax.psum(num_p, seq_axis)
            denom = jax.lax.psum(denom, seq_axis)
        denom = jnp.maximum(denom, 1.0)
        frac_tokens, frac_probs = num_t / denom, num_p / denom
    else:
        frac_tokens = jnp.mean(onehot_sum, axis=(0, 1))              # [E]
        frac_probs = jnp.mean(probs, axis=(0, 1))                    # [E]
        if seq_axis is not None:   # same denominator on every shard: exact
            frac_tokens = jax.lax.pmean(frac_tokens, seq_axis)
            frac_probs = jax.lax.pmean(frac_probs, seq_axis)
    return cfg.num_experts * jnp.sum(frac_tokens * frac_probs)


def moe_capacity(cfg: DecoderConfig, tokens: int) -> int:
    """Static per-expert buffer size for a ``tokens``-token dispatch:
    ceil(capacity_factor * k * T / E), rounded up to a multiple of 8
    (TPU sublane tiling), capped at k*T (beyond that nothing can drop)."""
    e, k = cfg.num_experts, cfg.experts_per_token
    c = -(-int(cfg.capacity_factor * k * tokens) // e)
    c = -(-max(c, 1) // 8) * 8
    return min(c, k * tokens)


def _moe_dispatch(p: dict, x: jax.Array, cfg: DecoderConfig,
                  expert_axis: Optional[str] = None,
                  seq_axis: Optional[str] = None,
                  valid_len: Optional[jax.Array] = None,
                  tp_axis: Optional[str] = None,
                  capacity_per_row: bool = False,
                  tail: Optional[jax.Array] = None):
    """Capacity-factor top-k dispatch (SURVEY.md §2.6 EP row: the TPU-native
    MoE data path; (U) training-operator-era Mixtral recipes route via NCCL
    all-to-all — here the routing is scatter/gather into static [E, C]
    buffers and GSPMD/psum provides the cross-device movement).

    - Priority is choice-major: every token's FIRST choice claims capacity
      before any token's second choice (a token never loses its primary
      expert to a neighbor's secondary).
    - A (token, choice) pair over capacity is DROPPED: its combine weight
      contributes nothing (remaining choices are NOT renormalized — Switch/
      Mixtral drop semantics); with capacity_factor >= E/... ample, the
      output matches the dense oracle exactly.
    - Static shapes throughout: C is a compile-time function of T, so one
      trace serves all traffic; the scatter/gather are O(k·T·D) data
      movement instead of the dense path's E/k compute overhead.
    - Capacity is per DISPATCH BATCH: under pipeline microbatching each
      microbatch competes for its own C slots, so drop patterns differ
      from a full-batch run (the standard GPipe×MoE trade) — equivalence
      across schedules holds exactly only when capacity is ample.
    - ``capacity_per_row`` (serving: the chunks of several prompts in one
      program) makes every ROW of ``x`` a dispatch batch of its own: an
      expert holds ``moe_capacity(cfg, S)`` slots for each row, the
      choice-major order runs within a row, and the buffers are
      ``[E, B*c, D]``, so a row keeps and drops exactly the (token, choice)
      pairs it keeps and drops alone while each expert's weights are still
      read once for all rows. At one row the two are the same computation.
    - ``tail`` [N, D] (serving: the decode rows that ride in a chunk
      program) are ``N`` more tokens, a dispatch group of their own whose
      capacity is ``N``: an expert is chosen by a token at most once, so
      none of them can drop, and no token of ``x`` displaces one. Their
      ``N`` slots stand behind ``x``'s in each expert's buffer
      (``[E, g*c + N, D]``), so an expert's weights are still read once.
      ``out`` is then the pair (``x``'s [B,S,D], the tail's [N,D]); the
      balance loss stays ``x``'s.

    With ``expert_axis`` (inside shard_map): weights hold the local expert
    slice; positions are computed on the replicated router output (identical
    on every shard), each shard scatters/computes only rows routed to its
    local experts, and the combined partial psums over the axis.
    """
    dt = cfg.activation_dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    # ``g`` dispatch groups of ``tg`` tokens, each with its own capacity.
    g = b if capacity_per_row else 1
    tg = t // g
    xf = x.reshape(t, d)
    n = 0 if tail is None else tail.shape[0]
    if n:
        xf = jnp.concatenate([xf, tail.astype(xf.dtype)])            # [T+N,D]
    router_logits, topk_idx, topk_w = route(p, xf, cfg)              # [T+N,k]
    if n:
        tail_idx, tail_w = topk_idx[t:], topk_w[t:]
        router_logits, topk_idx, topk_w = (
            router_logits[:t], topk_idx[:t], topk_w[:t])

    c = moe_capacity(cfg, tg)
    # Choice-major flattening within a group: row r of group i is
    # (choice r // tg) of the group's token (r % tg).
    def choice_major(a):                     # [T, k] -> [g, k*tg]
        return jnp.swapaxes(a.reshape(g, tg, k), 1, 2).reshape(g, k * tg)

    flat_e = choice_major(topk_idx)                                  # [g,k*tg]
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)                  # [g,k*tg,E]
    valid_flat, valid_bs = None, None
    if valid_len is not None:
        # Padding rows claim no capacity (zeroed before the cumsum), are
        # dropped outright (below), and are masked out of both sides of
        # the balance loss — which otherwise reads a bucket of identical
        # pads as a catastrophically unbalanced router.
        vl = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(valid_len)), (b,))
        valid_bs = jnp.arange(s)[None, :] < vl[:, None]              # [B,S]
        valid_flat = jnp.tile(valid_bs.reshape(g, tg), (1, k))
        oh = oh * valid_flat[..., None].astype(oh.dtype)
    pos = jnp.cumsum(oh, axis=1) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_e[..., None], 2)[..., 0]
    keep = pos_in_e < c
    if valid_flat is not None:
        keep = keep & valid_flat

    e_local, offset = e, 0
    if expert_axis is not None:
        e_local = p["up"].shape[0]
        offset = jax.lax.axis_index(expert_axis) * e_local
        keep = keep & (flat_e >= offset) & (flat_e < offset + e_local)
    # An expert's buffer: group 0's c slots, then group 1's, ..., then the
    # tail's N.
    per_e = g * c + n
    slot = jnp.arange(g, dtype=jnp.int32)[:, None] * c + pos_in_e
    rows = jnp.where(keep, (flat_e - offset) * per_e + slot,
                     e_local * per_e).reshape(-1)                    # [kT]
    tok_of = (jnp.arange(g, dtype=jnp.int32)[:, None] * tg + jnp.tile(
        jnp.arange(tg, dtype=jnp.int32), k)[None, :]).reshape(-1)    # [kT]
    if n:
        # Choice-major like a group; the place among the expert's tail slots
        # is the count of earlier pairs that chose it, always below N.
        tail_e = jnp.swapaxes(tail_idx, 0, 1).reshape(-1)            # [kN]
        tail_oh = jax.nn.one_hot(tail_e, e, dtype=jnp.int32)
        place = jnp.take_along_axis(jnp.cumsum(tail_oh, axis=0) - 1,
                                    tail_e[:, None], 1)[:, 0]
        here = (tail_e >= offset) & (tail_e < offset + e_local)
        rows = jnp.concatenate([rows, jnp.where(
            here, (tail_e - offset) * per_e + g * c + place,
            e_local * per_e)])                                       # [kT+kN]
        tok_of = jnp.concatenate([tok_of, t + jnp.tile(
            jnp.arange(n, dtype=jnp.int32), k)])
    # TPU lowers row-granular scatters poorly (measured 2.9× slower than
    # dense!): invert the slot permutation with a SCALAR scatter (cheap),
    # then fill the buffers with a row GATHER — empty slots read OOB and
    # fill with zeros.
    row_of_slot = jnp.full((e_local * per_e,), t + n, jnp.int32).at[
        rows].set(tok_of, mode="drop")
    buf = jnp.take(xf, row_of_slot, axis=0, mode="fill",
                   fill_value=0).reshape(e_local, per_e, d)

    if "gate" in p:
        gate = _act(jnp.einsum("ecd,edm->ecm", buf, p["gate"].astype(dt)),
                    cfg.hidden_act)
        inner = gate * jnp.einsum("ecd,edm->ecm", buf, p["up"].astype(dt))
    else:                       # two matrices: the activation on ``up``
        inner = _act(jnp.einsum("ecd,edm->ecm", buf, p["up"].astype(dt)),
                     cfg.hidden_act)
    y = jnp.einsum("ecm,emd->ecd", inner,
                   p["down"].astype(dt)).reshape(e_local * per_e, d)

    back = jnp.take(y, rows, axis=0, mode="fill", fill_value=0)      # [kT,D]
    w_flat = choice_major(topk_w).reshape(-1, 1).astype(dt)
    if n:
        w_tail = jnp.swapaxes(tail_w, 0, 1).reshape(-1, 1).astype(dt)
        out_tail = (back[k * t:] * w_tail).reshape(k, n, d).sum(0)
        back = back[:k * t]
    out = (back * w_flat).reshape(g, k, tg, d).sum(1).reshape(b, s, d)
    # One combined reduction: expert partials (each shard computed its
    # local experts) and Megatron partials (down contracted a local
    # m-slice) sum over both axes at once.
    axes = tuple(a for a in (expert_axis, tp_axis) if a is not None)
    if n:
        out = (out, out_tail)
    if axes:
        out = jax.lax.psum(out, axes)

    aux = _moe_aux_loss(
        router_logits.reshape(b, s, e),
        oh.astype(jnp.float32).reshape(g, k, tg, e).sum(1).reshape(b, s, e),
        cfg, seq_axis, valid=valid_bs)
    return checkpoint_name(out, "mlp_out"), aux


# Rows a tile of the grouped-matmul kernel holds. A group of fewer rows still
# costs a whole tile of matrix work, which is cheap beside reading its
# weights once: the sorted expert layer is bound by the experts' bytes.
GROUPED_TILE_ROWS = 128


# What a call of the grouped-matmul kernel may hold in the chip's fast
# memory (a v5e's 16 MiB a kernel), the rows from which on the chip's
# compiler keeps a product's output there too, and what that costs the call:
# at (128, 6144, 512) the tiles alone are 15.5 MiB, and from 10240 rows on
# (9216 still pass) the compile of the gate and up products is refused 0.75
# MiB over the limit (compiled for a described v5e, PR 57: ``T x k`` rows,
# whatever the model).
GROUPED_VMEM_BYTES = 16 * 2 ** 20
GROUPED_ROWS_OUT_ELSEWHERE = 9216
GROUPED_OUT_HELD_BYTES = 5 * 2 ** 18


def _grouped_tile_columns(n: int, k: int, m: int) -> int:
    """Columns a tile of the grouped matmul holds of an ``n``-wide output:
    512 where that divides ``n``; else the widest whole number of 128-value
    lanes up to 512 that does (256 at experts of 1280: a [4096, 1280] tile
    of weights, twice for the pipeline, is 20 MB of the kernel's 16); ``n``
    whole where no such width divides it (a tiny preset's). A call of more
    than ``GROUPED_ROWS_OUT_ELSEWHERE`` rows (``m``; ``k`` the products'
    inner width) takes the widest of them whose tiles leave the output its
    room: a row tile [128, k], a weight tile [k, t] and an output tile [128,
    t], each twice, and the float32 accumulator."""
    widths = [t for t in (512, 384, 256, 128) if n % t == 0]
    if m > GROUPED_ROWS_OUT_ELSEWHERE:
        rows = GROUPED_TILE_ROWS
        widths = [t for t in widths
                  if 4 * (rows * k + k * t + rows * t) + 4 * rows * t
                  <= GROUPED_VMEM_BYTES - GROUPED_OUT_HELD_BYTES] \
            or widths[-1:]
    return widths[0] if widths else n


def grouped_matmul(rows: jax.Array, w: jax.Array, sizes: jax.Array,
                   cfg: DecoderConfig) -> jax.Array:
    """``rows`` [M, K] sorted by group times ``w`` [G, K, N]: the rows of
    group ``g`` (``sizes[g]`` of them, in order) against ``w[g]``; empty
    groups cost nothing. With the fused kernels on and a tile of rows or
    more (a chunk's rows, a decode step's where they reach a tile; fewer are
    XLA's), the Pallas grouped matmul with
    tiles of this layer's own widths (each expert's weights are read about
    once, which XLA's own ``ragged_dot`` kernel at these shapes is 2.5x
    from: PERF.md, PR 28); ``jax.lax.ragged_dot`` otherwise."""
    m, (k, n) = rows.shape[0], w.shape[1:]
    if fused_kernels_on(cfg) and m >= GROUPED_TILE_ROWS:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        from kubeflow_tpu.ops import auto_interpret

        # Rows beyond whole tiles (a decode step's 48 streams x 12 choices
        # are 576) ride in a last tile of their own: rows of no group, which
        # the kernel walks past, cut off again behind it.
        pad = -m % GROUPED_TILE_ROWS
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        out = megablox.gmm(
            rows, w, sizes, rows.dtype,
            (GROUPED_TILE_ROWS, k, _grouped_tile_columns(n, k, m + pad)),
            None, None, False, auto_interpret())
        return out[:m] if pad else out
    return jax.lax.ragged_dot(rows, w, sizes)


def _moe_sorted(p: dict, x: jax.Array, cfg: DecoderConfig,
                seq_axis: Optional[str] = None,
                expert_stack: Optional[tuple] = None,
                latent: Optional[jax.Array] = None):
    """Drop-free sparse experts: the k rows of every token are sorted by
    expert and each projection is ONE grouped matmul over the sorted rows
    (``grouped_matmul``: group ``e`` holds the rows routed to expert ``e``,
    however many), so an expert's weights are read once and only the
    chosen experts compute. No capacity exists, so nothing can overflow and
    co-batched tokens cannot change each other's result. Pad rows of a
    serving chunk are routed and computed like any other; they displace
    nothing. With ``expert_stack`` the weights are the whole group's,
    viewed [L*E, ...] (a bitcast), and this layer's experts are the groups
    from ``layer*E`` on; every other group is empty.

    A layer that holds a share (``cfg.experts_held`` experts from
    ``cfg.expert_offset`` on: one chip of an expert-parallel group) routes
    over ALL ``num_experts`` and computes its own experts' part: a row whose
    expert lies elsewhere sorts behind every held group and belongs to none,
    so it costs no matrix work (the grouped matmul walks the groups' rows
    only) and adds nothing; however uneven the routing, every row of a held
    expert is computed.

    A row that chose a ZERO expert (``cfg.zero_experts``: the router's
    outputs from ``num_experts`` on, the identity) goes behind every held
    group the same way and costs no matrix work either; what it adds is its
    token's own input: ``(the sum of a token's zero choices' weights) x h``,
    one elementwise product a token. So the matrix work a token costs runs
    from none of its ``k`` choices to all of them.

    ``latent`` [B,S,R] (``moe_block``: experts behind a latent projection):
    the router reads ``x``, the experts' rows are ``latent``'s, and ``out``
    comes back ``R`` wide. Returns (out, aux, the rows routed, the rows held
    and, with zero experts, the rows that chose one)."""
    dt = cfg.activation_dtype
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    eh = cfg.experts_here
    t = b * s
    xf = x.reshape(t, d)
    router_logits, topk_idx, topk_w = route(p, xf, cfg)              # [T,k]
    held, n_held, zero = None, t * k, None
    flat_e = topk_idx.reshape(-1)                                    # [Tk]
    if eh != e or cfg.zero_experts:
        local = topk_idx - cfg.expert_offset
        held = (local >= 0) & (local < eh)                           # [T,k]
        # a row held elsewhere, or by nobody: group ``eh``, behind every
        # held group
        flat_e = jnp.where(held, local, eh).reshape(-1)
        n_held = jnp.sum(held, dtype=jnp.int32)
    if cfg.zero_experts:
        zero = topk_idx >= e
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1) if held is None \
        else jnp.zeros((eh + 1,), jnp.int32).at[flat_e].add(1)[:eh]
    w = p
    if expert_stack is not None:
        stack, layer = expert_stack
        w = {n: a.reshape(-1, *a.shape[2:]) for n, a in stack.items()}
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w["up"].shape[0],), jnp.int32), sizes,
            (layer * eh,))
    if latent is not None:      # the experts' rows, and their width
        xe, d = latent.reshape(t, -1), latent.shape[-1]
    else:
        xe = xf
    rows = jnp.take(xe, order // k, axis=0)                          # [Tk,D]
    if "gate" in w:
        gate = _act(grouped_matmul(rows, w["gate"].astype(dt), sizes, cfg),
                    cfg.hidden_act)
        inner = gate * grouped_matmul(rows, w["up"].astype(dt), sizes, cfg)
    else:                       # two matrices: the activation on ``up``
        inner = _act(grouped_matmul(rows, w["up"].astype(dt), sizes, cfg),
                     cfg.hidden_act)
    y = grouped_matmul(inner, w["down"].astype(dt), sizes, cfg)      # [Tk,D]
    # Back to token order: row r of the sorted rows is (token, choice)
    # ``order[r]``; a scalar scatter inverts the permutation.
    inv = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    back = jnp.take(y, inv, axis=0).reshape(t, k, d)
    if held is not None:
        # Rows behind the groups were never computed: whatever lies there
        # (a kernel leaves it unwritten) must not reach the sum.
        back = jnp.where(held[..., None], back, 0)
    out = jnp.einsum("tkd,tk->td", back, topk_w.astype(dt))
    rows = (t * k, n_held)
    if zero is not None:
        out = out + jnp.sum(jnp.where(zero, topk_w, 0), axis=-1,
                            keepdims=True).astype(dt) * xf
        rows += (jnp.sum(zero, dtype=jnp.int32),)
    out = out.reshape(b, s, d)
    width = cfg.router_width
    aux = _moe_aux_loss(
        router_logits.reshape(b, s, width),
        jax.nn.one_hot(topk_idx, width, dtype=jnp.float32).sum(-2).reshape(
            b, s, width), cfg, seq_axis)
    return checkpoint_name(out, "mlp_out"), aux, rows


def _moe_dense(p: dict, x: jax.Array, cfg: DecoderConfig,
               expert_axis: Optional[str] = None,
               seq_axis: Optional[str] = None,
               tp_axis: Optional[str] = None,
               latent: Optional[jax.Array] = None):
    """Einsum-dense formulation: every expert computes every token and a
    one-hot combine weights the results. FLOP-inefficient (E/k overcompute)
    but fully static-shaped and drop-free — under GSPMD the ``expert``
    sharding of the weight specs turns the expert einsums into
    expert-parallel partials XLA combines; serves as the dispatch path's
    correctness oracle.

    With ``expert_axis`` (inside shard_map — the pipeline×EP composition),
    ``p["gate"]/["up"]/["down"]`` hold this device's expert slice: the block
    computes local experts only, slices the combine weights at the shard
    offset, and psums the combined output over the axis. The router is
    replicated, so top-k runs on full logits. ``seq_axis`` (sequence-sharded
    activations, PP×SP): the load-balancing fractions pmean over the axis so
    the aux loss sees full-sequence statistics. ``latent`` [B,S,R]: what the
    experts read in ``x``'s place (``moe_block``); ``out`` is then ``R``
    wide."""
    dt = cfg.activation_dtype
    e, k = cfg.num_experts, cfg.experts_per_token
    router_logits, topk_idx, topk_w = route(p, x, cfg)               # [B,S,k]
    xe = x if latent is None else latent
    onehot = jax.nn.one_hot(topk_idx, cfg.router_width,
                            dtype=jnp.float32)                       # [B,S,k,E]
    combine = jnp.einsum("bske,bsk->bse", onehot, topk_w)            # [B,S,E]
    if cfg.zero_experts:
        # the zero experts (the outputs behind the ``e`` with weights) hand
        # a token its own input back
        combine, zero = combine[..., :e], jnp.sum(combine[..., e:], axis=-1)

    if expert_axis is not None:
        e_local = p["up"].shape[0]
        offset = jax.lax.axis_index(expert_axis) * e_local
        combine = jax.lax.dynamic_slice_in_dim(combine, offset, e_local,
                                               axis=-1)
    if "gate" in p:
        gate = _act(jnp.einsum("bsd,edm->ebsm", xe, p["gate"].astype(dt)),
                    cfg.hidden_act)
        inner = gate * jnp.einsum("bsd,edm->ebsm", xe, p["up"].astype(dt))
    else:
        inner = _act(jnp.einsum("bsd,edm->ebsm", xe, p["up"].astype(dt)),
                     cfg.hidden_act)
    expert_out = jnp.einsum("ebsm,emd->ebsd", inner, p["down"].astype(dt))
    out = jnp.einsum("ebsd,bse->bsd", expert_out, combine.astype(dt))
    axes = tuple(a for a in (expert_axis, tp_axis) if a is not None)
    if axes:
        out = jax.lax.psum(out, axes)
    if cfg.zero_experts:
        out = out + zero[..., None].astype(dt) * x

    aux = _moe_aux_loss(router_logits, onehot.sum(axis=2), cfg, seq_axis)
    return out, aux


# -- Embedding -----------------------------------------------------------------

def init_embedding(key, cfg: DecoderConfig):
    tok = _init(key, (cfg.vocab_size, cfg.hidden), cfg.weight_dtype, scale=1.0)
    return tok, ("vocab", "embed_table")
