"""Decoder model configuration + the preset zoo.

Presets cover the BASELINE.json configs (Llama-3-8B, Gemma-2B, Mixtral-8x7B)
and the models the benchmark serves at their published widths (GLM-4.7-Flash,
LFM2-24B-A2B, K-EXAONE-236B-A23B, Solar-Open2-250B,
Phi-4-mini-flash-reasoning, Falcon-H1-34B-Instruct, GLM-5,
LongCat-Flash-Omni's language model, Nemotron-3-Super-120B-A12B), plus tiny
variants of each structure for tests.
Architecture facts are from the public model cards and ``config.json``
files.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


# The kinds a state-space stack brings: "ssm" keeps a state a sequence; "gmu"
# and "cross" keep none and read what another layer computed (value: the
# kind whose last layer in front of the tail they read).
STATELESS_KINDS = {"gmu": "ssm", "cross": "attention"}
SSM_KINDS = ("ssm", *STATELESS_KINDS)
# A layer of the key's kind ALSO keeps the cache planes of these kinds: a
# "parallel" layer its K and V rows a token, as an attention layer does, AND
# the state a sequence that an "ssd" layer keeps (it runs both operators).
ALSO_HOLDS = {"parallel": ("attention", "ssd")}


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """Hashable (jit-static) decoder architecture description."""

    vocab_size: int = 32000
    hidden: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8            # < n_heads => GQA
    head_dim: int = 64
    mlp_dim: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    # silu => SwiGLU; gelu => GeGLU (gemma); relu2 => a plain MLP of TWO
    # matrices, ``relu(x W_up) ** 2 W_down`` (no gate matrix anywhere: the
    # dense MLP's, the experts' and the shared expert's trees hold "up" and
    # "down" alone)
    hidden_act: str = "silu"
    tie_embeddings: bool = False
    norm_plus_one: bool = False    # gemma-style (1 + w) RMSNorm weight
    embed_scale: bool = False      # gemma-style sqrt(hidden) embedding scale
    logits_softcap: Optional[float] = None   # gemma-2 style tanh softcap
    # MoE (0 => dense)
    num_experts: int = 0
    experts_per_token: int = 2
    # "dispatch": capacity-factor top-k routing — only selected experts
    # compute (k/E of dense FLOPs; tokens over a full expert drop).
    # "dense": every expert computes every token, one-hot combine — the
    # FLOP-inefficient but drop-free oracle the dispatch path tests against.
    # "sorted": rows sorted by expert into one grouped matmul per
    # projection — k/E of the dense FLOPs and no capacity, so no token is
    # ever dropped (a model published without a capacity).
    moe_impl: str = "dispatch"
    # Expert width where it differs from the dense layers' ``mlp_dim``
    # (0 = the same), experts every token passes through beside the routed
    # ones, and how many leading layers keep a plain MLP of ``mlp_dim``.
    moe_mlp_dim: int = 0
    shared_experts: int = 0
    leading_dense_layers: int = 0
    # Experts behind a latent projection (0 = none): the ROUTED experts read
    # and write rows of this width, ``x W_dn`` once a token in front of the
    # sort and ``(sum of the chosen experts' outputs) W_up`` once behind the
    # combine, with nothing between a projection and an expert; the router
    # and the shared expert read the block's own ``hidden``-wide input
    # (``layers.moe_block``).
    moe_latent_dim: int = 0
    # The router's score: "softmax" (Mixtral: top-k of the logits, softmax
    # over the chosen), "sigmoid" (scores sigmoid(logits) in float32,
    # CHOSEN by score plus a learned bias, WEIGHTED by the score alone,
    # normalised over the chosen when ``router_norm_topk``, then scaled) or
    # "softmax_all" (the same with scores softmax(logits) over EVERY output
    # of the router, the zero experts' among them).
    router_score: str = "softmax"
    router_norm_topk: bool = True
    router_scale: float = 1.0
    router_norm_eps: float = 1e-20   # beside the sum the weights divide by
    # One chip's share of an expert-parallel group: the layer HOLDS
    # ``experts_held`` of the ``num_experts`` the router scores (0 = all of
    # them), the experts ``expert_offset ..``. The router's matrix and bias
    # keep ``num_experts`` outputs, the top-k and its normalisation run over
    # all of them, and only the rows routed to a held expert are computed
    # (``layers._moe_sorted``); what the other chips' experts would add is
    # left out, the shared expert runs whole.
    experts_held: int = 0
    expert_offset: int = 0
    # Experts that compute nothing: the router's outputs ``num_experts ..
    # num_experts + zero_experts - 1`` are the IDENTITY, so a token that
    # chooses one gets its own normed input back, times the choice's weight
    # (``layers._moe_sorted``: no weights, no matrix work, and on a chip that
    # holds a share no exchange: every chip computes them where the token
    # is). ``num_experts`` counts the experts that have weights.
    zero_experts: int = 0
    # The expert layer on a shortcut: EVERY block keeps a dense MLP of
    # ``mlp_dim``, and the blocks go in pairs: the first of a pair also
    # starts the expert layer on its normed input ``h`` (the input of its
    # dense MLP), and the result joins the stream at the END of the second,
    # behind that block's attention and dense MLP. ``n_layers`` counts
    # blocks (two a published layer), an expert layer a pair.
    moe_shortcut: bool = False
    # Latent attention (MLA; kv_lora_rank > 0): queries through a
    # ``q_lora_rank`` bottleneck, keys and values expanded per head from one
    # ``kv_lora_rank`` latent row a token, beside ``qk_rope_dim`` rotary
    # values shared by all heads. The cache holds the latent and the rotary
    # row; ``head_dim`` is not read.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # ``latent_rank_scale``: two constant factors on what leaves the
    # bottlenecks, ``sqrt(hidden / q_lora_rank)`` on the per-head queries
    # and ``sqrt(hidden / kv_lora_rank)`` on the normed latent before its
    # expansion into keys and values (``layers.latent_qkv``; the rotary key
    # is not scaled).
    latent_rank_scale: bool = False
    # A learned indexer that SELECTS the keys latent attention reads
    # (DeepSeek sparse attention; ``index_topk`` 0 = none, every key is
    # read): ``index_heads`` query heads of ``index_head_dim`` values from
    # the latent query, ONE key of that width a token (a LayerNorm; RoPE on
    # its first ``qk_rope_dim`` values), a weight a head from the block's
    # input; query ``t`` attends to the ``min(index_topk, t + 1)`` positions
    # of largest ``sum_j w_j ReLU(q_j . k_s)``, a tie to the lower position
    # (layers.index_scores, layers.select_keys). The cache holds the
    # indexer's key a token in a plane of its own beside the latent row.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The stack's pattern: the kind of layer ``i`` is ``layer_kinds[i %
    # len(layer_kinds)]``, "attention", "window" or "conv" (() = every layer
    # attention). A "window" layer is attention whose query ``i`` sees the
    # keys ``i - attn_window < j <= i`` (itself among ``attn_window``), with
    # parameters and cache planes of its own kind; ``rope_window_only``: only
    # the window layers rotate q and k (the global layers carry no position).
    # A "conv" layer's operator is the gated short convolution
    # (layers.conv_block): a causal depthwise convolution of ``conv_taps``
    # taps over time between two elementwise gates, whose state is the last
    # ``conv_taps - 1`` gated rows of a sequence. ``qk_norm``: an RMSNorm
    # over each head's values of q and k, before RoPE.
    # A "linear" layer's operator is gated delta-rule linear attention
    # (layers.kda_block): ``linear_heads`` heads of ``linear_head_dim`` keys
    # and as many values, q / k / v each through a causal depthwise
    # convolution of ``conv_taps`` taps, a decay a CHANNEL and an output gate
    # through two projections of rank ``linear_gate_rank``; its state is a
    # ``[linear_head_dim, linear_head_dim]`` float32 matrix a head and the
    # convolutions' tails, one entry a SEQUENCE (serve/paged.py).
    # ``attn_output_gate``: an attention layer's output is multiplied by
    # ``sigmoid(x Wgate)``, a value a head channel, before ``wo``.
    # An "ssm" layer's operator is the Mamba-1 selective scan
    # (layers.ssm_block, ops/ssm.py): ``ssm_inner`` channels behind a causal
    # depthwise convolution of ``conv_taps`` taps, each with ``ssm_state``
    # states, the step through a projection of rank ``ssm_dt_rank``; its
    # state is a ``[ssm_state, ssm_inner]`` float32 matrix and the
    # convolution's tail, one entry a SEQUENCE. A "gmu" layer (gated memory
    # unit) multiplies ``SiLU(x W1)`` with the scan output of the LAST ssm
    # layer in front of it at the same position and keeps nothing; a "cross"
    # layer is attention with queries only, over the K and V of the last
    # "attention" layer in front of it, and keeps nothing either. Both kinds
    # stand behind every layer that keeps state (``stateless_tail``).
    # ``diff_attention``: every attention layer (window, global, cross) is
    # differential attention (layers.diff_qkv): adjacent heads pair up and
    # the second's softmax is subtracted from the first's, scaled by a
    # learned lambda. ``attn_bias``: biases on its projections.
    # ``use_rope`` False: no layer rotates q and k. ``norm_kind``: "rms", or
    # "layer" (LayerNorm with weight and bias) for every norm of the stack.
    # A "parallel" layer runs TWO operators on its one normed input and adds
    # both to the residual (layers.ssd_block, ops/ssd.py): attention as an
    # "attention" layer has it, and a Mamba-2 (SSD) mixer of ``ssd_heads``
    # heads of ``ssd_head_dim`` values, each with a ``[ssd_state,
    # ssd_head_dim]`` float32 state and ONE scalar decay a token, ``B`` and
    # ``C`` shared by the heads of each of ``ssd_groups`` groups, behind a
    # causal depthwise convolution of ``conv_taps`` taps over ``[x | B |
    # C]``; the chunked form walks blocks of ``ssd_chunk`` positions. Its
    # layer keeps K and V rows a token AND the state and the convolution's
    # tail, one entry a SEQUENCE. A stack of them holds no other kind.
    # An "ssd" layer's ONLY operator is that mixer (no K and V rows: its
    # state and tail are all it keeps), and it stands beside attention
    # layers in one stack.
    # ``ffn_free``: the places in ONE PERIOD of ``layer_kinds`` whose block
    # is its operator alone, ``x + F(N(x))``: no second norm, no feed-forward
    # part (a published stack that lists mixers and feed-forward parts as
    # layers of their own, read as blocks: a mixer followed by another
    # mixer).
    layer_kinds: tuple = ()
    ffn_free: tuple = ()
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_state: int = 0
    ssd_groups: int = 1
    ssd_chunk: int = 128
    # Fixed multipliers of a model parameterised for width transfer, each
    # applied where the forward pass has it (() and 1.0: none).
    # ``embed_multiplier`` a token's embedding, ``head_multiplier`` the
    # logits; ``attn_multipliers`` (in, out, key): the attention branch's
    # input, its output, its keys; ``ssd_multipliers`` (in, out, z, x, B, C,
    # dt): the SSD branch's input, its output and the five column blocks of
    # its in-projection; ``mlp_multipliers`` (gate, down): the gate inside
    # its activation, the feed-forward's output.
    embed_multiplier: float = 1.0
    head_multiplier: float = 1.0
    attn_multipliers: tuple = ()
    ssd_multipliers: tuple = ()
    mlp_multipliers: tuple = ()
    ssm_state: int = 0
    ssm_inner: int = 0
    ssm_dt_rank: int = 0
    diff_attention: bool = False
    attn_bias: bool = False
    use_rope: bool = True
    norm_kind: str = "rms"
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_gate_rank: int = 0
    attn_output_gate: bool = False
    attn_window: int = 0
    rope_window_only: bool = False
    # Serving: the pages a sequence keeps in a window layer of the page pool,
    # a ring over its first pages (serve/paged.py::ring_table). The engine
    # sets it from its chunk and page sizes (``engine.serving_configs``,
    # ``paged.ring_pages``) and nobody else does. It rides here because a
    # program's config is all a caller of the paged programs hands them of
    # the engine (the benchmark's correctness seam passes
    # ``engine._cfg_decode`` and one table row: no slot count to derive it
    # from). 0: a window layer keeps every page, like a global one
    # (``ring_table`` over the whole row); an engine's pool refuses it.
    window_ring_pages: int = 0
    conv_taps: int = 3
    qk_norm: bool = False
    # One ``n_kv_heads * head_dim`` row a token for K and one for V in the
    # page pool, not ``[n_kv_heads, head_dim]``: for heads narrower than the
    # chip's 128-value lanes, whose per-head planes the compiler pads to
    # twice their size and copies whole around every decode write.
    kv_heads_packed: bool = False
    # Per-expert buffer size = capacity_factor * k * T / E (rounded up to a
    # multiple of 8 for TPU tiling). 1.0 = perfectly balanced load fits.
    capacity_factor: float = 1.25
    # compile-time policy
    scan_layers: bool = True
    remat_policy: str = "nothing_saveable"   # none | nothing_saveable | full
    # Pipeline-parallel microbatch schedule (only read when the mesh has
    # pipeline>1): "gpipe" | "1f1b" (parallel/pipeline.py).
    pipeline_schedule: str = "gpipe"
    # Sequence-chunked cross-entropy: never materialize [B,S,V] logits
    # (0 = off). Big win at large vocab; numerics identical.
    loss_chunk_size: int = 0
    # Fused Pallas kernels for the non-attention hot ops (ops/fused_xent.py
    # blockwise vocab-chunked CE, ops/fused_norm.py RMSNorm(+residual) and
    # SwiGLU): "auto" = on when the backend is TPU (resolved the same way
    # bench.py resolves attn_impl="pallas"), "on" forces them (interpret
    # mode off-TPU — the CPU parity-test path), "off" keeps the XLA ops.
    # Single-device / per-shard only: under a multi-device GSPMD mesh the
    # kernels fall back (Mosaic can't be auto-partitioned).
    fused_kernels: str = "auto"
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "float32"

    def __post_init__(self):
        # A configuration file's list (JSON has no tuple) stays hashable.
        object.__setattr__(self, "layer_kinds", tuple(self.layer_kinds))
        for name, n in (("attn_multipliers", 3), ("ssd_multipliers", 7),
                        ("mlp_multipliers", 2)):
            value = tuple(float(m) for m in getattr(self, name))
            if len(value) not in (0, n):
                raise ValueError(f"{name} holds {n} multipliers or none")
            object.__setattr__(self, name, value)
        object.__setattr__(self, "ffn_free",
                           tuple(int(i) for i in self.ffn_free))
        unknown = set(self.layer_kinds) - {"attention", "window", "conv",
                                           "linear", "parallel", "ssd",
                                           *SSM_KINDS}
        if unknown:
            raise ValueError(f"unknown layer kinds {sorted(unknown)}")
        if self.ffn_free and not (
                set(self.ffn_free) <= set(range(len(self.layer_kinds)))
                and not self.moe_shortcut and not self.leading_dense_layers
                and not self.stateless_tail):
            raise ValueError(
                f"ffn_free={self.ffn_free} names places of the "
                f"{len(self.layer_kinds)} of layer_kinds; not with a "
                "shortcut, leading dense layers or a stateless tail")
        if self.moe_latent_dim and not (
                self.is_moe and not self.zero_experts
                and self.moe_impl in ("sorted", "dense")):
            raise ValueError(
                "a latent projection (moe_latent_dim) stands round the "
                "routed experts of a sorted or dense expert layer: a zero "
                "expert's identity has no width to return, a capacity "
                "buffer is hidden-wide")
        if {"parallel", "ssd"} & set(self.layer_kinds):
            if not (self.ssd_heads > 0 and self.ssd_head_dim > 0
                    and self.ssd_state > 0 and self.ssd_groups > 0
                    and self.ssd_chunk > 0
                    and self.ssd_heads % self.ssd_groups == 0):
                raise ValueError(
                    "parallel and ssd layers need ssd_heads, ssd_head_dim, "
                    "ssd_state and ssd_chunk > 0 and ssd_groups dividing "
                    "ssd_heads")
        if "parallel" in self.layer_kinds:
            if set(self.layer_kinds) != {"parallel"} or self.is_latent \
                    or self.diff_attention or self.kv_heads_packed:
                raise NotImplementedError(
                    "parallel layers beside another kind of layer, or over "
                    "latent, differential or packed attention: a parallel "
                    "layer's K and V are the global planes' rows of its "
                    "own index")
        if "window" in self.layer_kinds and self.attn_window <= 0:
            raise ValueError("window layers need attn_window > 0")
        if "linear" in self.layer_kinds and not (
                self.linear_heads > 0 and self.linear_head_dim > 0
                and self.linear_gate_rank > 0):
            raise ValueError("linear layers need linear_heads, "
                             "linear_head_dim and linear_gate_rank > 0")
        if self.norm_kind not in ("rms", "layer"):
            raise ValueError(f"unknown norm_kind {self.norm_kind!r}")
        if "ssm" in self.layer_kinds and not (
                self.ssm_state > 0 and self.ssm_inner > 0
                and self.ssm_dt_rank > 0):
            raise ValueError("ssm layers need ssm_state, ssm_inner and "
                             "ssm_dt_rank > 0")
        if self.diff_attention and (self.n_heads % 2 or self.n_kv_heads % 2
                                    or self.is_latent):
            raise ValueError("differential attention pairs per-head queries "
                             "and K/V heads: both counts even, not latent")
        if self.index_topk and not (
                self.is_latent and self.index_heads > 0
                and self.index_head_dim >= self.qk_rope_dim > 0
                and set(self.period) == {"attention"}):
            raise ValueError(
                "an indexer (index_topk > 0) selects the keys of LATENT "
                "attention layers: index_heads > 0 and index_head_dim >= "
                "qk_rope_dim")
        kinds = self.kinds
        head = self.n_layers - self.stateless_tail
        if "cross" in kinds and not self.diff_attention:
            raise NotImplementedError(
                "cross layers are differential attention's (diff_attention)")
        # (a config that is ALL tail is one of a stack's groups,
        # ``decoder.layer_groups``, not a stack)
        for kind, source in STATELESS_KINDS.items():
            if kind not in kinds or not head:
                continue
            if source not in kinds[:head] or kind in kinds[:head]:
                raise ValueError(
                    f"{kind!r} layers stand behind every layer that keeps "
                    f"state and read the last {source!r} layer in front of "
                    f"them; the stack is {kinds}")
        if self.zero_experts and not self.is_moe:
            raise ValueError("zero experts are outputs of an expert layer's "
                             "router: num_experts > 0")
        if self.moe_shortcut and not (
                self.is_moe and self.n_layers % 2 == 0
                and not self.layer_kinds and not self.leading_dense_layers):
            raise ValueError(
                "an expert layer on a shortcut rides beside the dense MLPs "
                "of a PAIR of attention blocks: num_experts > 0, an even "
                "n_layers, no layer_kinds, no leading dense layers")
        if self.experts_held and self.num_experts and not (
                0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} held "
                f"of {self.num_experts}")

    @property
    def period(self) -> tuple:
        """One period of the stack's pattern of layer kinds (under a
        shortcut the pair of blocks one expert layer spans)."""
        return self.layer_kinds or ("attention",) * (1 + self.moe_shortcut)

    @property
    def kinds(self) -> tuple:
        """The kind of every layer of the stack, in order."""
        period = self.period
        return tuple(period[i % len(period)] for i in range(self.n_layers))

    @property
    def fed(self) -> tuple:
        """Whether each layer of the stack has its feed-forward part (and
        the norm in front of it): all but the places ``ffn_free`` names."""
        p = len(self.period)
        return tuple(i % p not in self.ffn_free
                     for i in range(self.n_layers))

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def layers_holding(self, kind: str) -> int:
        """Layers that keep cache planes of ``kind``: the layers of that
        kind and those that hold its planes beside their own
        (``ALSO_HOLDS``)."""
        return sum(k == kind or kind in ALSO_HOLDS.get(k, ())
                   for k in self.kinds)

    @property
    def stateless_tail(self) -> int:
        """The layers at the END of the stack that keep no state and write
        no cache ("gmu" and "cross"): a position whose logits nobody reads
        need not pass through them (serve/paged.py::_pool_forward)."""
        kinds = self.kinds
        n = 0
        while n < len(kinds) and kinds[-1 - n] in STATELESS_KINDS:
            n += 1
        return n

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def weight_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_mlp_dim(self) -> int:
        return self.moe_mlp_dim or self.mlp_dim

    @property
    def experts_here(self) -> int:
        """The experts whose weights this layer holds."""
        return self.experts_held or self.num_experts

    @property
    def router_width(self) -> int:
        """The router's outputs: every expert with weights, held or not,
        and the zero experts behind them."""
        return self.num_experts + self.zero_experts

    def expert_layer(self, block: int) -> int:
        """The expert layer, counted among its group's, that block ``block``
        of the group holds (under a shortcut: starts or joins); with blocks
        that have no feed-forward part (``ffn_free``), the feed-forward
        parts in front of ``block``."""
        if self.ffn_free:       # (``block`` may be a scan's traced index)
            p = len(self.period)
            before = [sum(self.fed[:j]) for j in range(p)]
            place = block % p
            return block // p * sum(self.fed[:p]) + (
                before[place] if isinstance(block, int)
                else jnp.asarray(before, jnp.int32)[place])
        return block // 2 if self.moe_shortcut else block

    @property
    def mlp_matrices(self) -> int:
        """Matrices of one MLP (dense, an expert, the shared expert): gate,
        up and down, or up and down alone (``hidden_act`` "relu2")."""
        return 2 if self.hidden_act == "relu2" else 3

    def _conv_params(self) -> int:
        """One conv block's operator: in and out projections and the taps."""
        d = self.hidden
        return 3 * d * d + self.conv_taps * d + d * d

    @property
    def linear_dim(self) -> int:
        """Channels of a linear layer's q, k or v: all its heads' values."""
        return self.linear_heads * self.linear_head_dim

    def _linear_params(self) -> int:
        """One linear-attention block's operator: q / k / v and output
        projections, their taps, the two low-rank pairs (decay, gate), the
        decay's ``A_log`` a head and ``dt_bias`` a channel, beta's
        projection, the output norm's weight."""
        d, n, r = self.hidden, self.linear_dim, self.linear_gate_rank
        return (4 * d * n + 3 * self.conv_taps * n + 2 * (d * r + r * n)
                + self.linear_heads + n + d * self.linear_heads
                + self.linear_head_dim)

    def _ssm_params(self) -> int:
        """One ssm block's operator: the in projection (u and z), the taps
        and their bias, ``wx`` (step, B, C), the step's projection and bias,
        ``A`` a channel and state, ``D``, the out projection."""
        d, e, n, r = self.hidden, self.ssm_inner, self.ssm_state, \
            self.ssm_dt_rank
        return (2 * d * e + (self.conv_taps + 1) * e + e * (r + 2 * n)
                + r * e + e + n * e + e + e * d)

    @property
    def ssd_inner(self) -> int:
        """Channels of an SSD mixer's ``x``, gate and output: all its heads'
        values."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_conv_dim(self) -> int:
        """Channels the SSD mixer's convolution runs over: ``[x | B | C]``."""
        return self.ssd_inner + 2 * self.ssd_groups * self.ssd_state

    def _ssd_params(self) -> int:
        """One SSD mixer: the in-projection (gate, ``[x | B | C]``, a step a
        head), the taps and their bias, ``A_log``, ``D`` and ``dt_bias`` a
        head, the gated norm's weight, the out-projection."""
        d, e, c, h = self.hidden, self.ssd_inner, self.ssd_conv_dim, \
            self.ssd_heads
        return (d * (e + c + h) + (self.conv_taps + 1) * c + 3 * h + e
                + e * d)

    def _diff_params(self, cross: bool) -> int:
        """One differential attention operator: q (k and v unless ``cross``)
        and output projections with their biases, the four lambda vectors of
        ``head_dim`` and the pair norm's weight of twice that."""
        d = self.hidden
        kv = 0 if cross else 2 * self.kv_dim
        return ((d + 1) * (self.q_dim + kv) + (self.q_dim + 1) * d
                + 6 * self.head_dim)

    def _attn_params(self) -> int:
        """One block's attention matrices (a latent block's two norms too;
        the two per-head norms of ``qk_norm``; the output gate's matrix)."""
        d, h = self.hidden, self.n_heads
        if self.diff_attention:
            return self._diff_params(cross=False)
        if self.is_latent:
            r, q = self.kv_lora_rank, self.q_lora_rank
            hi, di = self.index_heads, self.index_head_dim
            indexer = (q * hi * di + d * di + 2 * di + d * hi) \
                if self.index_topk else 0
            return (d * q + q + q * h * (self.qk_nope_dim + self.qk_rope_dim)
                    + d * (r + self.qk_rope_dim) + r
                    + r * h * (self.qk_nope_dim + self.v_head_dim)
                    + h * self.v_head_dim * d + indexer)
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d \
            + (2 * self.head_dim if self.qk_norm else 0) \
            + (d * self.q_dim if self.attn_output_gate else 0)

    def _operator_params(self, first: int, last: int) -> int:
        """The operators (attention, convolution or linear attention) of
        layers [first, last)."""
        kinds = self.kinds[first:last]
        return (kinds.count("attention") + kinds.count("window")) \
            * self._attn_params() + kinds.count("conv") * self._conv_params() \
            + kinds.count("linear") * self._linear_params() \
            + kinds.count("ssm") * self._ssm_params() \
            + kinds.count("parallel") * (self._attn_params()
                                         + self._ssd_params()) \
            + kinds.count("ssd") * self._ssd_params() \
            + kinds.count("gmu") * 2 * self.hidden * self.ssm_inner \
            + (kinds.count("cross") * self._diff_params(cross=True)
               if "cross" in kinds else 0)

    def _mlp_params(self, active: bool) -> int:
        """One expert layer's (or, dense, one MLP's) matrices; ``active``
        counts the experts one token multiplies against HERE (of its
        ``experts_per_token`` choices the expected share that falls on a held
        expert, ``experts_here / num_experts`` of them), not those held."""
        d, mats = self.hidden, self.mlp_matrices
        if not self.is_moe:
            return mats * d * self.mlp_dim
        # a routed expert works at the latent's width where there is one;
        # the shared expert and the router at the hidden's
        per_expert = mats * (self.moe_latent_dim or d) * self.expert_mlp_dim
        shared = self.shared_experts * mats * d * self.expert_mlp_dim
        latent = 2 * d * self.moe_latent_dim
        if active:      # the router's small product is left out, as before
            met = self.experts_per_token * self.experts_here \
                / self.router_width
            return int(met * per_expert) + shared + latent
        routing = self.router_width * (
            d + (self.router_score != "softmax"))       # the choice's bias
        return self.experts_here * per_expert + shared + latent + routing

    def num_params(self) -> int:
        """Parameters HELD (embedding included once if tied): of an expert
        layer the ``experts_here`` experts this chip keeps, beside the whole
        router and the shared expert."""
        d, v = self.hidden, self.vocab_size
        k = self.leading_dense_layers
        norm = d * (2 if self.norm_kind == "layer" else 1)  # weight (, bias)
        if self.moe_shortcut:   # a dense MLP a block, an expert layer a pair
            k = self.n_layers
        layers = self._operator_params(0, self.n_layers) \
            + self.expert_layer(self.n_layers - self.leading_dense_layers) \
            * self._mlp_params(False) \
            + (self.n_layers + sum(self.fed)) * norm \
            + k * self.mlp_matrices * d * self.mlp_dim
        embed = v * d if self.tie_embeddings else 2 * v * d
        return layers + embed + norm

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6N for dense; MoE
        counts only the experts a token meets HERE: all its choices where
        every expert is held, the expected share of them on a chip that holds
        ``experts_held``)."""
        d = self.hidden
        k = self.n_layers if self.moe_shortcut else self.leading_dense_layers
        dense_n = self._operator_params(0, self.n_layers) \
            + self.expert_layer(self.n_layers - self.leading_dense_layers) \
            * self._mlp_params(True) \
            + k * self.mlp_matrices * d * self.mlp_dim + self.vocab_size * d
        return 6.0 * dense_n


def blocks_of(pattern: str) -> tuple:
    """A published stack that lists every sublayer as a layer of its own
    ("M" a Mamba-2 mixer, "*" an attention, "E" an expert layer, each ``x +
    F(N(x))``) as this system's blocks: (a block's kind for every mixer or
    attention, in order; the places of the blocks whose operator no "E"
    follows: ``layer_kinds``, ``ffn_free``)."""
    kinds, free = [], []
    for i, c in enumerate(pattern):
        if c == "E":
            if not kinds or pattern[i - 1] == "E":
                raise ValueError(f"an expert layer behind no operator at {i}")
            continue
        kinds.append({"M": "ssd", "*": "attention"}[c])
        if pattern[i + 1:i + 2] != "E":
            free.append(len(kinds) - 1)
    return tuple(kinds), tuple(free)


_NEMOTRON_BLOCKS = blocks_of(
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


PRESETS: dict[str, DecoderConfig] = {
    # Llama-3-8B (public card: 32L, 4096h, 32 heads / 8 kv, 14336 mlp, 128k vocab)
    "llama3-8b": DecoderConfig(
        vocab_size=128256, hidden=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        head_dim=128, mlp_dim=14336, max_seq_len=8192, rope_theta=500000.0,
        loss_chunk_size=512,
    ),
    # Llama-3-70B-class (for sharding dry-runs only)
    "llama3-70b": DecoderConfig(
        vocab_size=128256, hidden=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        head_dim=128, mlp_dim=28672, max_seq_len=8192, rope_theta=500000.0,
        loss_chunk_size=512,
    ),
    # Gemma-2B (public card: 18L, 2048h, 8 heads / 1 kv, head_dim 256, gelu,
    # 256k vocab, tied embeddings, embedding scale, (1+w) norms)
    "gemma-2b": DecoderConfig(
        vocab_size=256128, hidden=2048, n_layers=18, n_heads=8, n_kv_heads=1,
        head_dim=256, mlp_dim=16384, max_seq_len=8192, rope_theta=10000.0,
        hidden_act="gelu", tie_embeddings=True, norm_plus_one=True,
        embed_scale=True, loss_chunk_size=512,
    ),
    # Mixtral-8x7B (public card: 32L, 4096h, 32/8 heads, 14336 mlp, 8 experts top-2)
    "mixtral-8x7b": DecoderConfig(
        vocab_size=32000, hidden=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        head_dim=128, mlp_dim=14336, max_seq_len=8192, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2,
    ),
    # GLM-4.7-Flash (zai-org config.json, model_type glm4_moe_lite: 47L,
    # 2048h, 20 latent-attention heads, one dense layer of 10240 then 64
    # sigmoid-routed experts of 1536, top-4, beside one shared expert)
    "glm-4.7-flash": DecoderConfig(
        vocab_size=154880, hidden=2048, n_layers=47, n_heads=20,
        n_kv_heads=20, head_dim=256, mlp_dim=10240, max_seq_len=202752,
        rope_theta=1000000.0, num_experts=64, experts_per_token=4,
        moe_impl="sorted", moe_mlp_dim=1536, shared_experts=1,
        leading_dense_layers=1, router_score="sigmoid",
        router_norm_topk=True, router_scale=1.8, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256,
    ),
    # GLM-5 (zai-org config.json, model_type glm_moe_dsa: 78L, 6144h, 64
    # latent-attention heads that read the 2048 keys an indexer of 32 heads
    # of 128 selects, three dense layers of 12288 then 256 sigmoid-routed
    # experts of 2048, top-8, beside one shared expert)
    "glm-5": DecoderConfig(
        vocab_size=154880, hidden=6144, n_layers=78, n_heads=64,
        n_kv_heads=64, head_dim=64, mlp_dim=12288, max_seq_len=202752,
        rope_theta=1000000.0, num_experts=256, experts_per_token=8,
        moe_impl="sorted", moe_mlp_dim=2048, shared_experts=1,
        leading_dense_layers=3, router_score="sigmoid",
        router_norm_topk=True, router_scale=2.5, q_lora_rank=2048,
        kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256,
        index_heads=32, index_head_dim=128, index_topk=2048,
    ),
    # LongCat-Flash-Omni's language model (meituan-longcat config.json;
    # LongCat-Flash technical report: 28 published layers, 6144h, each TWO
    # latent attentions of 64 heads behind ranks 1536 / 512 (both rank
    # factors) and TWO dense MLPs of 12288, so 56 blocks here, with ONE
    # expert layer a published layer on a shortcut beside them: a softmax
    # router over 512 experts of 2048 and 256 that are the identity, top-12
    # by score plus a bias, weights 6 x the score, not normalised; untied
    # head. The audio and vision towers and the codec decoder are not built)
    "longcat-flash-omni": DecoderConfig(
        vocab_size=131072, hidden=6144, n_layers=56, n_heads=64,
        n_kv_heads=64, head_dim=128, mlp_dim=12288, max_seq_len=131072,
        rope_theta=1e7, norm_eps=1e-5, num_experts=512, zero_experts=256,
        experts_per_token=12, moe_impl="sorted", moe_mlp_dim=2048,
        moe_shortcut=True, router_score="softmax_all",
        router_norm_topk=False, router_scale=6.0, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        latent_rank_scale=True,
    ),
    # LFM2-24B-A2B (LiquidAI config.json, model_type lfm2_moe: 40L, 2048h;
    # layers 2, 6, ... 38 attention of 32/8 heads of 64 with per-head q/k
    # norms, every other layer a gated short convolution of 3 taps; two
    # dense layers of 11776 then 64 sigmoid-routed experts of 1536, top-4,
    # no shared expert; tied head)
    "lfm2-24b-a2b": DecoderConfig(
        vocab_size=65536, hidden=2048, n_layers=40, n_heads=32, n_kv_heads=8,
        head_dim=64, mlp_dim=11776, max_seq_len=128000,
        rope_theta=1000000.0, tie_embeddings=True, num_experts=64,
        experts_per_token=4, moe_impl="sorted", moe_mlp_dim=1536,
        leading_dense_layers=2, router_score="sigmoid",
        router_norm_topk=True, router_scale=1.0, router_norm_eps=1e-6,
        layer_kinds=("conv", "conv", "attention", "conv"), conv_taps=3,
        qk_norm=True, kv_heads_packed=True,
    ),
    # K-EXAONE-236B-A23B (LGAI-EXAONE config.json, model_type exaone_moe:
    # 48L, 6144h, 64/8 heads of 128 with per-head q/k norms; layers follow
    # "LLLG": three window layers (128 keys, rotated) then one global layer
    # (every key, no rotation); one dense layer of 18432 then 128
    # sigmoid-routed experts of 2048, top-8, beside one shared expert,
    # weights scaled 2.5; untied head)
    "k-exaone-236b-a23b": DecoderConfig(
        vocab_size=153600, hidden=6144, n_layers=48, n_heads=64,
        n_kv_heads=8, head_dim=128, mlp_dim=18432, max_seq_len=262144,
        rope_theta=1000000.0, num_experts=128, experts_per_token=8,
        moe_impl="sorted", moe_mlp_dim=2048, shared_experts=1,
        leading_dense_layers=1, router_score="sigmoid",
        router_norm_topk=True, router_scale=2.5,
        layer_kinds=("window", "window", "window", "attention"),
        attn_window=128, rope_window_only=True, qk_norm=True,
    ),
    # Solar-Open2-250B (upstage config.json, model_type solar_open2: 48L,
    # 4096h; layers 0, 4, ... 44 softmax GQA of 64/8 heads of 128 with no
    # position and an output gate, every other layer gated delta-rule linear
    # attention (KDA) of 64 heads of 128 behind convolutions of 4 taps; every
    # layer 320 sigmoid-routed experts of 1280, top-8, beside one shared
    # expert; untied head)
    "solar-open2-250b": DecoderConfig(
        vocab_size=196608, hidden=4096, n_layers=48, n_heads=64,
        n_kv_heads=8, head_dim=128, mlp_dim=10240, max_seq_len=1048576,
        rope_theta=10000.0, num_experts=320, experts_per_token=8,
        moe_impl="sorted", moe_mlp_dim=1280, shared_experts=1,
        router_score="sigmoid", router_norm_topk=True, router_scale=1.0,
        layer_kinds=("attention", "linear", "linear", "linear"),
        rope_window_only=True, attn_output_gate=True, conv_taps=4,
        linear_heads=64, linear_head_dim=128, linear_gate_rank=128,
    ),
    # Phi-4-mini-flash-reasoning (microsoft config.json, model_type
    # phi4flash; SambaY, arXiv:2507.06607: 32L, 2560h, 40/20 heads of 64,
    # differential attention without position, LayerNorm; layers 0-15
    # (Mamba-1, window 512) x 8, 16 Mamba-1 whose scan output is the memory,
    # 17 full attention whose K/V every cross layer reads, 18-31 (gated
    # memory unit, cross attention) x 7; dense MLP of 10240; tied head)
    "phi-4-mini-flash": DecoderConfig(
        vocab_size=200064, hidden=2560, n_layers=32, n_heads=40,
        n_kv_heads=20, head_dim=64, mlp_dim=10240, max_seq_len=262144,
        norm_eps=1e-5, tie_embeddings=True,
        layer_kinds=("ssm", "window") * 8 + ("ssm", "attention")
        + ("gmu", "cross") * 7,
        attn_window=512, conv_taps=4, ssm_state=16, ssm_inner=5120,
        ssm_dt_rank=160, diff_attention=True, attn_bias=True,
        use_rope=False, norm_kind="layer",
    ),
    # Falcon-H1-34B-Instruct (tiiuae config.json, model_type falcon_h1;
    # arXiv:2507.22448: 72L, 5120h; EVERY block runs GQA of 20/4 heads of
    # 128 with RoPE (theta 1e11) and a Mamba-2 (SSD, arXiv:2405.21060) mixer
    # of 32 heads of 128 with a state of 256, 2 groups, a convolution of 4
    # taps, side by side on one normed input; dense MLP of 21504; untied
    # head; the model's fixed muP multipliers)
    "falcon-h1-34b": DecoderConfig(
        vocab_size=261120, hidden=5120, n_layers=72, n_heads=20,
        n_kv_heads=4, head_dim=128, mlp_dim=21504, max_seq_len=262144,
        rope_theta=1e11, norm_eps=1e-5, layer_kinds=("parallel",),
        conv_taps=4, ssd_heads=32, ssd_head_dim=128, ssd_state=256,
        ssd_groups=2, ssd_chunk=128,
        embed_multiplier=5.656854249492381, head_multiplier=0.0078125,
        attn_multipliers=(1.0, 0.0375, 0.011048543456039804),
        ssd_multipliers=(0.25, 0.08838834764831845, 0.3535533905932738,
                         0.25, 0.1767766952966369, 0.5, 0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ),
    # Nemotron-3-Super-120B-A12B (nvidia config.json, model_type nemotron_h;
    # arXiv:2504.03624: 88 published layers, 4096h, each ONE sublayer: a
    # Mamba-2 mixer (M: 128 heads of 64, state 128, 8 groups, 4 taps), an
    # expert layer (E) or GQA of 32/2 heads of 128 without position (*),
    # pattern "MEMEMEM*E..."; read as 48 BLOCKS of a mixer or an attention
    # and the expert layer behind it, the 8 mixers in front of an attention
    # without one (``ffn_free``); 512 sigmoid-routed experts of 2688 behind a
    # latent of 1024, top-22, weights scaled 5, beside one shared expert of
    # 5376 (two of 2688 in one matrix); squared-ReLU MLPs of two matrices;
    # untied head. The prediction module is not built)
    "nemotron-3-super-120b-a12b": DecoderConfig(
        vocab_size=131072, hidden=4096, n_layers=48, n_heads=32,
        n_kv_heads=2, head_dim=128, mlp_dim=2688, max_seq_len=262144,
        rope_theta=10000.0, norm_eps=1e-5, hidden_act="relu2",
        num_experts=512, experts_per_token=22, moe_impl="sorted",
        moe_mlp_dim=2688, shared_experts=2, moe_latent_dim=1024,
        router_score="sigmoid", router_norm_topk=True, router_scale=5.0,
        layer_kinds=_NEMOTRON_BLOCKS[0], ffn_free=_NEMOTRON_BLOCKS[1],
        use_rope=False, conv_taps=4, ssd_heads=128, ssd_head_dim=64,
        ssd_state=128, ssd_groups=8, ssd_chunk=128,
    ),
    # tiny variants for tests/sim (structure-faithful, sized for 1 CPU core)
    "tiny": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=128, max_seq_len=128,
    ),
    "tiny-gemma": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=1,
        head_dim=16, mlp_dim=128, max_seq_len=128, hidden_act="gelu",
        tie_embeddings=True, norm_plus_one=True, embed_scale=True,
        logits_softcap=30.0,
    ),
    "tiny-moe": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=128, max_seq_len=128,
        num_experts=4, experts_per_token=2,
    ),
    # GLM-4.7-Flash's structure at odd small ranks: 1 dense + 3 expert
    # layers, 8 sigmoid-routed experts top-2 beside 1 shared, latent cache
    "tiny-glm": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=20, mlp_dim=160, max_seq_len=128, num_experts=8,
        experts_per_token=2, moe_impl="sorted", moe_mlp_dim=48,
        shared_experts=1, leading_dense_layers=1, router_score="sigmoid",
        router_norm_topk=True, router_scale=1.8, q_lora_rank=24,
        kv_lora_rank=40, qk_nope_dim=12, qk_rope_dim=8, v_head_dim=20,
    ),
    # GLM-5's structure as one chip of four holds it: tiny-glm's latent
    # attention behind an indexer of 2 heads of 16 (RoPE on 8 of them) that
    # selects 24 keys (the tests' contexts run under, at and over it), 16
    # experts top-4 of which 4 are held
    "tiny-glm-5": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=20, mlp_dim=160, max_seq_len=256, rope_theta=1e6,
        num_experts=16, experts_per_token=4, moe_impl="sorted",
        moe_mlp_dim=48, shared_experts=1, leading_dense_layers=1,
        router_score="sigmoid", router_norm_topk=True, router_scale=2.5,
        experts_held=4, q_lora_rank=24, kv_lora_rank=40, qk_nope_dim=12,
        qk_rope_dim=8, v_head_dim=20, index_heads=2, index_head_dim=16,
        index_topk=24,
    ),
    # LongCat-Flash's structure as one chip of four holds it: 2 published
    # layers (4 blocks of tiny-glm's latent attention, both rank factors,
    # and a dense MLP each; an expert layer a pair on its shortcut), a
    # softmax router over 16 experts and 8 that are the identity, top-4, of
    # which 4 are held
    "tiny-longcat-flash": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=20, mlp_dim=160, max_seq_len=256, rope_theta=1e7,
        num_experts=16, zero_experts=8, experts_per_token=4,
        moe_impl="sorted", moe_mlp_dim=48, moe_shortcut=True,
        router_score="softmax_all", router_norm_topk=False,
        router_scale=6.0, experts_held=4, q_lora_rank=24, kv_lora_rank=40,
        qk_nope_dim=12, qk_rope_dim=8, v_head_dim=20,
        latent_rank_scale=True,
    ),
    # LFM2's structure: a leading dense conv layer, then two periods of
    # (attention, conv, conv, conv) with 8 sigmoid-routed experts top-2
    "tiny-lfm2": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=9, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=160, max_seq_len=256, tie_embeddings=True,
        num_experts=8, experts_per_token=2, moe_impl="sorted",
        moe_mlp_dim=48, leading_dense_layers=1, router_score="sigmoid",
        router_norm_topk=True, router_norm_eps=1e-6,
        layer_kinds=("conv", "attention", "conv", "conv", "conv",
                     "attention", "conv", "conv", "conv"),
        conv_taps=3, qk_norm=True, kv_heads_packed=True,
    ),
    # K-EXAONE's structure as one chip of four holds it: a leading dense
    # window layer, then window, window, global, window over 16
    # sigmoid-routed experts top-4 of which 4 are held, beside a shared one;
    # a window of 24 (longer than the tests' page of 16; they also run 8)
    "tiny-exaone": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=160, max_seq_len=256, num_experts=16,
        experts_per_token=4, moe_impl="sorted", moe_mlp_dim=48,
        shared_experts=1, leading_dense_layers=1, router_score="sigmoid",
        router_norm_topk=True, router_scale=2.5, experts_held=4,
        layer_kinds=("window", "window", "window", "attention", "window"),
        attn_window=24, rope_window_only=True, qk_norm=True,
    ),
    # Solar-Open2's structure as one chip of four holds it: two periods of
    # (gated global attention without position, linear, linear, linear)
    # over 16 sigmoid-routed experts top-4 of which 4 are held, beside a
    # shared one; linear heads of 16 keys, convolutions of 4 taps
    "tiny-solar": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=160, max_seq_len=256, num_experts=16,
        experts_per_token=4, moe_impl="sorted", moe_mlp_dim=48,
        shared_experts=1, router_score="sigmoid", router_norm_topk=True,
        router_scale=1.0, experts_held=4,
        layer_kinds=("attention", "linear", "linear", "linear"),
        rope_window_only=True, attn_output_gate=True, conv_taps=4,
        linear_heads=4, linear_head_dim=16, linear_gate_rank=8,
    ),
    # Phi-4-mini-flash's structure: two (ssm, window 8), one (ssm, full
    # attention), two (gmu, cross); differential attention over 4/2 heads of
    # 16 without position, LayerNorm, 128 channels of 4 states, tied head
    "tiny-phi4flash": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=10, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=160, max_seq_len=256, tie_embeddings=True,
        layer_kinds=("ssm", "window") * 2 + ("ssm", "attention")
        + ("gmu", "cross") * 2,
        attn_window=8, conv_taps=4, ssm_state=4, ssm_inner=128,
        ssm_dt_rank=4, diff_attention=True, attn_bias=True, use_rope=False,
        norm_kind="layer",
    ),
    # Falcon-H1's structure: three parallel blocks of GQA (4/2 heads of 16,
    # rotated) beside an SSD mixer of 4 heads of 16 with a state of 32 in 2
    # groups, blocks of 8 positions, convolutions of 4 taps; every
    # multiplier another number than 1
    "tiny-falconh1": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=160, max_seq_len=256, rope_theta=1e6,
        layer_kinds=("parallel",), conv_taps=4, ssd_heads=4,
        ssd_head_dim=16, ssd_state=32, ssd_groups=2, ssd_chunk=8,
        embed_multiplier=2.0, head_multiplier=0.5,
        attn_multipliers=(0.75, 0.5, 0.25),
        ssd_multipliers=(0.5, 0.4, 0.7, 0.6, 0.35, 0.8, 0.45),
        mlp_multipliers=(0.6, 0.3),
    ),
    # Nemotron-3-Super's structure as one chip of four holds it: the
    # published pattern "MEM*EME" as four blocks (mixer + experts, mixer
    # ALONE, attention + experts, mixer + experts): an SSD mixer of 4 heads
    # of 16 with a state of 16 in 2 groups, blocks of 8 positions; GQA of
    # 4/2 heads of 16 without position; 16 sigmoid-routed experts of 32
    # behind a latent of 32, top-4, 4 held, beside a shared expert of 64;
    # squared-ReLU MLPs of two matrices
    "tiny-nemotron-h": DecoderConfig(
        vocab_size=256, hidden=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, mlp_dim=32, max_seq_len=256, hidden_act="relu2",
        num_experts=16, experts_per_token=4, moe_impl="sorted",
        moe_mlp_dim=32, shared_experts=2, moe_latent_dim=32,
        router_score="sigmoid", router_norm_topk=True, router_scale=5.0,
        experts_held=4, layer_kinds=("ssd", "ssd", "attention", "ssd"),
        ffn_free=(1,), use_rope=False, conv_taps=4, ssd_heads=4,
        ssd_head_dim=16, ssd_state=16, ssd_groups=2, ssd_chunk=8,
    ),
}


def preset(name: str, **overrides) -> DecoderConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; known: {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
