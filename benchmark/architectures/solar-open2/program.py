"""The program's side of the ``solar-open2`` architecture (Solar-Open2-250B:
gated delta-rule linear-attention layers (KDA) whose state a sequence lives in
the page pool, beside one softmax GQA layer in four without position and with
an output gate; every layer sigmoid-routed experts with a correction bias
beside a shared expert, of which ONE CHIP'S SHARE is held; untied head over a
slice of the vocabulary): the config object for a configuration file, held
against every key of the file that says something about the model's
equations, the depth, the layers, the experts and the vocabulary held. The
only file of the architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    layers held are the published layers ``0 .. num_hidden_layers`` (their
    kinds read off ``gqa_layers``, the published list; ``gqa_layers_held`` its
    part that is held), the experts held ``n_routed_experts`` from
    ``expert_offset`` on (``n_routed_experts_routed`` is the router's width,
    the published ``n_routed_experts``)."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    lin = conf["linear_attn_config"]
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "gqa_layers_held": [i for i, kind in enumerate(cfg.kinds)
                            if kind == "attention"],
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_routed": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_shared_experts": cfg.shared_experts,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "use_gqa_gate": cfg.attn_output_gate,
        "kda_gate_rank": cfg.linear_gate_rank,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "use_rope": False,              # no layer of this stack rotates
        "kda_use_full_proj": False,     # the decay and the gate at low rank
        "kda_allow_neg_eigval": True,   # beta = 2 sigmoid: (0, 2)
    }
    architecture.agree(conf, same)
    if lin != {"short_conv_kernel_size": cfg.conv_taps,
               "head_dim": cfg.linear_head_dim,
               "num_heads": cfg.linear_heads, "num_kv_heads": None}:
        raise architecture.ManifestError(
            f"linear_attn_config: the file says {lin}, the program has "
            f"{cfg.linear_heads} heads of {cfg.linear_head_dim} (as many "
            f"value heads) behind {cfg.conv_taps} taps")
    held = conf["gqa_layers_held"]
    if held != [i for i in conf["gqa_layers"] if i < cfg.n_layers]:
        raise architecture.ManifestError(
            "gqa_layers_held is not the published gqa_layers below "
            f"{cfg.n_layers}")
    if not (cfg.rope_window_only and not cfg.layers_of("window")
            and cfg.layers_of("linear") and cfg.moe_impl == "sorted"
            and cfg.router_score == "sigmoid" and cfg.experts_held
            and cfg.hidden_act == "silu"):
        raise architecture.ManifestError(
            "solar-open2 is linear-attention layers beside global attention "
            "that carries no position, over a held share of drop-free "
            "sigmoid-routed experts; the program's config has "
            f"layer_kinds={cfg.layer_kinds}, rope_window_only="
            f"{cfg.rope_window_only}, moe_impl={cfg.moe_impl!r}, "
            f"router_score={cfg.router_score!r}, "
            f"experts_held={cfg.experts_held}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
