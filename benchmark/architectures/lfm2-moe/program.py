"""The program's side of the ``lfm2-moe`` architecture (LFM2-24B-A2B: gated
short-convolution layers with one attention layer in four, per-head q/k
norms, leading dense layers, sigmoid-routed experts with a correction bias
and no shared expert, tied head): the config object for a configuration
file, held against every key of the file that says something about the
model's equations, the depth and the layers held. The only file of the
architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture

# the program's name of a layer's kind -> the published ``layer_types`` name
PUBLISHED_NAME = {"conv": "conv", "attention": "full_attention"}


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    layers held are ``layer_types_held`` (``layer_types`` stays the
    published list, which the cut is read off)."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "num_dense_layers": cfg.leading_dense_layers,
        "layer_types_held": [PUBLISHED_NAME[kind] for kind in cfg.kinds],
        "intermediate_size": cfg.mlp_dim,
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "use_expert_bias": cfg.router_score == "sigmoid",
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "conv_L_cache": cfg.conv_taps, "conv_bias": False,
        "norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
    architecture.agree(conf, same, absent={
        "head_dim": conf["hidden_size"] // conf["num_attention_heads"]})
    if conf["rope_parameters"] != {"rope_theta": cfg.rope_theta,
                                   "rope_type": "default"}:
        raise architecture.ManifestError(
            f"rope_parameters: the file says {conf['rope_parameters']}, the "
            f"program rotates whole heads at theta {cfg.rope_theta}")
    if not (cfg.qk_norm and cfg.moe_impl == "sorted"
            and not cfg.shared_experts and cfg.layers_of("conv")):
        raise architecture.ManifestError(
            "lfm2-moe is conv layers beside attention with per-head q/k "
            "norms over drop-free experts; the program's config has "
            f"qk_norm={cfg.qk_norm}, moe_impl={cfg.moe_impl!r}, "
            f"layer_kinds={cfg.layer_kinds}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture yet)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
