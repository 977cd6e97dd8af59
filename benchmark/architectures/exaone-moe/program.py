"""The program's side of the ``exaone-moe`` architecture (K-EXAONE-236B-A23B:
window and global attention layers in the pattern LLLG with per-head q/k
norms, rotation on the window layers only, a leading dense layer, then
sigmoid-routed experts with a correction bias beside a shared expert, of
which ONE CHIP'S SHARE is held; untied head over a slice of the vocabulary):
the config object for a configuration file, held against every key of the
file that says something about the model's equations, the depth, the layers,
the experts and the vocabulary held. The only file of the architecture that
imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture

# the program's name of a layer's kind -> the published ``layer_types`` name
PUBLISHED_NAME = {"window": "sliding_attention", "attention": "full_attention"}


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    layers held are ``layer_types_held`` (``layer_types`` stays the
    published list, which the cut is read off), the experts held
    ``num_experts`` from ``expert_offset`` on (``num_experts_routed`` is the
    router's width, the published ``num_experts``)."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "layer_types_held": [PUBLISHED_NAME[kind] for kind in cfg.kinds],
        "intermediate_size": cfg.mlp_dim,
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "num_experts": cfg.experts_here,
        "num_experts_routed": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_shared_experts": cfg.shared_experts,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "scoring_func": cfg.router_score,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "sliding_window": cfg.attn_window,
        "rms_norm_eps": cfg.norm_eps,
        "hidden_act": cfg.hidden_act,
        "tie_word_embeddings": cfg.tie_embeddings,
        "num_nextn_predict_layers": 0,      # no prediction module is built
        "n_group": 1, "topk_group": 1,      # no group limit on the choice
    }
    architecture.agree(conf, same)
    if conf["rope_parameters"] != {"rope_theta": cfg.rope_theta,
                                   "rope_type": "default"}:
        raise architecture.ManifestError(
            f"rope_parameters: the file says {conf['rope_parameters']}, the "
            f"program rotates whole heads at theta {cfg.rope_theta}")
    held = conf["layer_types_held"]
    if held != conf["layer_types"][:len(held)]:
        raise architecture.ManifestError(
            "layer_types_held is not the published layer_types' first "
            f"{len(held)} entries")
    if not (cfg.qk_norm and cfg.rope_window_only and cfg.moe_impl == "sorted"
            and cfg.layers_of("window") and cfg.experts_held):
        raise architecture.ManifestError(
            "exaone-moe is window layers (rotated) beside global ones (not "
            "rotated) with per-head q/k norms over a held share of "
            "drop-free experts; the program's config has "
            f"qk_norm={cfg.qk_norm}, rope_window_only="
            f"{cfg.rope_window_only}, moe_impl={cfg.moe_impl!r}, "
            f"layer_kinds={cfg.layer_kinds}, "
            f"experts_held={cfg.experts_held}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
