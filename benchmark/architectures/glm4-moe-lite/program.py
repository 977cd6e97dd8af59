"""The program's side of the ``glm4-moe-lite`` architecture (GLM-4.7-Flash:
latent attention, a leading dense layer, sigmoid-routed experts beside a
shared one): the config object for a configuration file, held against every
key of the file that says something about the model's equations or the depth
and the experts held. The only file of the architecture that imports
``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. What
    the program does not have at all (a group limit in the router, a
    next-token prediction module, a RoPE scaling, an attention bias, a
    partial rotary factor) is held to the value that means "none"."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "intermediate_size": cfg.mlp_dim,
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "n_routed_experts": cfg.num_experts,
        "n_shared_experts": cfg.shared_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "topk_method": {"sigmoid": "noaux_tc"}.get(cfg.router_score),
        "n_group": 1, "topk_group": 1,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta, "rope_scaling": None,
        "partial_rotary_factor": 1, "attention_bias": False,
        "rms_norm_eps": cfg.norm_eps, "hidden_act": cfg.hidden_act,
        "tie_word_embeddings": cfg.tie_embeddings,
        "num_nextn_predict_layers": 0,
    }
    architecture.agree(conf, same)
    if not (cfg.is_latent and cfg.moe_impl == "sorted"):
        raise architecture.ManifestError(
            "glm4-moe-lite is latent attention over drop-free experts; the "
            f"program's config has kv_lora_rank={cfg.kv_lora_rank}, "
            f"moe_impl={cfg.moe_impl!r}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture yet)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
