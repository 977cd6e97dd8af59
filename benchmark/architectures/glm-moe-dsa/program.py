"""The program's side of the ``glm-moe-dsa`` architecture (GLM-5: latent
attention that reads the keys a learned indexer selects, leading dense
layers, sigmoid-routed experts of which ONE CHIP'S SHARE is held beside a
shared one, an untied head over a slice of the vocabulary): the config object
for a configuration file, held against every key of the file that says
something about the model's equations or the depth, the experts and the
vocabulary held. The only file of the architecture that imports
``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    experts held are ``n_routed_experts`` from ``expert_offset`` on
    (``n_routed_experts_published`` is the router's width). What the program
    does not have at all (a group limit in the router, a prediction module,
    an attention bias) is held to the value that means "none"."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.leading_dense_layers,
        "moe_layer_freq": 1,                # every later layer is experts
        "intermediate_size": cfg.mlp_dim,
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "n_shared_experts": cfg.shared_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.router_norm_topk,
        "routed_scaling_factor": cfg.router_scale,
        "scoring_func": cfg.router_score,
        "topk_method": {"sigmoid": "noaux_tc"}.get(cfg.router_score),
        "n_group": 1, "topk_group": 1,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim,
        "qk_head_dim": cfg.qk_nope_dim + cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim,
        "head_dim": cfg.head_dim,           # carried; latent attention does not read it
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "attention_bias": False,
        "rms_norm_eps": cfg.norm_eps, "hidden_act": cfg.hidden_act,
        "tie_word_embeddings": cfg.tie_embeddings,
        "num_nextn_predict_layers": 0,      # no prediction module is built
    }
    architecture.agree(conf, same)
    if conf["rope_parameters"] != {"rope_theta": cfg.rope_theta,
                                   "rope_type": "default"}:
        raise architecture.ManifestError(
            f"rope_parameters: the file says {conf['rope_parameters']}, the "
            f"program rotates at theta {cfg.rope_theta}")
    if not (cfg.is_latent and cfg.index_topk and cfg.moe_impl == "sorted"
            and cfg.experts_held):
        raise architecture.ManifestError(
            "glm-moe-dsa is latent attention over the keys an indexer "
            "selects and a held share of drop-free experts; the program's "
            f"config has kv_lora_rank={cfg.kv_lora_rank}, "
            f"index_topk={cfg.index_topk}, moe_impl={cfg.moe_impl!r}, "
            f"experts_held={cfg.experts_held}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
