"""The program's side of the ``longcat-flash`` architecture
(LongCat-Flash-Omni's language model: a published layer of TWO latent
attentions and TWO dense MLPs with the expert layer on a shortcut beside
them, a softmax router over the experts and as many again by half that
compute nothing, of which ONE CHIP'S SHARE of the experts is held, an untied
head over a slice of the vocabulary): the config object for a configuration
file, held against every key of the file that says something about the
model's equations or the depth, the experts and the vocabulary held. The
only file of the architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    file counts PUBLISHED layers (``num_layers``), the program blocks, two a
    published layer. The experts held are ``n_routed_experts`` from
    ``expert_offset`` on (``n_routed_experts_published`` is the experts the
    router scores beside its ``zero_expert_num`` zero experts). What the
    program does not have at all (an attention bias, a bias on the router's
    logits, a normalisation of the chosen weights, another kind of zero
    expert than the identity) is held to the value that means "none"."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_layers": cfg.n_layers // 2,
        "ffn_hidden_size": cfg.mlp_dim,
        "expert_ffn_hidden_size": cfg.expert_mlp_dim,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "zero_expert_num": cfg.zero_experts,
        "zero_expert_type": "identity",
        "moe_topk": cfg.experts_per_token,
        "routed_scaling_factor": cfg.router_scale,
        "norm_topk_prob": cfg.router_norm_topk,
        "router_bias": False,               # none on the logits
        "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim,
        "mla_scale_q_lora": cfg.latent_rank_scale,
        "mla_scale_kv_lora": cfg.latent_rank_scale,
        "attention_method": "MLA",
        "attention_bias": False,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
    architecture.agree(conf, same, absent={
        "norm_topk_prob": False, "router_bias": False,
        "tie_word_embeddings": False})
    if not (cfg.is_latent and cfg.moe_shortcut and cfg.zero_experts
            and cfg.router_score == "softmax_all"
            and cfg.moe_impl == "sorted" and cfg.experts_held
            and cfg.n_layers % 2 == 0 and not cfg.index_topk):
        raise architecture.ManifestError(
            "longcat-flash is pairs of latent-attention blocks with a dense "
            "MLP each and a held share of drop-free experts on a shortcut "
            "beside them, routed by a softmax over every output, zero "
            "experts among them; the program's config has "
            f"kv_lora_rank={cfg.kv_lora_rank}, "
            f"moe_shortcut={cfg.moe_shortcut}, "
            f"zero_experts={cfg.zero_experts}, "
            f"router_score={cfg.router_score!r}, moe_impl={cfg.moe_impl!r}, "
            f"experts_held={cfg.experts_held}, n_layers={cfg.n_layers}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
