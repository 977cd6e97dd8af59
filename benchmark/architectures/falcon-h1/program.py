"""The program's side of the ``falcon-h1`` architecture (Falcon-H1: every
block runs grouped-query attention with RoPE and a Mamba-2 (SSD) mixer side
by side on one normed input, the SSD state a sequence in the page pool beside
the layer's own K and V; a dense SwiGLU MLP, RMSNorms, an untied head, the
model's fixed multipliers): the config object for a configuration file, held
against every key of the file that says something about the model's
equations, its widths, its depth and its multipliers. The only file of the
architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    a_in, a_out, key = cfg.attn_multipliers
    s_in, s_out, *blocks = cfg.ssd_multipliers
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.mlp_dim, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "hidden_act": cfg.hidden_act,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": False, "mlp_bias": False,
        "mamba_proj_bias": False, "projectors_bias": False,
        "mamba_conv_bias": True, "mamba_rms_norm": True,
        "mamba_norm_before_gate": False, "rope_scaling": None,
        "mamba_d_conv": cfg.conv_taps, "mamba_d_head": cfg.ssd_head_dim,
        "mamba_d_ssm": cfg.ssd_inner, "mamba_d_state": cfg.ssd_state,
        "mamba_n_groups": cfg.ssd_groups, "mamba_n_heads": cfg.ssd_heads,
        "mamba_chunk_size": cfg.ssd_chunk,
        "embedding_multiplier": cfg.embed_multiplier,
        "lm_head_multiplier": cfg.head_multiplier,
        "attention_in_multiplier": a_in, "attention_out_multiplier": a_out,
        "key_multiplier": key, "ssm_in_multiplier": s_in,
        "ssm_out_multiplier": s_out, "ssm_multipliers": blocks,
        "mlp_multipliers": list(cfg.mlp_multipliers),
    }
    architecture.agree(conf, same)
    if not (set(cfg.kinds) == {"parallel"} and cfg.norm_kind == "rms"
            and not cfg.is_moe and not cfg.qk_norm and cfg.use_rope
            and not cfg.attn_bias):
        raise architecture.ManifestError(
            "falcon-h1 is attention beside an SSD mixer in EVERY block, "
            "under RMSNorms, rotated, dense; the program's config has "
            f"layer_kinds={cfg.layer_kinds}, norm_kind={cfg.norm_kind!r}, "
            f"num_experts={cfg.num_experts}, use_rope={cfg.use_rope}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
