"""The program's side of the ``nemotron-h`` architecture (Nemotron-3-Super's
stack: every published layer ONE sublayer, a Mamba-2 mixer, an attention
without position or an expert layer; sigmoid-routed experts that work behind
a latent projection, of which ONE CHIP'S SHARE is held, beside a shared
expert; squared-ReLU MLPs of two matrices; an untied head over a slice of the
vocabulary): the config object for a configuration file, held against every
key of the file that says something about the model's equations or the
depth, the experts and the vocabulary held. The only file of the
architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart. The
    file counts PUBLISHED layers (``num_hidden_layers``, one sublayer each,
    ``layers_held`` in the pattern's letters); the program counts BLOCKS, a
    mixer or an attention with the expert layer behind it: the overrides'
    ``n_layers``, ``layer_kinds`` and ``ffn_free`` (the blocks without a
    feed-forward part) are held against ``layers_held`` read as blocks
    (``config.blocks_of``). The experts
    held are ``n_routed_experts`` from ``expert_offset`` on
    (``n_routed_experts_published`` is the experts the router scores). The
    published ONE shared expert of ``moe_shared_expert_intermediate_size`` is
    the program's ``shared_experts`` of ``moe_mlp_dim`` in one matrix."""
    from kubeflow_tpu.models.config import blocks_of, preset
    from kubeflow_tpu.models.decoder import layer_groups

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    if (cfg.kinds, cfg.ffn_free) != blocks_of(conf["layers_held"]):
        raise architecture.ManifestError(
            f"layers_held {conf['layers_held']!r} is the blocks "
            f"{blocks_of(conf['layers_held'])}; the program's config has "
            f"{(cfg.kinds, cfg.ffn_free)}")
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": len(conf["layers_held"]),
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.mlp_dim,
        "moe_intermediate_size": cfg.expert_mlp_dim,
        "moe_latent_size": cfg.moe_latent_dim,
        "moe_shared_expert_intermediate_size":
            cfg.shared_experts * cfg.expert_mlp_dim,
        "n_shared_experts": 1,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.router_scale,
        "norm_topk_prob": cfg.router_norm_topk,
        "n_group": 1, "topk_group": 1,      # no group limit on the choice
        "mlp_hidden_act": cfg.hidden_act, "mamba_hidden_act": "silu",
        "layer_norm_epsilon": cfg.norm_eps, "norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": False, "mlp_bias": False, "use_bias": False,
        "mamba_proj_bias": False, "use_conv_bias": True,
        "conv_kernel": cfg.conv_taps, "mamba_head_dim": cfg.ssd_head_dim,
        "mamba_num_heads": cfg.ssd_heads, "ssm_state_size": cfg.ssd_state,
        "n_groups": cfg.ssd_groups, "chunk_size": cfg.ssd_chunk,
        "num_nextn_predict_layers": 0,      # the prediction module: unbuilt
    }
    architecture.agree(conf, same)
    if not (set(cfg.kinds) <= {"ssd", "attention"} and not cfg.use_rope
            and cfg.router_score == "sigmoid" and cfg.moe_impl == "sorted"
            and cfg.experts_held and not cfg.qk_norm and not cfg.attn_bias
            and cfg.norm_kind == "rms"):
        raise architecture.ManifestError(
            "nemotron-h is blocks of a Mamba-2 mixer or an attention without "
            "position, under RMSNorms, with a held share of drop-free "
            "sigmoid-routed experts behind a latent projection; the "
            f"program's config has layer_kinds={cfg.layer_kinds}, "
            f"use_rope={cfg.use_rope}, router_score={cfg.router_score!r}, "
            f"moe_impl={cfg.moe_impl!r}, experts_held={cfg.experts_held}")
    # the tree ``weights.py`` builds is the program's groups, block for block
    want = [(name, list(zip(g.kinds, g.fed)))
            for name, g, _ in layer_groups(cfg)]
    weights = architecture.part(conf, "weights")
    have = [(name, [({"attn": "attention"}.get(k, k), fed)
                    for k, fed in blocks])
            for name, blocks in weights.groups_of(
                weights.blocks_of(conf["layers_held"]))]
    if want != have:
        raise architecture.ManifestError(
            f"layers_held {conf['layers_held']!r}: the program groups its "
            f"blocks as {want}, weights.py as {have}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
