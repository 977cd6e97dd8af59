"""The program's side of the ``phi4flash`` architecture (Phi-4-mini-flash-
reasoning, SambaY: Mamba-1 layers whose state a sequence lives in the page
pool, differential attention without position over window rings and ONE
full-attention layer whose pages the cross layers read, gated memory units,
LayerNorm, a dense MLP, a tied head): the config object for a configuration
file, held against every key of the file that says something about the
model's equations, its depth and its layers. The only file of the
architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture

# a layer's kind in the file's ``layer_types`` -> in the program's config
KINDS = {"mamba": "ssm", "sliding_attention": "window",
         "full_attention": "attention", "gmu": "gmu",
         "cross_attention": "cross"}


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's
    own), then held against the file, so the two cannot drift apart."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {
        "hidden_size": cfg.hidden, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.mlp_dim,
        "layer_norm_eps": cfg.norm_eps,
        "sliding_window": cfg.attn_window,
        "tie_word_embeddings": cfg.tie_embeddings,
        "mlp_bias": False, "lm_head_bias": False,
        "mb_per_layer": 2,
        "d_state": cfg.ssm_state, "d_conv": cfg.conv_taps,
        "dt_rank": cfg.ssm_dt_rank,
        "layer_types": [k for kind in cfg.kinds
                        for k, v in KINDS.items() if v == kind],
    }
    architecture.agree(conf, same)
    if not (cfg.diff_attention and cfg.attn_bias and not cfg.use_rope
            and cfg.norm_kind == "layer" and not cfg.is_moe
            and cfg.hidden_act == "silu"
            and cfg.head_dim * cfg.n_heads == cfg.hidden
            and cfg.ssm_inner == conf["expand"] * cfg.hidden
            and cfg.stateless_tail
            == cfg.layers_of("gmu") + cfg.layers_of("cross")):
        raise architecture.ManifestError(
            "phi4flash is Mamba-1 layers beside differential attention "
            "without position under LayerNorms, a dense MLP, and a "
            "cross-decoder that keeps no state; the program's config has "
            f"layer_kinds={cfg.layer_kinds}, diff_attention="
            f"{cfg.diff_attention}, use_rope={cfg.use_rope}, norm_kind="
            f"{cfg.norm_kind!r}, ssm_inner={cfg.ssm_inner}")
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes``: the program's own rules for its
    decoder on ``mesh`` (no cell trains this architecture)."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
