"""The program's side of the ``mistral`` architecture (Mistral's dense and
Mixtral's sparse decoder, which the program runs through one
``DecoderConfig``): the config object for a configuration file, held against
the file's published sizes, and the shardings its trainer wants. The only
file of the architecture that imports ``kubeflow_tpu``.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    """The program's ``DecoderConfig`` from the configuration file: the
    preset it starts from plus every override (and ``extra``, a caller's own:
    a trainer's sequence length), then held against the published sizes in
    the same file, so the two cannot drift apart."""
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    same = {"hidden_size": cfg.hidden, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.mlp_dim, "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "num_local_experts": cfg.num_experts,
            "tie_word_embeddings": cfg.tie_embeddings}
    if cfg.num_experts:
        same["num_experts_per_tok"] = cfg.experts_per_token
    architecture.agree(conf, same, absent={"num_local_experts": 0})
    return cfg


def param_shardings(cfg, mesh, shapes):
    """One sharding per leaf of ``shapes`` (the architecture's parameter
    tree as shapes): the program's own rules for its decoder on ``mesh``."""
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
