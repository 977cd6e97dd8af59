"""Test-only: the program's side of the CPU rehearsal's Gemma-shaped decoder
(the program's ``tiny-gemma`` preset: tied head, GeGLU, (1 + w) norms,
embedding scale, logit soft-cap). A fixture at tiny widths, added as files
alone; BENCHMARK.json names no configuration of it.
"""

from __future__ import annotations

from benchmark import architecture


def program_config(conf: dict, **extra):
    from kubeflow_tpu.models.config import preset

    prog = conf["program"]
    cfg = preset(prog["preset"], **{**prog["overrides"], **extra})
    architecture.agree(conf, {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.mlp_dim, "vocab_size": cfg.vocab_size,
        "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "hidden_activation": {"gelu": "gelu_pytorch_tanh"}.get(
            cfg.hidden_act, cfg.hidden_act),
        "final_logit_softcapping": cfg.logits_softcap,
        "tie_word_embeddings": cfg.tie_embeddings,
        # What the architecture always does, and the file cannot turn off.
        "norm_plus_one": cfg.norm_plus_one, "embed_scale": cfg.embed_scale,
        "num_local_experts": cfg.num_experts,
    }, absent={"norm_plus_one": True, "embed_scale": True,
               "tie_word_embeddings": True, "num_local_experts": 0})
    return cfg


def param_shardings(cfg, mesh, shapes):
    from kubeflow_tpu.models.decoder import decoder_param_specs
    from kubeflow_tpu.parallel.sharding import shard_params

    return shard_params(shapes, decoder_param_specs(cfg), mesh)
