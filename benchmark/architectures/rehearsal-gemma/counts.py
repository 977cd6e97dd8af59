"""Test-only: operations and bytes the rehearsal's Gemma-shaped decoder
needs. The head is the embedding table: one array, counted once as a
parameter, multiplied by every token and read by every decode step.
"""

from __future__ import annotations


def _layer_matmul_params(c: dict) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    attn = 2 * d * c["num_attention_heads"] * dh \
        + 2 * d * c["num_key_value_heads"] * dh
    return attn + 3 * d * c["intermediate_size"]


def _matmul_params(c: dict) -> int:
    return c["num_hidden_layers"] * _layer_matmul_params(c) \
        + c["hidden_size"] * c["vocab_size"]


def _attention_flops_causal(c: dict, n: int) -> float:
    return 4.0 * c["num_attention_heads"] * c["head_dim"] \
        * (n * (n + 1) / 2) * c["num_hidden_layers"]


def params_total(c: dict) -> int:
    d = c["hidden_size"]
    return c["num_hidden_layers"] * (_layer_matmul_params(c) + 2 * d) \
        + c["vocab_size"] * d + d


def prefill_flops(c: dict, prompt_len: int) -> float:
    return 2.0 * _matmul_params(c) * prompt_len \
        + _attention_flops_causal(c, prompt_len)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 6.0 * _matmul_params(c) \
        + 3.0 * _attention_flops_causal(c, seq_len) / seq_len


def decode_weight_bytes(c: dict, bytes_per_param: int) -> float:
    d = c["hidden_size"]
    return float(bytes_per_param) * (
        c["num_hidden_layers"] * (_layer_matmul_params(c) + 2 * d)
        + d * c["vocab_size"] + d)


def kv_bytes_per_token(c: dict, bytes_per_value: int) -> int:
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"] * bytes_per_value
