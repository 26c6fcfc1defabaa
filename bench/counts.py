"""Operations and bytes the served model needs, from its published sizes.

These count what the algorithm needs, not what an implementation does:
attention reads the keys and values of the positions a token attends to,
and nothing of the pages or rows a kernel walks without needing them.
Key/value entries and activations are bfloat16 (2 bytes), as served.
"""
from __future__ import annotations

KV_BYTES = 2
ACT_BYTES = 2


def layer_params(shape: dict) -> int:
    """Matmul weights of one block."""
    D, H, K, dh, F = (shape["hidden_size"], shape["num_attention_heads"],
                      shape["num_key_value_heads"], shape["head_dim"],
                      shape["intermediate_size"])
    return 2 * D * H * dh + 2 * D * K * dh + 3 * D * F


def attention_flops(shape: dict, keys: int) -> float:
    """QK^T and PV over ``keys`` (query, key) pairs summed over tokens,
    all layers."""
    return (4.0 * keys * shape["num_attention_heads"] * shape["head_dim"]
            * shape["num_hidden_layers"])


def decode_attention_bytes(shape: dict, keys: int, tokens: int) -> float:
    """Keys and values read for ``keys`` attended positions, plus each
    decoded token's query read and output written, all layers."""
    L, H, K, dh = (shape["num_hidden_layers"], shape["num_attention_heads"],
                   shape["num_key_value_heads"], shape["head_dim"])
    return float(L * (keys * K * dh * 2 * KV_BYTES
                      + tokens * 2 * H * dh * ACT_BYTES))


def model_flops(shape: dict, tokens: int, keys: int,
                logit_rows: int) -> float:
    """Forward FLOPs of ``tokens`` tokens attending ``keys`` positions in
    all, with output logits computed for ``logit_rows`` of them."""
    return (2.0 * layer_params(shape) * shape["num_hidden_layers"] * tokens
            + attention_flops(shape, keys)
            + 2.0 * shape["hidden_size"] * shape["vocab_size"] * logit_rows)
