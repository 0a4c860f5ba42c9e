"""Operations and bytes that one decode step needs, counted from shapes.

These count what the algorithm needs, not what today's program moves: a
step reads every weight once, reads the keys and values of the positions
below each active slot's cache length, and writes one new key and value row
per active slot. A program that copies its whole cache per step moves more;
one that stops doing so reads closer to the roofline.
"""
from __future__ import annotations

from typing import Dict, Iterable

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_weights(a: Dict) -> int:
    """Weights that take part in a matmul per token: every projection of
    every layer and the (tied) output head."""
    d, f, hd = a["hidden_size"], a["intermediate_size"], a["head_dim"]
    hq, hkv = a["num_attention_heads"], a["num_key_value_heads"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return a["num_hidden_layers"] * per_layer + a["vocab_size"] * d


def norm_weights(a: Dict) -> int:
    return (2 * a["num_hidden_layers"] + 1) * a["hidden_size"]


def token_flops(a: Dict, ctx: int) -> int:
    """FLOPs to decode one token that attends to ``ctx`` positions: two per
    matmul weight, and QK^T plus AV over the context in every layer."""
    attn = 4 * a["num_hidden_layers"] * a["num_attention_heads"] \
        * a["head_dim"] * ctx
    return 2 * matmul_weights(a) + attn


def kv_row_bytes(a: Dict, dtype: str) -> int:
    """Bytes of one position's keys and values over all layers."""
    return 2 * a["num_hidden_layers"] * a["num_key_value_heads"] \
        * a["head_dim"] * BYTES[dtype]


def step_bytes(a: Dict, dtype: str, cache_lens: Iterable[int]) -> int:
    """Bytes one decode step needs: the weights once (norm scales in
    float32), the cache below each active slot's length, one new row per
    active slot."""
    lens = list(cache_lens)
    row = kv_row_bytes(a, dtype)
    return (matmul_weights(a) * BYTES[dtype] + norm_weights(a) * 4
            + row * sum(lens) + row * len(lens))


def step_flops(a: Dict, cache_lens: Iterable[int]) -> int:
    """FLOPs of one decode step over its active slots (each attends to its
    cache and its new row)."""
    return sum(token_flops(a, n + 1) for n in cache_lens)
