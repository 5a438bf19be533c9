"""Operations and bytes that the algorithms need, computed from shapes.

The per-layer readers divide these by device or host time; nothing here
reads a clock or the program.
"""
from __future__ import annotations

import re

import numpy as np

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text`` (``s32[4,128]``)."""
    total = 0
    for dt, dims in _SHAPE.findall(text):
        n = int(np.prod([int(x) for x in dims.split(",") if x] or [1]))
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def dense_lm_matmul_params(dims: dict) -> int:
    """Weights that one token meets in the layers' matrix multiplications."""
    d, f = dims["hidden_size"], dims["intermediate_size"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    return dims["num_hidden_layers"] * (d * q + 2 * d * kv + q * d + 3 * d * f)


def _attn_flops(dims: dict, positions: int) -> float:
    """Scores and weighted sum of one query over ``positions`` keys."""
    return 4.0 * dims["num_hidden_layers"] * dims["num_attention_heads"] \
        * dims["head_dim"] * positions


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Useful FLOPs of prefilling a prompt: every true prompt token through
    the layers, causal attention, and the logits of the last position."""
    p = prompt_len
    return 2.0 * dense_lm_matmul_params(dims) * p \
        + _attn_flops(dims, p * (p + 1) // 2) \
        + 2.0 * dims["hidden_size"] * dims["vocab_size"]


def decode_flops(dims: dict, context: int) -> float:
    """Useful FLOPs of one decoded token whose query sees ``context``
    positions (itself included)."""
    return 2.0 * dense_lm_matmul_params(dims) + _attn_flops(dims, context) \
        + 2.0 * dims["hidden_size"] * dims["vocab_size"]
