"""Operations and bytes that the algorithm needs, computed from shapes.

Counts are of the mathematics, not of what a program happens to execute:
no recompute, causal attention over the positions at or before each query. Configuration dictionaries use the published
``config.json`` keys.
"""
from __future__ import annotations

from bench.reference.qwen2 import dims


def matmul_params_per_layer(c: dict) -> int:
    m = dims(c)
    d, hq, hkv, hd, f = m["d"], m["hq"], m["hkv"], m["hd"], m["f"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def params_total(c: dict) -> int:
    """Every parameter: layers (matrices, biases, norm gains), the tied
    embedding once, the final norm."""
    m = dims(c)
    per_layer = matmul_params_per_layer(c) + (m["hq"] + 2 * m["hkv"]) * m["hd"] + 2 * m["d"]
    embed = m["v"] * m["d"] * (1 if c.get("tie_word_embeddings", True) else 2)
    return m["layers"] * per_layer + embed + m["d"]


def forward_flops_per_token(c: dict, context: float) -> float:
    """One token's forward: the layers' products, attention over ``context``
    keys (QK and PV, all query heads), and the head over the vocabulary."""
    m = dims(c)
    dense = 2 * (m["layers"] * matmul_params_per_layer(c) + m["d"] * m["v"])
    attn = m["layers"] * 4 * m["hq"] * m["hd"] * context
    return dense + attn


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward (3x forward) per trained token of a causal
    sequence of ``seq`` tokens: the mean query sees (seq + 1) / 2 keys."""
    return 3.0 * forward_flops_per_token(c, (seq + 1) / 2.0)
