"""Operations of the Granite 4.0-H hybrid per trained token, computed from
the configuration's shapes (published ``config.json`` keys).

Counts are of the mathematics, as ``bench/flops.py`` counts them: each
layer's matrix products, the Mamba2 state-space model as its token
recurrence (per head, the state update ``dt B x^T`` and the readout
``C h``, 2 N P operations each), causal attention over the positions at or
before each query, and the tied head over the vocabulary held. No
recompute; the conv, norms and elementwise work are left out.
"""
from __future__ import annotations

from bench.reference.granite_hybrid import dims


def mamba_matmul_params(c: dict) -> int:
    m = dims(c)
    d, d_in, n, h, f = m["d"], m["d_in"], m["n"], m["h"], m["f"]
    return d * (2 * d_in + 2 * n + h) + d_in * d + 3 * d * f


def attention_matmul_params(c: dict) -> int:
    m = dims(c)
    d, hq, hkv, hd, f = m["d"], m["hq"], m["hkv"], m["hd"], m["f"]
    return 2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f


def forward_flops_per_token(c: dict, context: float) -> dict:
    """One token's forward by part: ``mamba`` (products and the SSM),
    ``attention`` (products and scores over ``context`` keys), ``head``."""
    m = dims(c)
    ssm = 4 * m["n"] * m["p"] * m["h"]
    return {
        "mamba": m["n_mamba"] * (2 * mamba_matmul_params(c) + ssm),
        "attention": m["n_attn"] * (2 * attention_matmul_params(c)
                                    + 4 * m["hq"] * m["hd"] * context),
        "head": 2 * m["d"] * m["v"],
    }


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward (3x forward) per trained token of a causal
    sequence of ``seq`` tokens: the mean query sees (seq + 1) / 2 keys."""
    return 3.0 * sum(forward_flops_per_token(c, (seq + 1) / 2.0).values())
