"""Model FLOP/s utilisation of the Granite 4.0-H hybrid's training window,
in %: operations per trained token (``bench/flops_hybrid.py``) times tokens
per second, over the chips' bf16 peak."""
from __future__ import annotations

from typing import Optional

from bench import flops_hybrid
from bench.readers import train_only


def read(run) -> Optional[float]:
    if not train_only(run):
        return None
    per_token = flops_hybrid.train_flops_per_token(run.config, run.traffic["seq"])
    return 100.0 * per_token * run.tokens_per_s / (run.chips * run.peaks["bf16_flops"])
