"""Arithmetic shared by the per-layer readers in ``bench/metrics/``.

Each reader takes the run a driver returned and gives one number, or None
where the run holds nothing to read (another kind of cell, no trace, no
such span). A share of a roofline or of a peak is never made up as 0.
"""
from __future__ import annotations

from typing import Optional

from bench import common, flops


def train_only(run) -> bool:
    return getattr(run, "kind", None) == "train"


def train_mfu(run) -> Optional[float]:
    """Forward and backward operations per trained token, times tokens per
    second, over the chips' bf16 peak."""
    if not train_only(run):
        return None
    per_token = flops.train_flops_per_token(run.config, run.traffic["seq"])
    return 100.0 * per_token * run.tokens_per_s / (run.chips * run.peaks["bf16_flops"])


def stage_switch_ms(run) -> Optional[float]:
    """Mean over the window's stage changes of (first update of the new,
    higher stage) minus (that stage's median update), from ``train.update``
    spans. The return to stage 0 that starts each ladder pass is no stage
    change of SEBS and is left out."""
    if not train_only(run):
        return None
    ups = [ev for ev in run.spans if ev["name"] == "train.update"]
    by_stage = {}
    for ev in ups:
        by_stage.setdefault(ev["args"]["stage"], []).append(ev["dur"])
    med = {s: common.median(d) for s, d in by_stage.items()}
    firsts = [ev for prev, ev in zip(ups, ups[1:]) if ev["args"]["stage"] > prev["args"]["stage"]]
    if not firsts:
        return None
    return 1e3 * sum(ev["dur"] - med[ev["args"]["stage"]] for ev in firsts) / len(firsts)
