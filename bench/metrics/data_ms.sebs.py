"""Mean time of an update's data phase (``train.data``: the pipeline's
batch and its placement), in ms."""
from __future__ import annotations

from typing import Optional

from bench.readers import train_only


def read(run) -> Optional[float]:
    if not train_only(run):
        return None
    durs = [ev["dur"] for ev in run.spans if ev["name"] == "train.data"]
    return 1e3 * sum(durs) / len(durs) if durs else None
