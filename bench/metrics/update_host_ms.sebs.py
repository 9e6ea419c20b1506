"""Host time per update in which the chip waits on the host, in ms: the
mean over the window's updates of (``train.update`` minus its
``train.wait``) plus the ``train.after`` that follows it."""
from __future__ import annotations

from typing import Optional

from bench.readers import train_only


def read(run) -> Optional[float]:
    if not train_only(run):
        return None
    # spans are recorded as they close: an update's children before it,
    # its train.after next; an update missing either is left out
    host, wait, pending = [], None, None
    for ev in run.spans:
        name = ev["name"]
        if name == "train.wait":
            wait = ev["dur"]
        elif name == "train.update":
            pending = None if wait is None else ev["dur"] - wait
            wait = None
        elif name == "train.after" and pending is not None:
            host.append(pending + ev["dur"])
            pending = None
    return 1e3 * sum(host) / len(host) if host else None
