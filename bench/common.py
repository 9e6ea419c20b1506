"""Helpers shared by the benchmark's drivers, readers and tests.

Nothing here imports the program under test (``src/repro``): seeds, the
percentile rule, file discovery by name, the compile counter and the
device description are the benchmark's own.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from typing import Any, Dict, Optional, Sequence

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# -- discovery by name ---------------------------------------------------------

def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    """``bench/configs/<name>.json``: the configuration as it is run."""
    return json.loads((bench / "configs" / f"{name}.json").read_text())


def load_traffic(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    """``bench/traffic/<name>.json``: one mix's parameters and limits."""
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (reader and driver names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name or f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, bench: Path = BENCH):
    return load_module(bench / "drivers" / f"{name}.py", f"bench_driver_{name}")


def load_reader(metric: str, bench: Path = BENCH):
    """``bench/metrics/<metric>.py`` exposes ``read(run) -> float | None``."""
    return load_module(bench / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_").replace("-", "_")).read


def metrics_for(spec: Dict[str, Any], kind: str, workload: str):
    """The ``end_to_end`` or ``per_layer`` entries that a cell reports: those
    that list it under ``workloads``, and those with no such list."""
    return [m for m in spec[kind] if workload in m.get("workloads", [workload])]


# -- seeds ---------------------------------------------------------------------

def seed_key(seed: int, stream: int):
    """A JAX key for one named stream of ``--seed``. Seeds beyond 32 bits
    are folded in as two 31-bit halves, so every whole number up to 2**62
    gives its own key."""
    import jax

    key = jax.random.key(stream)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed & 0x7FFFFFFF)


# -- statistics ----------------------------------------------------------------

def nearest_rank(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: ``sorted(xs)[ceil(q/100 * n) - 1]``; NaN on
    an empty sample. (Same rule as ``repro.obs.metrics.nearest_rank``.)"""
    n = len(xs)
    if n == 0:
        return float("nan")
    return sorted(xs)[max(math.ceil(q / 100.0 * n), 1) - 1]


def median(xs: Sequence[float]) -> float:
    return nearest_rank(xs, 50.0)


# -- device --------------------------------------------------------------------

def peaks_for(device_kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """Published peaks of one chip, keyed by ``device_kind``; a kind that is
    not in the table is an error, not a default."""
    table = json.loads((bench / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts backend compiles and their seconds (JAX's monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self
