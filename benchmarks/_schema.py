"""Canonical benchmark-record schema: the per-PR perf trajectory.

Every module in :mod:`benchmarks` emits a list of :class:`Record`s from its
``run()``; :mod:`benchmarks.run` serializes them into one
``BENCH_<module>.json`` per module at the repo root. Those artifacts are the
repo's perf trajectory — committed snapshots live under
``benchmarks/baselines/`` and :mod:`benchmarks.compare` diffs a fresh run
against them with per-metric tolerance bands (the CI ``bench-trajectory``
job gates on the result).

A record is one metric observation:

- ``name``       unique within its module (``paged_decode_gather_jnp_b8``),
- ``value``      a finite number,
- ``unit``       explicit ("tok/s", "us/token", "bytes", "count", ...) — the
                 legacy CSV had a single ``us_per_call`` header that silently
                 mixed µs/call and µs/token; the unit now travels with every row,
- ``direction``  how to gate it:
                   * ``higher`` / ``lower`` — wall-clock-ish, better in that
                     direction, compared with a relative tolerance band,
                   * ``exact``  — deterministic accounting (update counts,
                     sync events, bytes); any change is a regression,
                   * ``info``   — recorded for the trajectory, never gated,
- ``derived``    the human-readable summary string (what the CSV shows),
- ``context``    free-form dict of supporting numbers (percentile method,
                 per-batch breakdowns, config knobs).

Schema changes bump ``SCHEMA_VERSION``; :func:`validate` is the single
source of truth for well-formedness (no external jsonschema dependency).
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional

SCHEMA_VERSION = 1
DIRECTIONS = ("higher", "lower", "exact", "info")

# repo root = parent of the benchmarks/ package dir, independent of cwd
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DIR = os.path.join(REPO_ROOT, "benchmarks", "baselines")

CSV_HEADER = "name,value,unit,derived"


@dataclass
class Record:
    """One metric observation (see module docstring for field semantics)."""

    name: str
    value: float
    unit: str
    direction: str = "info"
    derived: str = ""
    context: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.direction not in DIRECTIONS:
            raise ValueError(f"{self.name}: bad direction {self.direction!r}")
        self.value = float(self.value)
        if not math.isfinite(self.value):
            raise ValueError(f"{self.name}: non-finite value {self.value!r}")

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "value": self.value,
            "unit": self.unit,
            "direction": self.direction,
            "derived": self.derived,
            "context": self.context,
        }

    def csv_row(self) -> str:
        # derived strings may contain commas; they live in the last column so
        # consumers split with maxsplit=3
        return f"{self.name},{self.value:g},{self.unit},{self.derived}"


def validate(payload: Dict[str, Any]) -> None:
    """Raise ``ValueError`` unless ``payload`` is a well-formed BENCH artifact."""
    if not isinstance(payload, dict):
        raise ValueError("BENCH payload must be a dict")
    if payload.get("schema_version") != SCHEMA_VERSION:
        raise ValueError(
            f"schema_version {payload.get('schema_version')!r} != {SCHEMA_VERSION}"
        )
    if not isinstance(payload.get("module"), str) or not payload["module"]:
        raise ValueError("missing module name")
    metrics = payload.get("metrics")
    if not isinstance(metrics, list):
        raise ValueError("metrics must be a list")
    seen = set()
    for m in metrics:
        if not isinstance(m, dict):
            raise ValueError("metric entries must be dicts")
        for key in ("name", "value", "unit", "direction"):
            if key not in m:
                raise ValueError(f"metric missing {key!r}: {m}")
        if m["direction"] not in DIRECTIONS:
            raise ValueError(f"{m['name']}: bad direction {m['direction']!r}")
        if not isinstance(m["value"], (int, float)) or not math.isfinite(m["value"]):
            raise ValueError(f"{m['name']}: non-finite value {m['value']!r}")
        if m["name"] in seen:
            raise ValueError(f"duplicate metric name {m['name']!r}")
        seen.add(m["name"])
    if not isinstance(payload.get("env"), dict):
        raise ValueError("missing env fingerprint")


def bench_payload(
    module: str, records: Iterable[Record], env: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    payload = {
        "schema_version": SCHEMA_VERSION,
        "module": module,
        "env": env if env is not None else {},
        "metrics": [r.as_dict() for r in records],
    }
    validate(payload)
    return payload


def bench_path(module: str, out_root: str = REPO_ROOT) -> str:
    return os.path.join(out_root, f"BENCH_{module}.json")


def write_bench(
    module: str,
    records: Iterable[Record],
    out_root: str = REPO_ROOT,
    env: Optional[Dict[str, Any]] = None,
) -> str:
    path = bench_path(module, out_root)
    os.makedirs(out_root, exist_ok=True)
    with open(path, "w") as f:
        json.dump(bench_payload(module, records, env), f, indent=2)
        f.write("\n")
    return path


def load_bench(path: str) -> Dict[str, Any]:
    with open(path) as f:
        payload = json.load(f)
    validate(payload)
    return payload


def print_csv(records: Iterable[Record], header: bool = True) -> None:
    """The standalone ``python -m benchmarks.<module>`` output path."""
    if header:
        print(CSV_HEADER)
    for r in records:
        print(r.csv_row())


def as_records(rows: Iterable[Any]) -> List[Record]:
    """Coerce an iterable of Records (typed path) — kept as a seam so a
    module failure surfaces as ``TypeError`` here, not deep in run.py."""
    out = []
    for r in rows:
        if not isinstance(r, Record):
            raise TypeError(f"benchmark modules must yield Record, got {r!r}")
        out.append(r)
    return out
