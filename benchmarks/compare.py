"""Perf-trajectory differ: gate a fresh benchmark run against a baseline.

Loads ``BENCH_<module>.json`` artifacts from two directories (typically the
committed ``benchmarks/baselines/`` vs a fresh run at the repo root),
matches metrics by name, and applies per-metric tolerance bands:

- ``exact``  metrics (schedule accounting: updates, sync events, bytes,
  token counts) must match to the last unit — any drift is a regression;
- ``higher`` / ``lower`` metrics (wall-clock: tok/s, latency, µs/call) get a
  relative band keyed on the unit class (default 25% — wide enough for CPU
  jitter under the pinned env of :mod:`benchmarks._env`, tight enough to
  catch a 30% throughput loss);
- ``info``   metrics are reported but never gate.

A metric present in the baseline but missing from the current run is a
regression too (silent coverage loss is exactly what the roofline
silent-zero bug looked like); new metrics are reported as additions.

Exit status: 0 = within tolerance, 1 = regressions found, 2 = usage/load
error.

Usage::

    PYTHONPATH=src python -m benchmarks.compare \
        --baseline benchmarks/baselines --current . \
        --modules table_comm,kernels

    # per-metric override (relative band):
    python -m benchmarks.compare --tolerance paged_decode_gather_jnp_b8=0.4
"""
from __future__ import annotations

import argparse
import glob
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from benchmarks._schema import REPO_ROOT, load_bench

BASELINE_DIR = os.path.join(REPO_ROOT, "benchmarks", "baselines")

# relative tolerance by unit class for higher/lower metrics; "exact" ignores
# this table entirely
_TIME_UNITS = {
    "tok/s", "samples/s", "us/call", "us/token", "us/sample", "us", "ms", "s",
}
_RATIO_UNITS = {"ratio", "corr", "frac"}
DEFAULT_REL_TOL = 0.25
RATIO_REL_TOL = 0.10
ERR_REL_TOL = 0.50  # kernel max-abs-err vs ref: order-of-magnitude gate


def default_tolerance(metric: Dict[str, Any]) -> float:
    if metric["unit"] in _RATIO_UNITS:
        return RATIO_REL_TOL
    if "err" in metric["unit"]:
        return ERR_REL_TOL
    if metric["unit"] in _TIME_UNITS:
        return DEFAULT_REL_TOL
    return DEFAULT_REL_TOL


def tolerance_for(metric: Dict[str, Any], overrides: Dict[str, float]) -> float:
    if metric["name"] in overrides:
        return overrides[metric["name"]]
    ctx = metric.get("context") or {}
    if isinstance(ctx.get("tolerance"), (int, float)):
        return float(ctx["tolerance"])
    return default_tolerance(metric)


def _regression(base: float, cur: float, direction: str, tol: float) -> bool:
    """True when ``cur`` regresses past the band. Improvements never gate."""
    if direction == "exact":
        # exact metrics are ints-in-float-clothing; allow repr noise only
        return abs(cur - base) > 1e-9 * max(1.0, abs(base))
    scale = max(abs(base), 1e-12)
    if direction == "higher":
        return cur < base - tol * scale
    if direction == "lower":
        return cur > base + tol * scale
    return False  # info


def diff_module(
    baseline: Dict[str, Any],
    current: Dict[str, Any],
    overrides: Dict[str, float],
) -> Tuple[List[str], List[str]]:
    """Returns (regressions, notes) for one module's pair of artifacts."""
    regressions, notes = [], []
    base_metrics = {m["name"]: m for m in baseline["metrics"]}
    cur_metrics = {m["name"]: m for m in current["metrics"]}
    mod = baseline["module"]
    for name, bm in base_metrics.items():
        cm = cur_metrics.get(name)
        if cm is None:
            regressions.append(f"{mod}/{name}: missing from current run "
                               f"(baseline={bm['value']:g} {bm['unit']})")
            continue
        if cm["unit"] != bm["unit"]:
            regressions.append(
                f"{mod}/{name}: unit changed {bm['unit']!r} -> {cm['unit']!r}"
            )
            continue
        tol = tolerance_for(bm, overrides)
        delta = cm["value"] - bm["value"]
        rel = delta / bm["value"] if bm["value"] else float("inf") if delta else 0.0
        line = (f"{mod}/{name}: {bm['value']:g} -> {cm['value']:g} {bm['unit']} "
                f"({rel:+.1%})")
        if _regression(bm["value"], cm["value"], bm["direction"], tol):
            if bm["direction"] == "exact":
                regressions.append(line + " [exact metric drifted]")
            else:
                regressions.append(
                    line + f" [outside {bm['direction']}-is-better band, tol {tol:.0%}]"
                )
        elif delta:
            notes.append(line)
    for name in cur_metrics.keys() - base_metrics.keys():
        notes.append(f"{mod}/{name}: new metric (no baseline)")
    return regressions, notes


def _modules_in(directory: str) -> Dict[str, str]:
    return {
        os.path.basename(p)[len("BENCH_"):-len(".json")]: p
        for p in sorted(glob.glob(os.path.join(directory, "BENCH_*.json")))
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=BASELINE_DIR)
    ap.add_argument("--current", default=REPO_ROOT)
    ap.add_argument("--modules", default=None,
                    help="comma-separated; default = modules present in --current")
    ap.add_argument("--tolerance", action="append", default=[],
                    metavar="NAME=REL", help="per-metric relative band override")
    ap.add_argument("--allow-missing-baseline", action="store_true",
                    help="skip modules with no baseline artifact instead of failing")
    args = ap.parse_args(argv)

    overrides: Dict[str, float] = {}
    for spec in args.tolerance:
        name, _, val = spec.partition("=")
        try:
            overrides[name] = float(val)
        except ValueError:
            print(f"bad --tolerance {spec!r}", file=sys.stderr)
            return 2

    cur_files = _modules_in(args.current)
    base_files = _modules_in(args.baseline)
    names = args.modules.split(",") if args.modules else sorted(cur_files)
    if not names:
        print(f"no BENCH_*.json under {args.current}", file=sys.stderr)
        return 2

    all_regressions: List[str] = []
    for name in names:
        if name not in cur_files:
            all_regressions.append(f"{name}: no BENCH_{name}.json in {args.current}")
            continue
        if name not in base_files:
            msg = f"{name}: no baseline in {args.baseline}"
            if args.allow_missing_baseline:
                print(f"SKIP  {msg}")
                continue
            all_regressions.append(msg + " (pass --allow-missing-baseline for new modules)")
            continue
        try:
            base = load_bench(base_files[name])
            cur = load_bench(cur_files[name])
        except (ValueError, OSError) as e:
            all_regressions.append(f"{name}: artifact load failed: {e}")
            continue
        regressions, notes = diff_module(base, cur, overrides)
        status = "FAIL" if regressions else "ok"
        print(f"{status:4}  {name}: {len(base['metrics'])} baseline metrics, "
              f"{len(regressions)} regressions, {len(notes)} drifts within band")
        for line in notes:
            print(f"      ~ {line}")
        for line in regressions:
            print(f"      ! {line}")
        all_regressions.extend(regressions)

    if all_regressions:
        print(f"\n{len(all_regressions)} perf regression(s) vs {args.baseline}",
              file=sys.stderr)
        return 1
    print("\nperf trajectory within tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
