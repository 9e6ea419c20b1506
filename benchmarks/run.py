"""Benchmark harness — one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig2,fig3,...]

Per module this

- prints ``name,value,unit,derived`` CSV (the unit travels with every row —
  µs/call and µs/token no longer share a column under one header),
- writes the canonical ``BENCH_<module>.json`` perf-trajectory artifact at
  the repo root (schema: :mod:`benchmarks._schema`; diffed against
  ``benchmarks/baselines/`` by :mod:`benchmarks.compare`),
- keeps the detailed human-readable JSON/markdown under
  ``benchmarks/results/``.

Env hygiene (:mod:`benchmarks._env`) is applied before jax is imported so
CPU numbers are stable enough to gate on.
"""
from __future__ import annotations

from benchmarks import _env

_env.apply()  # must precede any jax-importing module below

import argparse
import sys
import time
import traceback
from pathlib import Path
from typing import List, Optional

from benchmarks import _schema
from benchmarks import adaptive_sebs, fig1_util, fig2_optimal_batch, fig3_stagewise
from benchmarks import kernel_bench, roofline_report
from benchmarks import table1_updates, table_comm

MODULES = {
    "fig1": fig1_util,
    "fig2": fig2_optimal_batch,
    "fig3": fig3_stagewise,
    "table1": table1_updates,
    "table_comm": table_comm,
    "kernels": kernel_bench,
    "roofline": roofline_report,
    "adaptive": adaptive_sebs,
}

# the CI bench-trajectory subset: cheap enough for every PR, covers comm
# accounting and kernel timings
CHEAP_SUBSET = ("table_comm", "kernels")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument("--out-root", default=_schema.REPO_ROOT,
                    help="directory for BENCH_<module>.json artifacts")
    ap.add_argument("--allow-missing", action="store_true",
                    help="let roofline_report degrade to an explicit skip "
                         "instead of failing when its input artifacts are absent")
    args = ap.parse_args(argv)
    if args.only is not None:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        if not names:
            raise SystemExit(f"--only {args.only!r} names no modules; "
                             f"known: {sorted(MODULES)}")
        dupes = sorted({n for n in names if names.count(n) > 1})
        if dupes:
            raise SystemExit(f"--only lists module(s) twice: {dupes} "
                             "(each module writes one BENCH_<module>.json)")
    else:
        names = list(MODULES)
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        raise SystemExit(f"unknown benchmark module(s): {unknown}; "
                         f"known: {sorted(MODULES)}")
    out_root = Path(args.out_root)
    if out_root.exists() and not out_root.is_dir():
        raise SystemExit(f"--out-root {out_root} exists and is not a directory")
    out_root.mkdir(parents=True, exist_ok=True)
    roofline_report.ALLOW_MISSING = roofline_report.ALLOW_MISSING or args.allow_missing
    env = _env.fingerprint()
    print(_schema.CSV_HEADER)
    failures = []
    for name in names:
        t0 = time.time()
        try:
            records = _schema.as_records(MODULES[name].run())
            for rec in records:
                print(rec.csv_row(), flush=True)
            path = _schema.write_bench(name, records, out_root, env)
            print(f"# wrote {path}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"{name},0,none,FAILED: {e!r}", flush=True)
            traceback.print_exc(limit=6)
        print(f"# {name} took {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
