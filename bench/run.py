"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell, its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``), and hands them to the driver that the
mix names (``bench/drivers/<driver>.py``). The driver sets up (weights
from the seed, every shape warmed), measures for ``--seconds``, then checks
what the timed path produced against the plain reference. With
``--trace 1`` the window runs under the JAX profiler and each of the
cell's per-layer metrics is read by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), with ``checks`` last: each compared number and its limit.
The same comparisons are the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program (``src/repro``) is missing.
JAX's compilation cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def _configure_jax(root: Path):
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def make_ctx(args, cell, devices, root: Path = ROOT, data_dir=None, traffic=None,
             stand_in=None):
    """What a driver gets: the cell, its configuration and mix, the seed,
    the window, the chips it uses and the compile counter. ``stand_in``, where
    given, takes the program's place in the check (``bench/control.py``)."""
    from bench import common

    used = devices[: cell["chips"]]
    data = Path(data_dir) if data_dir else common.BENCH
    ctx = SimpleNamespace(
        workload=args.workload, cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS, devices=used,
        config=common.load_config(cell["config"], data),
        traffic=traffic or common.load_traffic(cell["traffic"], data),
        compiles=common.CompileCounter().install(),
        out_dir=root / ".bench_out" / f"{args.workload}-{args.seed}",
        say=say, stand_in=stand_in,
    )
    ctx.peaks = (common.peaks_for(used[0].device_kind) if used[0].platform == "tpu"
                 else {"bf16_flops": math.nan, "hbm_bytes_per_s": math.nan})
    return ctx


def main(argv=None, *, require_tpu: bool = True, spec=None, data_dir=None,
         root: Path = ROOT, stand_in=None) -> int:
    args = _parse(argv)
    if not (root / "src" / "repro").is_dir() or not (root / "BENCHMARK.json").is_file():
        say(f"bench: no program (src/repro) or BENCHMARK.json under {root}")
        return 2
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import common

    spec = spec or common.load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        say(f"bench: no workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]

    jax = _configure_jax(root)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        say(f"bench: no TPU: JAX found {devices[0].platform}")
        return 2
    if len(devices) < cell["chips"]:
        say(f"bench: {args.workload} needs {cell['chips']} chips, JAX found {len(devices)}")
        return 2
    ctx = make_ctx(args, cell, devices, root, data_dir, stand_in=stand_in)
    driver = common.load_driver(ctx.traffic["driver"])
    run = driver.run(ctx)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in common.metrics_for(spec, kind, args.workload):
        if args.trace:
            value = common.load_reader(m["name"])(run)
        else:
            value = run.e2e.get(m["name"])
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {name: {"value": value, "limit": limit} for name, value, limit in run.checks}
    correct = bool(run.ok) and all(c["value"] <= c["limit"] for c in checks.values())
    used = ctx.devices
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    say(f"bench: compiles inside the window: {run.window_compiles}")
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
