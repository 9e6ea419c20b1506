"""The check's control and a planted fault, at a cell's own size, on the chip.

    python3 bench/control.py --workload train-sebs --seeds 11 12 13

For each seed, two whole runs of the cell through ``bench/run.py``'s own
comparison, each with a stand-in in the program's place:

- ``float8``: the control, the float32 reference at the nearest lower
  precision (its products' operands rounded to float8 e4m3, since the
  configuration computes in bfloat16);
- ``half_batch``: the reference with half of each microbatch left out, the
  mean taken over the rest.

Each prints one JSON line: the seed, the stand-in, ``correct`` as the run
decided it, and every compared number beside its limit. (A state left
unchanged reads 1 on ``grad_gap`` and ``update_gap`` by their definition
and needs no run.) The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def stand_ins(mix: dict):
    """name -> the stand-in that ``bench/run.py`` puts in the program's place."""
    from bench import common
    from bench.reference import qwen2

    follow = common.load_driver(mix["driver"]).reference_follow
    return {
        "float8": lambda ctx, key, rows: follow(ctx.config, key, rows, ctx.traffic,
                                                mm=qwen2.fp8_mm),
        "half_batch": lambda ctx, key, rows: follow(ctx.config, key, rows, ctx.traffic,
                                                    batch_rows=ctx.traffic["b1"] // 2),
    }


def run_with(main, argv, stand_in) -> dict:
    """One run of ``main`` with ``stand_in``; its result line, parsed."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(argv, stand_in=stand_in)
    lines = out.getvalue().strip().splitlines()
    if rc != 0 or not lines:
        raise RuntimeError(f"run ended with {rc} and no result line")
    return json.loads(lines[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import common, run

    spec = common.load_spec(ROOT)
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    for seed in args.seeds:
        for name, stand_in in stand_ins(common.load_traffic(cell["traffic"])).items():
            res = run_with(run.main, ["--workload", args.workload, "--seed", str(seed),
                                      "--seconds", str(args.seconds), "--trace", "0"], stand_in)
            print(json.dumps({"seed": seed, "stand_in": name, "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
