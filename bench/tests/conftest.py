"""Tests of the benchmark itself: ``pytest bench/tests`` (CPU, test sizes).

A run here skips the harness's look for a chip (``require_tpu=False``)
and uses the test-size cells in ``bench/tests/data``.
"""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def spec():
    return json.loads((DATA / "spec.json").read_text())


@pytest.fixture
def run_cell(spec, capsys):
    """Run a test-size cell through ``bench/run.py``'s main; returns the
    parsed result line."""
    from bench import run

    def go(workload, seed=5, seconds=2.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)], require_tpu=False, spec=spec, data_dir=DATA)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0, out
        return json.loads(out[-1])

    return go
