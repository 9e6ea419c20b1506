"""The harness: refuses without a chip or without the program, and finds
configurations, mixes and per-layer readers by name alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import common

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "train-sebs", "--seed",
                           "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_only_host():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_named_file_exists():
    spec = common.load_spec(ROOT)
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert common.load_config(c["name"])["source"] == c["source"] + "/blob/main/config.json"
    for w in spec["workloads"]:
        mix = common.load_traffic(w["traffic"])
        assert (common.BENCH / "drivers" / f"{mix['driver']}.py").is_file()
    for m in spec["per_layer"]:
        assert callable(common.load_reader(m["name"]))
        assert set(m["workloads"]) <= {w["name"] for w in spec["workloads"]}


def test_a_new_cell_is_files_and_an_entry(tmp_path, spec, run_cell):
    """A configuration, a mix and a reader added as new files, plus entries in
    BENCHMARK.json, run with no edit to any existing file."""
    data = tmp_path / "data"
    shutil.copytree(DATA, data)
    c = json.loads((data / "configs" / "tiny-train.json").read_text())
    (data / "configs" / "tiny-train-b.json").write_text(json.dumps(dict(c, num_hidden_layers=1)))
    mix = json.loads((data / "traffic" / "tiny-ladder.json").read_text())
    (data / "traffic" / "tiny-ladder-b.json").write_text(json.dumps(dict(mix, seq=16)))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "tokens_seen.b.py").write_text(
        "def read(run):\n    return run.tokens_per_s\n")
    spec["configs"].append({"name": "tiny-train-b", "source": "test",
                            "file": "x", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "train-b", "config": "tiny-train-b",
                              "traffic": "tiny-ladder-b", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "tokens_seen.b", "unit": "tokens/s", "better": "higher",
                              "source": "host_clock", "layer": "trainer",
                              "moves": "train_tokens_per_s", "workloads": ["train-b"]})
    spec["end_to_end"][0]["workloads"].append("train-b")
    assert [m["name"] for m in common.metrics_for(spec, "per_layer", "train-b")] == ["tokens_seen.b"]
    assert common.load_reader("tokens_seen.b", bench=tmp_path)(
        type("R", (), {"tokens_per_s": 3.0})) == 3.0

    from bench import run

    rc = run.main(["--workload", "train-b", "--seed", "9", "--seconds", "1", "--trace", "0"],
                  require_tpu=False, spec=spec, data_dir=data)
    assert rc == 0
