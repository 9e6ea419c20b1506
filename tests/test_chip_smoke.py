"""chip_smoke.py refuses to report success anywhere but on a TPU inside a
checkout, and the compile-cache helper leaves JAX_COMPILATION_CACHE_DIR alone
or falls back to a fixed path in the checkout."""
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_tpu():
    res = _run_smoke(ROOT, ROOT / "chip_smoke.py")
    assert res.returncode != 0, res.stdout
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr


def test_chip_smoke_fails_outside_checkout(tmp_path):
    script = shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_smoke(tmp_path, script)
    assert res.returncode != 0, res.stdout
    assert '"ok": true' not in res.stdout


def _fake_jax(monkeypatch):
    updates = []
    fake = types.SimpleNamespace(
        config=types.SimpleNamespace(update=lambda name, value: updates.append((name, value)))
    )
    monkeypatch.setattr(compile_cache, "jax", fake)
    return updates


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    updates = _fake_jax(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, tmp_path):
    updates = _fake_jax(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == expected
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == expected
    assert updates == [("jax_compilation_cache_dir", expected)] * 2
