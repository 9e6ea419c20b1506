"""The cell train-sebs-hybrid (``sebs_reshape``) end to end at test size on
the CPU, through ``bench/run.py``'s main and the test-size spec in
``data_sebs``: the sound run is correct, and each planted fault and the
float8 control are not."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import common, control, run

DATA = Path(__file__).resolve().parent / "data_sebs"
CHECKS = ["loss_gap", "grad_gap", "update_gap", "grad_gap.s1", "grad_gap.s2"]


@pytest.fixture
def spec_sebs():
    return json.loads((DATA / "spec.json").read_text())


@pytest.fixture
def run_hybrid(spec_sebs, capsys):
    def go(seed=5, stand_in=None):
        rc = run.main(["--workload", "train-sebs-hybrid", "--seed", str(seed), "--seconds", "1",
                       "--trace", "0"], require_tpu=False, spec=spec_sebs, data_dir=DATA,
                      stand_in=stand_in)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0, out
        return json.loads(out[-1])

    return go


def test_hybrid_sound_run_is_correct(run_hybrid):
    res = run_hybrid()
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == CHECKS


def test_hybrid_float8_control_is_not_correct(run_hybrid):
    stand_in = control.stand_ins(common.load_traffic("tiny-reshape", DATA))["float8"]
    res = run_hybrid(stand_in=stand_in)
    assert not res["correct"], res["checks"]


def test_hybrid_norm_before_gate_is_caught(run_hybrid, monkeypatch):
    """The gated norm in the order the program had before: normalise y,
    then multiply by silu(z)."""
    from repro.models.layers import mamba2

    def norm_then_gate(params, y, z, eps):
        yf = y.astype(jnp.float32)
        yn = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True) + eps)
        yn = yn * (1.0 + params["norm_scale"].astype(jnp.float32))
        return (yn * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

    monkeypatch.setattr(mamba2, "_gated_norm", norm_then_gate)
    res = run_hybrid()
    assert not res["correct"], res["checks"]


def test_hybrid_state_reset_at_each_chunk_is_caught(run_hybrid, monkeypatch):
    """The SSM state dropped at every boundary of the dual form's chunks
    (64 tokens), as if each chunk started a sequence."""
    from repro.models.layers import mamba2

    ssd = mamba2.ssd

    def reset(x, dt, a, bm, cm, chunk, initial_state=None):
        parts = [ssd(*(t[:, i:i + 64] for t in (x, dt)), a,
                     *(t[:, i:i + 64] for t in (bm, cm)), chunk)
                 for i in range(0, x.shape[1], 64)]
        return jnp.concatenate([y for y, _ in parts], axis=1), parts[-1][1]

    monkeypatch.setattr(mamba2, "ssd", reset)
    res = run_hybrid()
    assert not res["correct"], res["checks"]
