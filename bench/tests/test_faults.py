"""Each fault a cell can have, planted under the timed path, makes ``correct``
false; the unbroken run is correct. Test-size cells on the CPU."""
import jax
import jax.numpy as jnp


def test_train_sound_run_is_correct(run_cell):
    res = run_cell("train-sebs")
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["loss_gap", "grad_gap", "update_gap", "grad_gap.s1",
                                   "grad_gap.s2"]


def _wrap_train_step(monkeypatch, wrap):
    import repro.core.trainer as trainer

    orig = trainer.build_train_step

    def build(*a, **k):
        return wrap(orig(*a, **{**k, "donate": False}))

    monkeypatch.setattr(trainer, "build_train_step", build)


def test_train_state_unchanged_is_caught(run_cell, monkeypatch):
    def wrap(step):
        def unchanged(state, batch, lr, stage):
            _, metrics = step(state, batch, lr, stage)
            return state, metrics
        return unchanged

    _wrap_train_step(monkeypatch, wrap)
    res = run_cell("train-sebs")
    assert not res["correct"], res["checks"]


def test_train_half_batch_is_caught(run_cell, monkeypatch):
    def wrap(step):
        def half(state, batch, lr, stage):
            axis = 0 if batch["tokens"].ndim == 2 else 1
            cut = {k: jax.lax.slice_in_dim(v, 0, v.shape[axis] // 2, axis=axis)
                   for k, v in batch.items()}
            return step(state, cut, lr, stage)
        return half

    _wrap_train_step(monkeypatch, wrap)
    res = run_cell("train-sebs")
    assert not res["correct"], res["checks"]


def test_train_accumulation_dropping_microbatches_is_caught(run_cell, monkeypatch):
    """Stages 1 and 2 accumulate 2 and 4 microbatches: a step that uses only
    the first of them (stage 0's step is sound) is caught."""
    def wrap(step):
        def first_only(state, batch, lr, stage):
            if batch["tokens"].ndim == 3:
                batch = {k: jnp.repeat(v[:1], v.shape[0], axis=0) for k, v in batch.items()}
            return step(state, batch, lr, stage)
        return first_only

    _wrap_train_step(monkeypatch, wrap)
    res = run_cell("train-sebs")
    assert not res["correct"], res["checks"]
    later = {k: c for k, c in res["checks"].items() if k.startswith("grad_gap.s")}
    assert any(c["value"] > c["limit"] for c in later.values()), later
