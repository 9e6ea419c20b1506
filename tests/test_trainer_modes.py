"""SEBSTrainer execution-mode coverage + schedule/pipeline integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import SEBS, DBSGD, AdaptiveSEBS, SEBSTrainer
from repro.data import DataPipeline, TokenDataset
from repro.models import build_model
from repro.obs.metrics import MetricsRegistry
from repro.optim import make_optimizer
from repro.train.state import TrainState


def _trainer(schedule, mode, accum_mode="psum_each", arch="qwen2.5-3b", opt="psgd"):
    cfg = get_config(arch, "smoke")
    model = build_model(cfg)
    optimizer = make_optimizer(opt, **({"gamma": 1e4} if opt == "psgd" else {}))
    ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=16, seed=0)
    trainer = SEBSTrainer(
        model, optimizer, schedule, DataPipeline(ds),
        mesh=None, microbatch=4 if mode == "accumulate" else None,
        mode=mode, accum_mode=accum_mode,
    )
    params, _ = model.init(jax.random.key(0))
    return trainer, TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))


def test_reshape_and_accumulate_consume_same_budget():
    sched = SEBS(b1=4, C1=32, rho=2.0, num_stages=3, eta=0.05)
    for mode in ("reshape", "accumulate"):
        trainer, state = _trainer(sched, mode)
        state, log = trainer.run(state, log_every=1)
        assert log.samples[-1] >= sched.total_samples
        assert all(np.isfinite(log.losses))


def test_accumulate_compiles_once_per_stage():
    sched = SEBS(b1=4, C1=32, rho=2.0, num_stages=3, eta=0.05)
    trainer, state = _trainer(sched, "accumulate")
    trainer.run(state, log_every=1)
    assert len(trainer._steps) == 3  # one compiled step per stage


def test_unrolled_accum_mode_runs():
    sched = SEBS(b1=4, C1=24, rho=2.0, num_stages=2, eta=0.05)
    trainer, state = _trainer(sched, "accumulate", accum_mode="unrolled")
    state, log = trainer.run(state, log_every=1)
    assert all(np.isfinite(log.losses))


@pytest.mark.parametrize("accum_mode", ["psum_each", "unrolled"])
def test_updates_unrolled_counter(accum_mode):
    """`train.updates_unrolled` counts the updates at accumulate 2-4 (any
    width above 1 in unrolled mode), never the accumulate-1 ones."""
    sched = SEBS(b1=4, C1=32, rho=2.0, num_stages=3, eta=0.05)
    trainer, state = _trainer(sched, "accumulate", accum_mode=accum_mode)
    trainer.metrics = MetricsRegistry()
    state, log = trainer.run(state, log_every=1)
    accums = [b // 4 for b in log.batch_sizes]
    assert sorted(set(accums)) == [1, 2, 4]
    unrolled = trainer.metrics.counter("train.updates_unrolled").value
    assert unrolled == sum(a > 1 for a in accums)
    assert trainer.metrics.counter("train.updates").value == len(accums)


def _two_stages():
    # batch 4 then 8 (accumulate 1, 2 in accumulate mode): 4 + 4 updates
    return SEBS(b1=4, C1=16, rho=2.0, num_stages=2, eta=0.05)


def _bytes(tree) -> list:
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


def _log_bytes(log: dict) -> dict:
    # NaN-safe bit comparison of a TrainLog's fields (the noise scale is
    # NaN until an accumulate update has fed the estimator)
    return {k: np.asarray(v, np.float64).tobytes() for k, v in log.items()}


def _overlapped(trainer) -> int:
    return trainer.metrics.counter("train.updates_overlapped").value


@pytest.mark.parametrize("mode", ["accumulate", "reshape"])
def test_lagged_run_matches_drained_run(mode, tmp_path):
    """One update in flight changes no number: the log (losses, noise
    scales, stages, batch sizes) and the final state are bit-identical to
    a run drained at every update (a save due at each)."""
    trainer, state = _trainer(_two_stages(), mode)
    trainer.metrics = MetricsRegistry()
    lagged, lag_log = trainer.run(state, log_every=1)
    n = len(lag_log.steps)
    assert n == 8 and _overlapped(trainer) == n - 1

    trainer, state = _trainer(_two_stages(), mode)
    trainer.metrics = MetricsRegistry()
    with CheckpointManager(str(tmp_path), keep_last=1) as ckpt:
        drained, drain_log = trainer.run(state, log_every=1, checkpointer=ckpt, save_every=1)
    assert _overlapped(trainer) == 0
    assert _log_bytes(lag_log.as_dict()) == _log_bytes(drain_log.as_dict())
    if mode == "accumulate":
        assert np.isfinite(lag_log.noise_scales[-1])  # the estimator was fed
    assert _bytes(lagged) == _bytes(drained)


@pytest.mark.parametrize("adaptive", [False, True])
def test_updates_overlapped_counter(adaptive):
    """`train.updates_overlapped` counts the updates dispatched while the
    previous one was unread: all but the first under a fixed schedule,
    none under a schedule that observes every loss."""
    sched = (AdaptiveSEBS(b1=4, eta=0.02, total=48, rho_max=4.0, min_stage_samples=16)
             if adaptive else _two_stages())
    trainer, state = _trainer(sched, "accumulate")
    trainer.metrics = MetricsRegistry()
    _, log = trainer.run(state, log_every=1)
    n = len(log.steps)
    assert n > 1 and trainer.metrics.counter("train.updates").value == n
    assert _overlapped(trainer) == (0 if adaptive else n - 1)


@pytest.mark.parametrize("stop", [1, 5, 100])
def test_stop_after_updates_runs_exactly_n(stop):
    """`stop_after_updates=n` runs n updates (or the schedule's all, if
    fewer), none beyond, and consumes exactly their samples."""
    trainer, state = _trainer(_two_stages(), "accumulate")
    trainer.metrics = MetricsRegistry()
    ran = list(trainer.controller.plans())[:stop]
    _, log = trainer.run(state, log_every=1, stop_after_updates=stop)
    assert log.steps == list(range(1, len(ran) + 1))
    assert trainer.metrics.counter("train.updates").value == len(ran)
    assert trainer.pipeline.samples_consumed == sum(p.batch_size for p in ran)


@pytest.mark.parametrize("k", [3, 6])
def test_save_holds_its_update(k, tmp_path):
    """A save due at update k holds the state, log and data position as of
    update k, though later updates are dispatched ahead of their reads."""
    trainer, state = _trainer(_two_stages(), "accumulate")
    with CheckpointManager(str(tmp_path), keep_last=8) as ckpt:
        trainer.run(state, log_every=1, checkpointer=ckpt, save_every=k)
        ref_trainer, ref_state = _trainer(_two_stages(), "accumulate")
        ref, ref_log = ref_trainer.run(ref_state, log_every=1, stop_after_updates=k)
        tree, meta = ckpt.restore({"train_state": ref}, step=k)
    assert meta["update"] == k
    assert _log_bytes(meta["log"]) == _log_bytes(ref_log.as_dict())
    assert meta["pipeline"] == ref_trainer.pipeline.state()
    assert _bytes(tree["train_state"]) == _bytes(ref)


def test_dbsgd_schedule_through_trainer():
    sched = DBSGD(b1=4, eta=0.05, epoch_size=16, total_epochs=3, scale=1.5)
    trainer, state = _trainer(sched, "reshape")
    state, log = trainer.run(state, log_every=1)
    assert max(log.batch_sizes) > min(log.batch_sizes)  # grew every epoch


@pytest.mark.parametrize(
    "arch", ["rwkv6-1.6b", pytest.param("arctic-480b", marks=pytest.mark.slow)]
)
def test_trainer_on_nondense_families(arch):
    """SEBS applies unchanged to SSM and MoE families (DESIGN §Arch-applicability)."""
    sched = SEBS(b1=4, C1=16, rho=2.0, num_stages=2, eta=0.02)
    trainer, state = _trainer(sched, "reshape", arch=arch, opt="momentum")
    state, log = trainer.run(state, log_every=1)
    assert all(np.isfinite(log.losses))
    assert max(log.stages) == 1
