"""SEBSTrainer execution-mode coverage + schedule/pipeline integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SEBS, DBSGD, SEBSTrainer
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
