"""Beyond-paper extension tests: gradient-noise-scale estimator and
loss-keyed AdaptiveSEBS (Eq. 8 with measured ε)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SEBS, AdaptiveSEBS, GradientNoiseScale, SEBSTrainer, StageController
from repro.core.noise_scale import microbatch_grad_sq_norms
from repro.data import DataPipeline, QuadraticProblem, TokenDataset
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train.state import TrainState


def test_gns_estimator_on_known_gaussian():
    """Analytic check: per-sample grads g_i = w − ξ_i with ξ ~ N(0, I):
    tr Σ = d, ‖G‖² = ‖w‖². Estimator must recover B_noise = d/‖w‖²."""
    d, b_small, n_micro = 64, 8, 64
    rng = np.random.default_rng(0)
    w = np.full(d, 2.0)  # ‖G‖² = 4d, B_noise = d / 4d = 0.25
    micro_sq, big_sum = [], np.zeros(d)
    for _ in range(n_micro):
        xi = rng.standard_normal((b_small, d))
        g = (w[None] - xi).mean(0)
        micro_sq.append(float(np.sum(g * g)))
        big_sum += g
    g_big = big_sum / n_micro
    tr_s, g_sq, b_noise = microbatch_grad_sq_norms(
        jnp.float32(np.mean(micro_sq)), jnp.float32(np.sum(g_big * g_big)),
        b_small, b_small * n_micro,
    )
    assert float(tr_s) == pytest.approx(d, rel=0.3)          # tr Σ = d
    assert float(g_sq) == pytest.approx(4 * d, rel=0.05)     # ‖w‖² = 4d
    assert float(b_noise) == pytest.approx(0.25, rel=0.35)


def test_gns_ema_converges():
    gns = GradientNoiseScale(ema=0.5)
    for _ in range(20):
        gns.update(sum_sq_small=12.0, sq_big=4.0, b_small=2, b_big=16)
    # trΣ = (12-4)/(1/2 - 1/16) = 18.286; |G|² = (16·4 − 2·12)/14 = 2.857
    assert gns.b_noise == pytest.approx(18.2857 / 2.8571, rel=1e-3)


def test_gns_update_stays_on_the_host():
    """The trainer feeds the estimator while the next update is in flight,
    where a device program would queue behind that update: the update moves
    nothing to the device, and its numbers are the float32 arithmetic's."""
    gns = GradientNoiseScale()
    with jax.transfer_guard("disallow"):
        gns.update(sum_sq_small=12.3, sq_big=4.1, b_small=2, b_big=16)
    tr_s, g_sq, _ = microbatch_grad_sq_norms(jnp.float32(12.3), jnp.float32(4.1), 2, 16)
    assert (gns._tr_sigma, gns._g_sq) == (float(tr_s), float(g_sq))


def test_adaptive_sebs_grows_with_observed_contraction():
    sched = AdaptiveSEBS(b1=8, eta=0.1, total=10_000, rho_max=4.0,
                         min_stage_samples=100, smooth=0.0)
    assert sched.info(0).batch_size == 8
    # no growth before min_stage_samples
    sched.observe(50, 1.0)
    sched.observe(90, 0.2)
    assert sched.info(90).batch_size == 8
    # loss contracted 5x -> growth capped at rho_max=4
    sched.observe(200, 0.2)
    assert sched.info(200).batch_size == 32
    assert sched.history[-1]["rho_obs"] == pytest.approx(5.0, rel=0.01)
    # flat loss -> no further growth
    sched.observe(400, 0.21)
    assert sched.info(400).batch_size == 32


class _EchoDataset:
    """Trivially learnable stream (token t+1 == token t): CE collapses fast,
    so the adaptive controller's contraction trigger fires deterministically."""

    def __init__(self, vocab_size, seq_len, seed=0):
        self.vocab_size, self.seq_len, self.seed = vocab_size, seq_len, seed

    def batch(self, index, batch_size):
        start = jax.random.randint(
            jax.random.fold_in(jax.random.key(self.seed), index),
            (batch_size, 1), 0, self.vocab_size,
        )
        return {"tokens": jnp.broadcast_to(start, (batch_size, self.seq_len + 1))}


def test_adaptive_sebs_through_trainer_tracks_inverse_loss():
    """End-to-end: adaptive batch grows as the LM loss falls, and the GNS
    metric is produced by accumulate mode."""
    cfg = get_config("qwen2.5-3b", "smoke")
    model = build_model(cfg)
    optimizer = make_optimizer("momentum", beta=0.9)
    sched = AdaptiveSEBS(b1=4, eta=0.02, total=640, rho_max=4.0,
                         min_stage_samples=64, smooth=0.5, loss_floor=0.0)
    ds = _EchoDataset(vocab_size=cfg.vocab_size, seq_len=16, seed=0)
    trainer = SEBSTrainer(
        model, optimizer, sched, DataPipeline(ds),
        mesh=None, microbatch=4, mode="accumulate", accum_mode="psum_each",
        grad_clip=1.0,
    )
    params, _ = model.init(jax.random.key(0))
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    state, log = trainer.run(state, log_every=1)
    assert max(log.batch_sizes) > 4, "batch never grew despite loss contraction"
    assert all(np.isfinite(log.losses))
    # noise scale was measured once accumulation kicked in
    assert any(np.isfinite(ns) for ns in log.noise_scales)
