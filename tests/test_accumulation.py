"""Gradient-accumulation equivalence — the execution-mode invariants behind
SEBS's `accumulate` batch-growth mode."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train.loss import lm_loss
from repro.train.state import TrainState
from repro.train.step import build_train_step, unrolls
from repro.utils.tree import tree_add, tree_scale


def _setup():
    # f32 compute so the K-microbatch mean and the big-batch mean agree to
    # float rounding (bf16 would round differently per microbatch)
    cfg = get_config("qwen2.5-3b", "smoke").replace(compute_dtype="float32")
    model = build_model(cfg)
    optimizer = make_optimizer("sgd")
    params, _ = model.init(jax.random.key(0))
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    return cfg, model, optimizer, state


def test_accumulated_equals_big_batch():
    """K microbatches accumulated == one K·b batch (same mean gradient)."""
    cfg, model, optimizer, state = _setup()
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    big = {"tokens": tokens}
    stacked = {"tokens": tokens.reshape(4, 2, 16)}

    step1 = build_train_step(model, optimizer, mesh=None, accum_steps=1, donate=False)
    stepk = build_train_step(model, optimizer, mesh=None, accum_steps=4, donate=False)
    s1, m1 = step1(state, big, jnp.float32(0.1), jnp.int32(0))
    sk, mk = stepk(state, stacked, jnp.float32(0.1), jnp.int32(0))
    assert float(m1["loss"]) == pytest.approx(float(mk["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(sk.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def _sq(tree):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def _rolled_reference(model, optimizer, accum):
    """The accumulate step written out as a rolled scan: the same body, the
    same order of additions."""

    def step(state, batch, lr, stage):
        def body(acc, mb):
            gsum, lsum, sqsum = acc
            (_, m), g = jax.value_and_grad(
                lambda p: lm_loss(model, p, mb), has_aux=True)(state.params)
            return (tree_add(gsum, g), lsum + m["loss"], sqsum + _sq(g)), None

        zeros = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), state.params)
        z = jnp.zeros((), jnp.float32)
        (gsum, lsum, sqsum), _ = jax.lax.scan(body, (zeros, z, z), batch)
        grads = tree_scale(gsum, 1.0 / accum)
        params, opt = optimizer.update(grads, state.opt_state, state.params, lr=lr, stage=stage)
        metrics = {"loss": lsum / accum, "grad_sq_small": sqsum / accum,
                   "grad_sq_big": _sq(grads)}
        return TrainState(params, opt, state.step + 1), metrics

    return jax.jit(step)


@pytest.mark.parametrize("accum", [2, 3, 4, 5])
def test_accumulate_step_matches_rolled_scan(accum):
    """The unrolled loop (accumulate <= 4) and the rolled one (5) give the
    losses, GNS norms and psgd updates of a rolled scan, over 2 updates."""
    assert unrolls(accum) == (accum <= 4)
    cfg = get_config("qwen2.5-3b", "smoke").replace(compute_dtype="float32")
    model = build_model(cfg)
    optimizer = make_optimizer("psgd", gamma=1e4)
    params, _ = model.init(jax.random.key(0))
    init = lambda: TrainState(jax.tree.map(jnp.copy, params), optimizer.init(params),
                              jnp.zeros((), jnp.int32))
    tokens = jax.random.randint(jax.random.key(1), (2, accum, 2, 16), 0, cfg.vocab_size)
    got, want = init(), init()
    step = build_train_step(model, optimizer, mesh=None, accum_steps=accum, donate=False)
    ref = _rolled_reference(model, optimizer, accum)
    for u in range(2):
        batch = {"tokens": tokens[u]}
        got, mg = step(got, batch, jnp.float32(0.3), jnp.int32(0))
        want, mw = ref(want, batch, jnp.float32(0.3), jnp.int32(0))
        for k in ("loss", "grad_sq_small", "grad_sq_big"):
            np.testing.assert_allclose(float(mg[k]), float(mw[k]), rtol=1e-6, err_msg=k)
    # each leaf's change over the 2 updates, in norm: a sum fused into the
    # gradient dots adds in another order inside the dot, and the gradient
    # entries cancel, so single entries differ by more than 1e-6 (the worst
    # leaf's change differs by 3.3e-6 here); a lost microbatch moves it ~0.5
    for a, b, w in zip(*(jax.tree.leaves(t) for t in (got.params, want.params, params))):
        a, b, w = (np.asarray(x, np.float64) for x in (a, b, w))
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b - w)


_DEFERRED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.state import TrainState
    from repro.train.step import build_train_step

    cfg = get_config("qwen2.5-3b", "smoke").replace(compute_dtype="float32")
    model = build_model(cfg)
    opt = make_optimizer("sgd")
    params, _ = model.init(jax.random.key(0))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    mesh = make_host_mesh(data=4, model=1)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    stacked = {"tokens": tokens.reshape(2, 4, 16)}

    step_d = build_train_step(model, opt, mesh, accum_steps=2, mode="deferred", donate=False)
    sd, md = step_d(state, stacked, jnp.float32(0.1), jnp.int32(0))
    step_p = build_train_step(model, opt, mesh=None, accum_steps=2, donate=False)
    sp, mp = step_p(state, stacked, jnp.float32(0.1), jnp.int32(0))

    assert abs(float(md["loss"]) - float(mp["loss"])) < 1e-3, (md["loss"], mp["loss"])
    for a, b in zip(jax.tree.leaves(sd.params), jax.tree.leaves(sp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-4)
    print("DEFERRED_OK")
    """
)


def test_deferred_psum_equals_pjit_on_fake_devices():
    """shard_map deferred-all-reduce mode reproduces plain pjit results
    (run in a subprocess with 4 host devices so this session keeps 1)."""
    res = subprocess.run(
        [sys.executable, "-c", _DEFERRED_SCRIPT], capture_output=True, text=True, cwd="."
    )
    assert "DEFERRED_OK" in res.stdout, res.stdout + res.stderr
