"""Elastic data-parallel train steps.

Exact-sync mode must satisfy a stronger contract than the deferred-psum
path in train/step.py: not just "one collective per update" but *bit-
identical results at every data-axis width*. Two ingredients deliver it:

1. The microbatch is the atomic unit of compute. Every width runs the
   same (microbatch, seq) forward/backward program, so per-microbatch
   gradients are bitwise equal everywhere; only the assignment of
   microbatches to replicas changes.
2. Cross-microbatch summation uses a canonical fixed-shape pairwise tree
   (:func:`span_tree_sum`) instead of a serial scan or a backend-ordered
   psum. Replicas tree-sum their local chunks, all-gather the W partial
   sums, and every replica finishes the SAME global tree locally — the
   reduction order is a function of the global accumulation count only.

Local-SGD mode drops the per-update collective entirely: the train state
carries a leading replica axis, each replica updates from its own chunk's
gradient, and averaging happens in a separate program
(repro.distributed.reshard.build_sync_step) on the scheduler's cadence.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.train.loss import lm_loss
from repro.train.state import TrainState
from repro.train.step import clip_by_global_norm, shard_map_manual
from repro.utils.tree import tree_add, tree_scale


def span_tree_sum(get: Callable[[int], "jax.typing.ArrayLike"], n: int):
    """Canonical pairwise reduction of ``n`` pytree terms: split at n//2.

    The tree shape depends only on ``n`` — never on how index spans are
    distributed over devices — so for any power-of-two W dividing n, W
    replicas that tree-sum their n/W-term chunks locally and then
    tree-combine the W partials (in replica order) reproduce the width-1
    reduction bit-for-bit: the top log2(W) splits of the global tree land
    exactly on the chunk boundaries. Floating-point addition is not
    associative; fixing the tree is what makes elastic width changes
    invisible to the numerics."""
    assert n >= 1
    if n == 1:
        return get(0)
    mid = n // 2
    left = span_tree_sum(get, mid)
    right = span_tree_sum(lambda i: get(mid + i), n - mid)
    return tree_add(left, right)


def _batch_in_spec(x):
    spec = [None] * x.ndim
    spec[0] = "data"
    return P(*spec)


def _stacked_spec(x):
    return P(*(["data"] + [None] * (x.ndim - 1)))


def _microbatch_term(model, params, batch, i, z_loss):
    """Gradient/metric contribution of microbatch ``i`` of the local chunk.

    Grads are accumulated in f32 (matching the scan path in train/step.py);
    the per-microbatch squared grad norm feeds the GNS estimator."""
    mb = jax.tree.map(lambda x: x[i], batch)
    loss_fn = lambda p, b: lm_loss(model, p, b, z_loss=z_loss)
    (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
    g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
    sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))
    return {"grads": g, "loss": m["loss"], "aux": m["aux"], "sq": sq}


def _apply(optimizer, state, grads, lr, stage, grad_clip):
    grads, gnorm = clip_by_global_norm(grads, grad_clip)
    new_params, new_opt = optimizer.update(
        grads, state.opt_state, state.params, lr=lr, stage=stage
    )
    return TrainState(new_params, new_opt, state.step + 1), gnorm


def build_elastic_train_step(
    model,
    optimizer,
    mesh,
    *,
    width: int,
    local_accum: int,
    z_loss: float = 0.0,
    grad_clip: float = 0.0,
    donate: bool = True,
):
    """Exact-sync step: ``step(state, batch, lr, stage) -> (state, metrics)``.

    ``state`` is replicated; batch leaves are (width·local_accum, micro, ...)
    with axis 0 sharded over the mesh's "data" axis. The only collective is
    one all-gather of the per-replica partial sums per optimizer update.
    Losses, grads and therefore the whole trajectory are bit-identical for
    every width satisfying the planner's divisibility rule, wherever the
    compiler rounds each microbatch's forward/backward alike in every
    width's program (f32 compute on the CPU host; not bf16, not a TPU).

    Compile-cost note: the canonical tree unrolls one forward/backward per
    local microbatch (a lax.scan would impose serial summation order and
    break cross-width identity), so trace size grows linearly with
    ``local_accum``. local_accum stays at accum/width while the stage ladder
    fits the device budget; for very deep ladders on a saturated budget,
    prefer ``local`` sync mode or a larger budget over letting local_accum
    grow past ~32."""
    global_accum = width * local_accum

    def local_fn(state, batch, lr, stage):
        total = span_tree_sum(
            lambda i: _microbatch_term(model, state.params, batch, i, z_loss),
            local_accum,
        )
        if width > 1:
            # THE sync point: partial sums cross replicas once per update.
            # all_gather + explicit tree combine, NOT psum — the backend's
            # all-reduce order varies with topology, ours must not.
            gathered = jax.lax.all_gather(total, "data")
            total = span_tree_sum(
                lambda d: jax.tree.map(lambda x: x[d], gathered), width
            )
        grads = tree_scale(total["grads"], 1.0 / global_accum)
        metrics = {
            "loss": total["loss"] / global_accum,
            "aux": total["aux"] / global_accum,
            "grad_sq_small": total["sq"] / global_accum,
            "grad_sq_big": sum(
                jnp.sum(jnp.square(x)) for x in jax.tree.leaves(grads)
            ),
        }
        new_state, gnorm = _apply(optimizer, state, grads, lr, stage, grad_clip)
        return new_state, dict(metrics, grad_norm=gnorm)

    if width == 1:
        step = local_fn
    else:

        def step(state, batch, lr, stage):
            in_specs = (
                jax.tree.map(lambda _: P(), state),
                jax.tree.map(_batch_in_spec, batch),
                P(),
                P(),
            )
            out_specs = (jax.tree.map(lambda _: P(), state), P())
            fn = shard_map_manual(
                local_fn, mesh, in_specs, out_specs, manual_axes=("data",)
            )
            return fn(state, batch, lr, stage)

    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(step, **jit_kwargs)


def build_local_train_step(
    model,
    optimizer,
    mesh,
    *,
    width: int,
    local_accum: int,
    z_loss: float = 0.0,
    grad_clip: float = 0.0,
    donate: bool = True,
):
    """Local-SGD step: ``step(stacked_state, batch, lr, stage)``.

    ``stacked_state`` leaves carry a leading (width,) replica axis sharded
    over "data"; each replica applies an independent optimizer update from
    its own chunk's mean gradient. ZERO collectives — metrics come back
    replica-stacked (leading width axis) and parameter averaging is a
    separate program on the SyncScheduler's cadence."""
    assert width > 1, "width-1 local SGD is exact sync; use the elastic step"

    def local_fn(stacked, batch, lr, stage):
        state = jax.tree.map(lambda x: jnp.squeeze(x, 0), stacked)
        total = span_tree_sum(
            lambda i: _microbatch_term(model, state.params, batch, i, z_loss),
            local_accum,
        )
        grads = tree_scale(total["grads"], 1.0 / local_accum)
        metrics = {
            "loss": total["loss"] / local_accum,
            "aux": total["aux"] / local_accum,
            "grad_sq_small": total["sq"] / local_accum,
            "grad_sq_big": sum(
                jnp.sum(jnp.square(x)) for x in jax.tree.leaves(grads)
            ),
        }
        new_state, gnorm = _apply(optimizer, state, grads, lr, stage, grad_clip)
        new_stacked = jax.tree.map(lambda x: x[None], new_state)
        metrics = {k: v[None] for k, v in dict(metrics, grad_norm=gnorm).items()}
        return new_stacked, metrics

    def step(stacked, batch, lr, stage):
        in_specs = (
            jax.tree.map(_stacked_spec, stacked),
            jax.tree.map(_batch_in_spec, batch),
            P(),
            P(),
        )
        out_specs = (jax.tree.map(_stacked_spec, stacked), P("data"))
        fn = shard_map_manual(
            local_fn, mesh, in_specs, out_specs, manual_axes=("data",)
        )
        return fn(stacked, batch, lr, stage)

    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(step, **jit_kwargs)
