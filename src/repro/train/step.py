"""Train-step builders.

Two gradient-accumulation execution modes (DESIGN.md §5):

- ``psum_each`` — plain pjit. The microbatch scan's backward pass contains
  a gradient all-reduce *per microbatch* (GSPMD inserts it inside the scan
  body; XLA cannot hoist collectives out of a while loop). This is the
  communication pattern of classical constant-batch training.
- ``deferred`` — ``shard_map`` manual over the batch axes (pod, data) with
  the model axis left automatic. Gradients accumulate locally across the
  microbatch scan and a single ``psum`` per optimizer update synchronizes
  them. Combined with SEBS (accum_steps = ρˢ at stage s), per-sample
  gradient-synchronization traffic falls by exactly ρˢ — the paper's
  iteration-complexity saving realized as collective-bytes saving.

``accum_steps`` is static per compilation; SEBS's ``accumulate`` mode
therefore compiles one step per stage (S ≈ 3–5 total compilations).

Up to ``UNROLL_MAX_ACCUM`` microbatches the scan is unrolled into
straight-line code: the f32 gradient sum then fuses into the weight-gradient
dots and the last add and the 1/n scale into the optimizer update, where a
rolled loop writes each microbatch's gradient and reads it back.
``mode="unrolled"`` is ``psum_each`` unrolled at every width.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import mesh_data_axes
from repro.train.loss import lm_loss
from repro.train.state import TrainState
from repro.utils.tree import tree_add, tree_scale


def shard_map_manual(f, mesh, in_specs, out_specs, manual_axes):
    """shard_map manual over ``manual_axes`` only (the model axis stays
    automatic), no replication/VMA checking.

    Shared by the deferred-psum train step below and the elastic
    data-parallel steps in ``repro.distributed.step``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(manual_axes), check_vma=False,
    )


def clip_by_global_norm(grads, max_norm: float):
    if not max_norm:
        return grads, jnp.zeros((), jnp.float32)
    norm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), norm


def _sq_norm(tree):
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))


def _with_grad_sq_big(metrics, grads):
    """Add ‖mean grad‖² (the GNS estimator's big-batch norm) where the step
    accumulated over microbatches."""
    if "grad_sq_small" not in metrics:
        return metrics
    with jax.named_scope("grad_accumulate"):
        return dict(metrics, grad_sq_big=_sq_norm(grads))


# Compiled for a v5e, the qwen2.5-3b 4-layer step fits unrolled over 4
# microbatches; over 8 it runs out of HBM, and unrolled by 4 it needs 12.8 GB
# of temporaries where the rolled loop needs 7.1.
UNROLL_MAX_ACCUM = 4


def unrolls(accum_steps: int, mode: str = "deferred") -> bool:
    """Whether the step built for ``accum_steps`` and ``mode`` runs its
    microbatch loop unrolled."""
    return accum_steps > 1 and (mode == "unrolled" or accum_steps <= UNROLL_MAX_ACCUM)


def _grads_over_microbatches(model, params, batch, accum_steps, z_loss, unroll):
    """Mean loss/grads over the (accum, micro, ...) leading axes of batch."""
    loss_fn = lambda p, mb: lm_loss(model, p, mb, z_loss=z_loss)
    if accum_steps == 1:
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return grads, metrics

    def body(acc, mb):
        gsum, lsum, asum, sqsum = acc
        (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        with jax.named_scope("grad_accumulate"):
            # per-microbatch squared grad norm — feeds the McCandlish
            # gradient-noise-scale estimator (core/noise_scale.py) for free
            sq = _sq_norm(g)
            return (tree_add(gsum, g), lsum + m["loss"], asum + m["aux"], sqsum + sq), None

    zeros = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), params)
    z = jnp.zeros((), jnp.float32)
    (gsum, lsum, asum, sqsum), _ = jax.lax.scan(body, (zeros, z, z, z), batch, unroll=unroll)
    with jax.named_scope("grad_accumulate"):
        grads = tree_scale(gsum, 1.0 / accum_steps)
    metrics = {
        "loss": lsum / accum_steps,
        "aux": asum / accum_steps,
        "grad_sq_small": sqsum / accum_steps,  # E‖g_micro‖² for GNS
    }
    return grads, metrics


def build_train_step(
    model,
    optimizer,
    mesh: Optional[Mesh] = None,
    *,
    accum_steps: int = 1,
    mode: str = "deferred",
    z_loss: float = 0.0,
    grad_clip: float = 0.0,
    donate: bool = True,
    raw: bool = False,
):
    """Returns a jitted ``step(state, batch, lr, stage) -> (state, metrics)``.

    Batch leaves are (B, ...) when accum_steps == 1, else (accum, micro, ...).
    """
    assert mode in ("deferred", "psum_each", "unrolled")
    unroll = unrolls(accum_steps, mode)
    if mode == "unrolled":
        mode = "psum_each"
    batch_axes = mesh_data_axes(mesh)

    @jax.named_scope("optimizer")
    def apply_update(state: TrainState, grads, lr, stage):
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        new_params, new_opt = optimizer.update(
            grads, state.opt_state, state.params, lr=lr, stage=stage
        )
        return TrainState(new_params, new_opt, state.step + 1), gnorm

    if mode == "psum_each" or not batch_axes or mesh is None:

        def step(state, batch, lr, stage):
            grads, metrics = _grads_over_microbatches(
                model, state.params, batch, accum_steps, z_loss, unroll
            )
            metrics = _with_grad_sq_big(metrics, grads)
            new_state, gnorm = apply_update(state, grads, lr, stage)
            metrics = dict(metrics, grad_norm=gnorm)
            return new_state, metrics

    else:
        bdim = 0 if accum_steps == 1 else 1
        n_shards = 1
        for a in batch_axes:
            n_shards *= mesh.shape[a]

        def local_step(state, batch, lr, stage):
            grads, metrics = _grads_over_microbatches(
                model, state.params, batch, accum_steps, z_loss, unroll
            )
            # THE deferred all-reduce: grads stay device-local through the
            # whole microbatch scan (check_vma=False → no automatic psum at
            # the params-broadcast transpose; verified against pjit grads,
            # exact ratio 1.0), and this single pmean per optimizer update
            # is the only gradient synchronization.
            grads = jax.lax.pmean(grads, batch_axes)  # repro-lint: disable=R101 -- mesh width is fixed for this executable's lifetime; cross-width bit-identity is repro.distributed's contract (span_tree_sum), not this deferred path's
            metrics = jax.lax.pmean(metrics, batch_axes)  # repro-lint: disable=R101 -- same fixed-width executable as the grads pmean above
            metrics = _with_grad_sq_big(metrics, grads)
            new_state, gnorm = apply_update(state, grads, lr, stage)
            metrics = dict(metrics, grad_norm=gnorm)
            return new_state, metrics

        def batch_in_spec(x):
            spec = [None] * x.ndim
            spec[bdim] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
            return P(*spec)

        def step(state, batch, lr, stage):
            in_specs = (
                jax.tree.map(lambda _: P(), state),
                jax.tree.map(batch_in_spec, batch),
                P(),
                P(),
            )
            out_specs = (jax.tree.map(lambda _: P(), state), P())
            fn = shard_map_manual(
                local_step, mesh, in_specs, out_specs, manual_axes=batch_axes
            )
            return fn(state, batch, lr, stage)

    if raw:
        return step
    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    return jax.jit(step, **jit_kwargs)


def build_eval_step(model, *, z_loss: float = 0.0):
    def eval_step(params, batch):
        _, metrics = lm_loss(model, params, batch, z_loss=z_loss)
        return metrics

    return jax.jit(eval_step)
