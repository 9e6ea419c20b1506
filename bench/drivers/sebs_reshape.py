"""Training driver: the SEBS ladder in reshape mode, for the Granite 4.0-H
hybrid (``bench/program_hybrid.py``, ``bench/reference/granite_hybrid.py``).

The mix fixes the job as in ``sebs_ladder``: first batch ``b1`` rows of
``seq`` tokens, growth ``rho`` over ``stages``, ``updates_per_stage``, psgd
(``eta``, ``gamma``). The trainer is ``SEBSTrainer`` in reshape mode: the
batch itself grows, one compiled step per stage, every other option at the
program's default.

Set-up, window and check are ``bench/ladder_run.py``'s. The check follows
set-up's pass 0 to the first update of the last stage and compares what
``sebs_ladder.readings`` compares: every loss; the first gradient as psgd
received it; the parameters' change after update 3; the gradient of the
first update of every later stage. Gradients are read by reference leaf,
each over the program's leaves that hold it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, ladder_run, program_hybrid
from bench.reference import granite_hybrid
from bench.reference.qwen2 import exact_mm, psgd_update

readings = ladder_run.sebs_ladder.readings


def _build(mix):
    from repro.core import SEBSTrainer

    def build(model, pipeline, tracer):
        opt, sched = ladder_run.psgd_ladder(mix)
        return opt, SEBSTrainer(model, opt, sched, pipeline, mode="reshape", tracer=tracer)

    return build


def read_pass0(trainer, state, mix, lmap):
    """Drive set-up's pass 0 and read what the check compares."""
    _, firsts, _ = ladder_run.ladder(mix)
    g_scale = (mix["gamma"] + mix["eta"]) / (mix["gamma"] * mix["eta"])
    norms = lambda a, b, s: program_hybrid.norms_by_leaf(lmap, a, b, s)
    grad = lambda st: norms(st.opt_state["anchor"], st.params, g_scale)
    losses, grads = [], {}

    def go(state, n):
        state, log = trainer.run(state, log_every=1, stop_after_updates=n)
        losses.extend(log.losses)
        return state

    state = go(state, 1)
    grads[1] = grad(state)
    state = go(state, 2)
    change = norms(state.params, state.opt_state["anchor"], 1.0)
    done = 3
    for u in firsts:
        state = go(state, u - done)
        grads[u] = grad(state)
        done = u
    state, _ = trainer.run(state, log_every=10 ** 9)
    return state, {"losses": losses, "grads": grads, "change": change}


def run(ctx):
    c, mix = ctx.config, ctx.traffic
    model = program_hybrid.build(c)
    key = common.seed_key(ctx.seed, ladder_run.sebs_ladder.WEIGHT_STREAM)
    params = program_hybrid.program_weights(model, c, key)
    lmap = program_hybrid.leaf_map(model)
    return ladder_run.run(ctx, model=model, params=params, key=key, build=_build(mix),
                          read_pass0=lambda tr, st: read_pass0(tr, st, mix, lmap),
                          check=_check)


def reference_follow(c: dict, key, rows, mix: dict, mm=exact_mm, batch_rows=None):
    """The float32 reference: psgd from the seed's weights over the rows that
    pass 0 reads, update by update as far as the check reads, each batch in
    blocks of ``b1`` rows (the mean of block means, which is the batch mean),
    the anchor moved to the parameters at each stage change. ``batch_rows``
    keeps that many rows of each block (a planted fault)."""
    batches, firsts, last = ladder_run.ladder(mix)
    b1, ups = mix["b1"], mix["updates_per_stage"]
    vg = jax.value_and_grad(lambda w, t: granite_hybrid.lm_loss(w, t, c, mm))

    def block(w, toks, g, loss):
        lb, gb = vg(w, toks)
        return loss + lb, jax.tree.map(jnp.add, g, gb)

    block = jax.jit(block, donate_argnums=(2,))
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
    upd = jax.jit(lambda w, g, a, n: psgd_update(
        w, jax.tree.map(lambda x: x / n, g), a, mix["eta"], mix["gamma"]), donate_argnums=(1,))
    norm = jax.jit(lambda t, n: {k: jnp.linalg.norm(v.ravel()) / n for k, v in t.items()})
    diff = jax.jit(lambda a, b: {k: jnp.linalg.norm((a[k] - b[k]).ravel()) for k in a})
    w = granite_hybrid.stacked_weights(key, c, jnp.float32)
    anchor, offset = w, 0
    losses, grads, change = [], {}, None
    for u in range(1, last + 1):
        stage = (u - 1) // ups
        if u > 1 and (u - 1) % ups == 0:
            anchor = w
        n_blocks = batches[stage] // b1
        loss, g = jnp.float32(0), zeros(w)
        for blk in range(n_blocks):
            toks = rows.rows(0, offset + blk * b1, b1)
            if batch_rows is not None:
                toks = toks[:batch_rows]
            loss, g = block(w, toks, g, loss)
        offset += batches[stage]
        n = jnp.float32(n_blocks)
        losses.append(float(loss) / n_blocks)
        if u == 1 or u in firsts:
            grads[u] = {k: float(v) for k, v in norm(g, n).items()}
        w = upd(w, g, anchor, n)
        del g
        if u == 3:
            change = {k: float(v) for k, v in diff(w, anchor).items()}
    return {"losses": losses, "grads": grads, "change": change}


def _check(ctx, key, rows, prog):
    limits = ctx.traffic["limits"]
    try:
        ref = reference_follow(ctx.config, key, rows, ctx.traffic)
        got = readings(prog, ref)
        ok = set(got) == set(limits) and all(np.isfinite(v) for v in got.values())
        ctx.say(f"bench: losses program {prog['losses']} reference {ref['losses']}")
    except Exception as e:  # a reference that fails is no pass
        ctx.say(f"bench: reference failed: {e!r}")
        got, ok = {}, False
    return [(k, got[k] if np.isfinite(got.get(k, np.nan)) else 1e30, limits[k])
            for k in limits], ok
