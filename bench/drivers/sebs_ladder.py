"""Training driver: the SEBS ladder through the program's trainer.

The mix fixes the job: microbatch ``b1`` x ``seq``, growth ``rho`` over
``stages``, ``updates_per_stage`` (SEBS keeps it equal across stages) and
the optimizer (psgd, ``eta``, ``gamma``). The trainer is ``SEBSTrainer``
in accumulate mode, every other option at the program's default.

Set-up builds one trainer with its state from the seed and drives it
through one whole ladder pass, which compiles every stage's step. The
check reads that pass up to the first update of the last stage: every
loss; the first gradient as psgd received it (from the state after update
1: ``g = (anchor - w) (gamma + eta) / (gamma eta)``); the parameters'
change after update 3 (``w - anchor``, read before update 4 runs); and the
gradient of the first update of every later stage, read the same way
(psgd moves its anchor to the parameters at a stage change, so that
update's change is its gradient, averaged over all its microbatches).

The window repeats the ladder from stage 0, with fresh rows and the state
carried over, until ``--seconds`` have passed, and always ends with a
whole pass: its tokens over its time are ``train_tokens_per_s``.

After the window the state is freed and the float32 reference follows the
same updates from the same weights and rows.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, program, trace_reduce
from bench.reference import qwen2

DATA_STREAM, WEIGHT_STREAM = 2, 1


class Rows:
    """The job's data: uniform random tokens, row ``i`` of pass ``p`` a pure
    function of (seed, p, i); every row of a run differs. Duck-types the
    program's dataset (``batch(offset, n) -> {"tokens": (n, seq)}``)."""

    def __init__(self, seed: int, seq: int, vocab: int, pass_rows: int):
        self.key = common.seed_key(seed, DATA_STREAM)
        self.seq, self.vocab, self.pass_rows = seq, vocab, pass_rows
        self.pass_index = 0
        self._make = jax.jit(self._rows, static_argnums=(2,))

    def _rows(self, key, first, n):
        idx = first + jnp.arange(n)
        one = lambda i: jax.random.randint(jax.random.fold_in(key, i), (self.seq,), 0,
                                           self.vocab, jnp.int32)
        return jax.vmap(one)(idx)

    def rows(self, pass_index: int, offset: int, n: int):
        return self._make(self.key, jnp.int32(pass_index * self.pass_rows + offset), n)

    def batch(self, offset: int, n: int) -> dict:
        return {"tokens": self.rows(self.pass_index, offset, n)}


def ladder(mix: dict):
    """(batch of each stage, first update of each later stage, updates the
    check follows)."""
    b1, rho, stages, ups = mix["b1"], mix["rho"], mix["stages"], mix["updates_per_stage"]
    assert ups >= 4, "the check reads updates 1-3 inside stage 0 and needs update 4 there too"
    batches = [b1 * int(round(rho ** s)) for s in range(stages)]
    firsts = [s * ups + 1 for s in range(1, stages)]
    return batches, firsts, (firsts[-1] if firsts else 3)


def _norms_by_leaf(tree_a, tree_b, scale: float):
    """{name: |a - b| * scale} over the reference's leaf names."""
    names = list(program.PROGRAM_LEAF)
    fn = jax.jit(lambda a, b: [jnp.linalg.norm((program.leaf(a, n).astype(jnp.float32)
                                                - program.leaf(b, n).astype(jnp.float32)).ravel())
                               * scale for n in names])
    return dict(zip(names, (float(x) for x in fn(tree_a, tree_b))))


def _gap(prog: dict, ref: dict, keep) -> float:
    """Worst leaf: |‖prog‖ - ‖ref‖| over max(‖ref‖, median leaf ‖ref‖)."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in ref if keep(n))


def _build(mix, model, pipeline, tracer):
    from repro.core import SEBS, SEBSTrainer
    from repro.optim import make_optimizer

    opt = make_optimizer("psgd", gamma=mix["gamma"])
    sched = SEBS(b1=mix["b1"], C1=mix["b1"] * mix["updates_per_stage"], rho=mix["rho"],
                 num_stages=mix["stages"], eta=mix["eta"])
    trainer = SEBSTrainer(model, opt, sched, pipeline, microbatch=mix["b1"],
                          mode="accumulate", tracer=tracer)
    return opt, trainer


def _read_pass0(trainer, state, mix):
    """Drive set-up's pass 0 and read what the check compares."""
    _, firsts, _ = ladder(mix)
    g_scale = (mix["gamma"] + mix["eta"]) / (mix["gamma"] * mix["eta"])
    grad = lambda st: _norms_by_leaf(st.opt_state["anchor"], st.params, g_scale)
    losses, grads = [], {}

    def go(state, n):
        state, log = trainer.run(state, log_every=1, stop_after_updates=n)
        losses.extend(log.losses)
        return state

    state = go(state, 1)
    grads[1] = grad(state)
    state = go(state, 2)
    change = _norms_by_leaf(state.params, state.opt_state["anchor"], 1.0)
    done = 3
    for u in firsts:
        state = go(state, u - done)
        grads[u] = grad(state)
        done = u
    state, _ = trainer.run(state, log_every=10 ** 9)
    return state, {"losses": losses, "grads": grads, "change": change}


def run(ctx):
    from repro.data import DataPipeline
    from repro.obs import Tracer
    from repro.train.state import TrainState

    c, mix = ctx.config, ctx.traffic
    batches, _, _ = ladder(mix)
    pass_rows = mix["updates_per_stage"] * sum(batches)
    pass_tokens = pass_rows * mix["seq"]

    model = program.build(c)
    key = common.seed_key(ctx.seed, WEIGHT_STREAM)
    params = program.program_weights(model, c, key)
    rows = Rows(ctx.seed, mix["seq"], c["vocab_size"], pass_rows)
    tracer = Tracer(jax_profiler=True) if ctx.trace else None
    opt, trainer = _build(mix, model, DataPipeline(rows), tracer)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    # -- set-up: pass 0, which the check reads ----------------------------------
    state, prog = _read_pass0(trainer, state, mix)

    # -- window: whole passes until --seconds ---------------------------------
    compiles0 = ctx.compiles.count
    if ctx.trace:
        trace_reduce.start(ctx.out_dir)
    if tracer is not None:
        tracer.clear()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx.t_process
    passes = 0
    window = jax.profiler.TraceAnnotation("bench.window") if ctx.trace else nullcontext()
    with window:
        while True:
            rows.pass_index += 1
            trainer.pipeline.restore({"samples_consumed": 0})
            state, _ = trainer.run(state, log_every=10 ** 9)
            passes += 1
            if time.perf_counter() - t_w0 >= ctx.seconds:
                break
    t_w1 = time.perf_counter()
    window_compiles = ctx.compiles.count - compiles0
    if ctx.trace:
        jax.profiler.stop_trace()
    window_s = t_w1 - t_w0
    tokens_per_s = passes * pass_tokens / window_s
    mem_peak = common.memory_peak_bytes(ctx.devices)
    spans = [e for e in (tracer.events if tracer else []) if e["ph"] == "X"]

    # -- check: free the program's state, then the reference -------------------
    program.free(state)
    del state, trainer
    gc.collect()
    trace = trace_reduce.collect(ctx.out_dir) if ctx.trace else None
    if ctx.stand_in is not None:  # the control in the program's place
        prog = ctx.stand_in(ctx, key, rows)
    checks, ok = _check(ctx, key, rows, prog)
    return SimpleNamespace(
        kind="train", ok=ok, attempted=passes * mix["updates_per_stage"] * mix["stages"],
        failed=0, e2e={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem_peak, trace=trace, spans=spans,
        window_s=window_s, window_compiles=window_compiles, tokens_per_s=tokens_per_s,
        config=c, traffic=mix, peaks=ctx.peaks, chips=len(ctx.devices),
    )


def reference_follow(c: dict, key, rows, mix: dict, mm=qwen2.exact_mm, batch_rows=None):
    """The float32 reference: psgd from the seed's weights over the rows that
    pass 0 reads, update by update as far as the check reads, each batch in
    blocks of ``b1`` rows (the mean of block means, which is the batch mean),
    the anchor moved to the parameters at each stage change. Returns what
    :func:`_read_pass0` returns, leaf by name. ``batch_rows`` keeps that many
    rows of each block (a planted fault)."""
    batches, firsts, last = ladder(mix)
    b1, ups = mix["b1"], mix["updates_per_stage"]
    vg = jax.value_and_grad(lambda w, t: qwen2.lm_loss(w, t, c, mm))

    def block(w, toks, g, loss):
        lb, gb = vg(w, toks)
        return loss + lb, jax.tree.map(jnp.add, g, gb)

    block = jax.jit(block, donate_argnums=(2,))
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w))
    upd = jax.jit(lambda w, g, a, n: qwen2.psgd_update(
        w, jax.tree.map(lambda x: x / n, g), a, mix["eta"], mix["gamma"]), donate_argnums=(1,))
    norm = jax.jit(lambda t, n: {k: jnp.linalg.norm(v.ravel()) / n for k, v in t.items()})
    diff = jax.jit(lambda a, b: {k: jnp.linalg.norm((a[k] - b[k]).ravel()) for k in a})
    w = qwen2.stacked_weights(key, c, jnp.float32)
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


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers. ``loss_gap``: the largest relative gap of the
    losses. ``grad_gap``, ``grad_gap.s<k>``: worst leaf of the gradient of
    update 1 and of stage k's first update. ``update_gap``: worst leaf of
    the change after update 3, leaving out leaves whose reference gradient
    is under a thousandth of the median leaf's (they move by round-off alone
    under psgd)."""
    g1 = ref["grads"][1]
    med = float(np.median(list(g1.values())))
    moved = lambda n: g1[n] >= 1e-3 * med
    out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])),
           "grad_gap": _gap(prog["grads"][1], g1, lambda n: True),
           "update_gap": _gap(prog["change"], ref["change"], moved)}
    for s, u in enumerate(sorted(u for u in ref["grads"] if u != 1), 1):
        out[f"grad_gap.s{s}"] = _gap(prog["grads"][u], ref["grads"][u], lambda n: True)
    return out


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
