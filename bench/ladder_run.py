"""One run of a SEBS-ladder cell, whatever trainer and model it drives.

A driver (``sebs_reshape``) hands in the model, its weights, the trainer it
builds and how the check reads set-up's pass; the
rest is ``bench/drivers/sebs_ladder.py``'s run: set-up drives one whole
ladder pass (every stage's step compiled, what the check compares read), the
window repeats the ladder from stage 0 with fresh rows and the state carried
over until ``--seconds`` have passed and ends with a whole pass, then the
state is freed and the float32 reference follows pass 0.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from bench import common, program, trace_reduce

sebs_ladder = common.load_driver("sebs_ladder")
Rows, ladder = sebs_ladder.Rows, sebs_ladder.ladder


def psgd_ladder(mix: dict):
    """The mix's optimizer (psgd) and SEBS schedule, as ``sebs_ladder`` makes
    them."""
    from repro.core import SEBS
    from repro.optim import make_optimizer

    sched = SEBS(b1=mix["b1"], C1=mix["b1"] * mix["updates_per_stage"], rho=mix["rho"],
                 num_stages=mix["stages"], eta=mix["eta"])
    return make_optimizer("psgd", gamma=mix["gamma"]), sched


def run(ctx, *, model, params, key, build, read_pass0, check):
    """``build(model, pipeline, tracer) -> (optimizer, trainer)``;
    ``read_pass0(trainer, state) -> (state, what the check compares)``;
    ``check(ctx, key, rows, prog) -> (checks, ok)``."""
    from repro.data import DataPipeline
    from repro.obs import Tracer
    from repro.train.state import TrainState

    c, mix = ctx.config, ctx.traffic
    batches, _, _ = ladder(mix)
    pass_rows = mix["updates_per_stage"] * sum(batches)
    pass_tokens = pass_rows * mix["seq"]
    rows = Rows(ctx.seed, mix["seq"], c["vocab_size"], pass_rows)
    tracer = Tracer(jax_profiler=True) if ctx.trace else None
    opt, trainer = build(model, DataPipeline(rows), tracer)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))

    # -- set-up: pass 0, which the check reads ----------------------------------
    state, prog = read_pass0(trainer, state)

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
    checks, ok = check(ctx, key, rows, prog)
    return SimpleNamespace(
        kind="train", ok=ok, attempted=passes * mix["updates_per_stage"] * mix["stages"],
        failed=0, e2e={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        checks=checks, memory_peak_bytes=mem_peak, trace=trace, spans=spans,
        window_s=window_s, window_compiles=window_compiles, tokens_per_s=tokens_per_s,
        config=c, traffic=mix, peaks=ctx.peaks, chips=len(ctx.devices),
    )
