"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --variant smoke --schedule sebs --rho 4 --stages 3 --b1 8 \
        --c1 256 --seq 64 --steps-log 5

Smoke-sized by default, which is what the CPU tests and CI run. On a TPU
``--variant full`` trains the published widths; ``--layers N`` cuts the
depth to what one chip's memory holds, e.g. qwen2.5-3b with psgd in f32:

    PYTHONPATH=src python -m repro.launch.train --variant full --layers 4 \
        --b1 2 --c1 6 --rho 2 --stages 2 --seq 512

On a TPU slice the same entry point runs the production mesh
(``--mesh single|multi``). ``main(argv)`` can also be called in-process
(``chip_smoke.py`` does): it returns the trainer, the final state and the
train log.

Fault tolerance: ``--ckpt-dir`` + ``--ckpt-every N`` snapshot the FULL run
state (params, optimizer state, step, host RNG, pipeline position,
schedule state) every N updates; ``--resume`` restarts from the latest
checkpoint in the directory and is kill-equivalent — the resumed run's
losses and final params are bit-identical to an uninterrupted run.
``--stop-after`` simulates a preemption for the CI resume smoke job.

Elastic data parallelism: ``--dp-elastic`` hands the run to
:class:`repro.distributed.ElasticTrainer` — the replica count follows the
SEBS stage ladder up to ``--device-budget``, with ``--sync-mode exact``
(bit-identical across widths at ``--compute-dtype float32`` on the CPU
host; on a TPU, or at bf16, widths agree closely, not bit for bit) or
``--sync-mode local`` (local SGD, averaging cadence
``--local-interval``/``--local-growth``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.core import SEBS, AdaptiveSEBS, ClassicalStagewise, SEBSTrainer
from repro.obs import MetricsRegistry, Tracer
from repro.data import DataPipeline, TokenDataset
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train.state import TrainState
from repro.utils.log import get_logger

log = get_logger("train")


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (single-segment configs; "
                         "widths unchanged)")
    ap.add_argument("--compute-dtype", default=None, choices=["bfloat16", "float32"],
                    help="override the config's compute dtype; elastic exact sync "
                         "is bit-identical across widths at float32 on the CPU host only")
    ap.add_argument("--schedule", default="sebs", choices=["sebs", "classical", "adaptive"])
    ap.add_argument("--optimizer", default="psgd")
    ap.add_argument("--gamma", type=float, default=1e4)
    ap.add_argument("--eta", type=float, default=0.3)
    ap.add_argument("--b1", type=int, default=8)
    ap.add_argument("--c1", type=int, default=256)
    ap.add_argument("--rho", type=float, default=4.0)
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mode", default="accumulate", choices=["accumulate", "reshape"])
    ap.add_argument("--accum-mode", default="psum_each", choices=["psum_each", "deferred", "unrolled"])
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"])
    ap.add_argument("--dp-elastic", action="store_true",
                    help="elastic data parallelism: the replica count follows the "
                         "SEBS stage ladder (repro.distributed); on CPU combine with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8. "
                         "Builds its own per-stage data submeshes (incompatible with "
                         "--mesh) and implies accumulate/deferred execution "
                         "(--mode/--accum-mode do not apply)")
    ap.add_argument("--sync-mode", default="exact", choices=["exact", "local"],
                    help="exact: one gradient collective per update, width-invariant "
                         "reduction order; local: local SGD with stage-keyed averaging")
    ap.add_argument("--device-budget", type=int, default=None,
                    help="max data-parallel width (default: all visible devices)")
    ap.add_argument("--local-interval", type=int, default=4,
                    help="local-SGD: updates between parameter averages at stage 0")
    ap.add_argument("--local-growth", type=float, default=1.0,
                    help="local-SGD: geometric growth of the averaging interval per stage")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (full run state, not just params)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a checkpoint every N optimizer updates (0: only at exit)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retain only the newest N checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--stop-after", type=int, default=None,
                    help="exit after N updates WITHOUT a final save "
                         "(simulated preemption, used by the CI resume smoke job)")
    ap.add_argument("--log-json", default=None,
                    help="dump the train log (losses, stages, GNS trajectory) as JSON")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(per-update spans with stage/batch/loss, comm and "
                         "GNS counters; open in Perfetto, summarize with "
                         "tools/trace_view.py)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the metrics registry snapshot (per-stage "
                         "update-time histograms, comm gauges) as JSON")
    ap.add_argument("--steps-log", type=int, default=5)
    args = ap.parse_args(argv)

    if args.dp_elastic and args.mesh != "none":
        ap.error("--dp-elastic builds its own per-stage data submeshes; drop --mesh")
    from repro.optim import _REGISTRY as _OPTIMIZERS

    if args.optimizer not in _OPTIMIZERS:
        ap.error(
            f"unknown --optimizer {args.optimizer!r}; available: {sorted(_OPTIMIZERS)}"
        )
    for flag, value, low in (
        ("--b1", args.b1, 1),
        ("--c1", args.c1, 1),
        ("--stages", args.stages, 1),
        ("--seq", args.seq, 1),
        ("--ckpt-every", args.ckpt_every, 0),
        ("--ckpt-keep", args.ckpt_keep, 1),
        ("--local-interval", args.local_interval, 1),
        ("--steps-log", args.steps_log, 1),
    ):
        if value < low:
            ap.error(f"{flag} must be >= {low} (got {value})")
    if args.rho <= 1.0 and args.schedule in ("sebs", "classical") and args.stages > 1:
        ap.error(f"--rho must be > 1.0 for a multi-stage {args.schedule} ladder")
    if args.ckpt_every and not args.ckpt_dir:
        ap.error("--ckpt-every has no effect without --ckpt-dir")
    if args.stop_after is not None and args.stop_after < 1:
        ap.error(f"--stop-after must be >= 1 (got {args.stop_after})")
    if args.device_budget is not None and args.device_budget < 1:
        ap.error(f"--device-budget must be >= 1 (got {args.device_budget})")
    if args.local_growth < 1.0:
        ap.error(f"--local-growth must be >= 1.0 (got {args.local_growth})")
    if not args.dp_elastic:
        # flags that would otherwise be silently ignored
        defaults = {"sync_mode": "exact", "device_budget": None,
                    "local_interval": 4, "local_growth": 1.0}
        for dest, default in defaults.items():
            if getattr(args, dest) != default:
                ap.error(f"--{dest.replace('_', '-')} requires --dp-elastic")

    cfg = get_config(args.arch, args.variant)
    if args.layers is not None:
        if len(cfg.segments) != 1 or args.layers < 1 or args.layers % len(cfg.segments[0].body):
            ap.error(f"--layers {args.layers} cannot cut {args.arch}: it needs one "
                     "segment and a positive multiple of its block period")
        seg = cfg.segments[0]
        cfg = cfg.replace(segments=(
            dataclasses.replace(seg, repeat=args.layers // len(seg.body)),))
    if args.compute_dtype is not None:
        cfg = cfg.replace(compute_dtype=args.compute_dtype)

    enable_compile_cache()
    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.mesh == "multi")

    model = build_model(cfg)
    opt_kwargs = {"gamma": args.gamma} if args.optimizer == "psgd" else {}
    optimizer = make_optimizer(args.optimizer, **opt_kwargs)

    if args.schedule == "sebs":
        schedule = SEBS(b1=args.b1, C1=args.c1, rho=args.rho, num_stages=args.stages, eta=args.eta)
    elif args.schedule == "classical":
        schedule = ClassicalStagewise(b=args.b1, C1=args.c1, rho=args.rho,
                                      num_stages=args.stages, eta1=args.eta)
    else:
        schedule = AdaptiveSEBS(b1=args.b1, eta=args.eta, rho_max=args.rho,
                                total=args.c1 * args.stages)

    tracer = Tracer() if args.trace else None
    metrics = MetricsRegistry() if args.metrics else None

    ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=args.seq, seed=0)
    if args.dp_elastic:
        from repro.distributed import ElasticTrainer

        trainer = ElasticTrainer(
            model, optimizer, schedule, DataPipeline(ds),
            microbatch=args.b1, sync_mode=args.sync_mode,
            device_budget=args.device_budget,
            local_interval=args.local_interval, local_growth=args.local_growth,
            tracer=tracer, metrics=metrics,
        )
    else:
        trainer = SEBSTrainer(
            model, optimizer, schedule, DataPipeline(ds, mesh),
            mesh=mesh, microbatch=args.b1, mode=args.mode, accum_mode=args.accum_mode,
            tracer=tracer, metrics=metrics,
        )
    params, _ = model.init(jax.random.key(0))
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))

    checkpointer = None
    if args.ckpt_dir:
        checkpointer = CheckpointManager(args.ckpt_dir, keep_last=args.ckpt_keep)
    if args.resume and checkpointer is None:
        ap.error("--resume requires --ckpt-dir")

    state, tlog = trainer.run(
        state,
        log_every=args.steps_log,
        checkpointer=checkpointer,
        save_every=args.ckpt_every,
        resume=args.resume,
        stop_after_updates=args.stop_after,
    )
    for i in range(len(tlog.steps)):
        log.info("update %4d samples %6d stage %d batch %4d loss %.4f",
                 tlog.steps[i], tlog.samples[i], tlog.stages[i],
                 tlog.batch_sizes[i], tlog.losses[i])
    if args.dp_elastic:
        acct = trainer.accountant
        log.info("comm: %d sync events, %.2f MiB/device across stages %s",
                 acct.total_sync_events, acct.total_bytes / 2**20,
                 sorted(acct.per_stage))
    if checkpointer is not None:
        checkpointer.close()
        log.info("checkpoints under %s (latest: update %s)",
                 args.ckpt_dir, checkpointer.latest_step())
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(tlog.as_dict(), f)
        log.info("train log written to %s", args.log_json)
    if tracer is not None:
        tracer.dump_chrome(args.trace)
        log.info("chrome trace (%d events, %d dropped) written to %s",
                 len(tracer.events), tracer.dropped, args.trace)
    if metrics is not None:
        metrics.dump(args.metrics)
        log.info("metrics snapshot (%d series) written to %s", len(metrics), args.metrics)
    return {"trainer": trainer, "state": state, "log": tlog}


if __name__ == "__main__":
    main()
