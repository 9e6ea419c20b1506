"""SEBSTrainer — glue between schedule, stage controller, data pipeline,
optimizer and the jitted train step.

Runs any :class:`Schedule` (SEBS, classical stagewise, DB-SGD, ...) over any
LM from the zoo, in either batch-growth execution mode. Train steps are
compiled per distinct (microbatch, accum_steps) pair and cached — SEBS with
S stages compiles exactly S step variants in `accumulate` mode.

Also the reference implementation of the paper's headline accounting: it
tracks (samples_consumed, parameter_updates) so experiments can plot loss
against *computation* complexity and against *iteration* complexity
(paper Fig. 3 left/right panels).

Fault tolerance: :meth:`SEBSTrainer.run` takes a
:class:`repro.checkpoint.CheckpointManager` and snapshots the FULL run
state every ``save_every`` updates — params, optimizer state, step counter,
host RNG, pipeline position, stateful-schedule internals (AdaptiveSEBS),
the GradientNoiseScale EMA and the log so far. The contract is
*kill-equivalence*: a run killed after any update and resumed from the
latest checkpoint produces bit-identical losses, stage transitions and
final params to an uninterrupted run (see tests/test_resume.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.checkpoint import CheckpointManager
from repro.core.noise_scale import GradientNoiseScale
from repro.core.schedules import Schedule
from repro.core.stages import StageController, StepPlan
from repro.data.pipeline import DataPipeline
from repro.obs.metrics import NULL_METRICS, MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer
from repro.optim.base import Optimizer
from repro.train.state import TrainState
from repro.train.step import build_train_step, unrolls


@dataclass
class TrainLog:
    steps: List[int] = field(default_factory=list)
    samples: List[int] = field(default_factory=list)
    stages: List[int] = field(default_factory=list)
    batch_sizes: List[int] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)
    noise_scales: List[float] = field(default_factory=list)
    # CUMULATIVE communication counters at each logged update (per-device
    # bytes moved by gradient/parameter synchronization, and the number of
    # sync collectives issued). Populated by the elastic data-parallel
    # trainer's CommAccountant (repro.distributed); the single-process
    # trainer logs zeros. Cumulative so they survive checkpoint/resume
    # without re-deriving per-interval deltas.
    comm_bytes: List[int] = field(default_factory=list)
    sync_events: List[int] = field(default_factory=list)

    def as_dict(self) -> Dict[str, list]:
        # copies, not views: checkpoint meta is serialized by an async
        # writer thread while the train loop keeps appending
        return {f.name: list(getattr(self, f.name)) for f in dataclasses.fields(self)}

    @classmethod
    def from_dict(cls, d: Dict[str, list]) -> "TrainLog":
        log = cls(**{f.name: list(d.get(f.name, [])) for f in dataclasses.fields(cls)})
        # checkpoints written before the comm counters existed: pad to the
        # logged length so the per-update alignment with `steps` holds
        for name in ("comm_bytes", "sync_events"):
            lst = getattr(log, name)
            if len(lst) < len(log.steps):
                lst.extend([0] * (len(log.steps) - len(lst)))
        return log


# the metrics the run loop reads back from each update
_READ_KEYS = ("loss", "grad_sq_small", "grad_sq_big")


@dataclass
class _Dispatched:
    """An update dispatched to the device, with what its bookkeeping needs
    once its metrics are read back."""

    update: int
    plan: StepPlan
    metrics: dict           # device arrays until read
    span: Any               # its ``train.update`` span: takes the loss
    t0: float               # its start, on the tracer's clock
    comm: tuple[int, int]   # ``_comm_counters()`` as of this update
    save: bool              # a checkpoint is due here


class SEBSTrainer:
    def __init__(
        self,
        model,
        optimizer: Optimizer,
        schedule: Schedule,
        pipeline: DataPipeline,
        *,
        mesh=None,
        microbatch: Optional[int] = None,
        mode: str = "accumulate",
        accum_mode: str = "deferred",
        grad_clip: float = 0.0,
        seed: int = 0,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.controller = StageController(schedule, microbatch=microbatch, mode=mode)
        self.pipeline = pipeline
        self.mesh = mesh
        self.accum_mode = accum_mode
        self.grad_clip = grad_clip
        # observability: no-op singletons unless attached; the trainer's
        # only clock reads go through the tracer's injected seam (R103:
        # no ambient wall-clock in core/), and instrumentation must not
        # perturb the update path — losses stay bit-identical with metrics
        # enabled (tests/test_obs.py)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_METRICS
        self._clock = self.tracer.clock
        # Host-side RNG for any non-data stochastic decision (sampling-with-
        # replacement datasets, stochastic eval triggers, ...). Data batches
        # themselves are keyed by sample offset, NOT by this generator — but
        # its state is checkpointed so consumers stay kill-equivalent too.
        self.host_rng = np.random.default_rng(seed)
        self._steps: Dict[tuple, Callable] = {}
        self._last_saved: Optional[int] = None  # update index of the last checkpoint

    def _step_fn(self, plan: StepPlan) -> Callable:
        key = (plan.microbatch, plan.accum_steps)
        if key not in self._steps:
            self._steps[key] = build_train_step(
                self.model,
                self.optimizer,
                self.mesh,
                accum_steps=plan.accum_steps,
                mode=self.accum_mode,
                grad_clip=self.grad_clip,
                donate=True,
            )
        return self._steps[key]

    def _shape_batch(self, batch: dict, plan: StepPlan) -> dict:
        if plan.accum_steps == 1:
            return batch
        return {
            k: v.reshape((plan.accum_steps, plan.microbatch) + v.shape[1:])
            for k, v in batch.items()
        }

    # -- checkpointing ------------------------------------------------------

    def _save(self, ckpt: CheckpointManager, update: int, state: TrainState,
              log: TrainLog, gns: GradientNoiseScale) -> None:
        """Snapshot the full run state after optimizer update ``update``."""
        meta = {
            "update": update,
            "pipeline": self.pipeline.state(),
            "gns": gns.state(),
            "host_rng": self.host_rng.bit_generator.state,
            "log": log.as_dict(),
        }
        if hasattr(self.controller.schedule, "state"):
            meta["schedule"] = self.controller.schedule.state()
        meta.update(self._meta_extra())
        with self.tracer.span("train.save", update=update):
            ckpt.save(update, {"train_state": self._save_view(state)}, meta=meta)
        self._last_saved = update

    def _restore(self, ckpt: CheckpointManager, state: TrainState,
                 log: TrainLog, gns: GradientNoiseScale):
        """Restore the latest checkpoint, if any. Returns (state, update)."""
        restored = ckpt.restore_latest({"train_state": state})
        if restored is None:
            return state, 0
        tree, meta = restored
        # put leaves back on device: the jitted step donates its state
        # argument, which raw numpy views cannot satisfy
        state = jax.tree.map(jnp.asarray, tree["train_state"])
        self.pipeline.restore(meta["pipeline"])
        gns.restore(meta["gns"])
        self.host_rng.bit_generator.state = meta["host_rng"]
        if meta.get("schedule") is not None and hasattr(self.controller.schedule, "restore"):
            self.controller.schedule.restore(meta["schedule"])
        saved_log = TrainLog.from_dict(meta["log"])
        for f in dataclasses.fields(TrainLog):
            getattr(log, f.name)[:] = getattr(saved_log, f.name)
        self._restore_extra(meta)
        return state, int(meta["update"])

    # -- subclass hooks (repro.distributed.ElasticTrainer) ------------------
    #
    # The run loop below is deliberately factored through these seams so the
    # elastic data-parallel trainer can change *where* state lives (which
    # mesh, replica-stacked or collapsed) and *when* it synchronizes,
    # without duplicating the schedule/checkpoint/GNS plumbing. All hooks
    # are identity/no-op here.

    def _before_update(self, state: TrainState, plan: StepPlan) -> TrainState:
        """Called before each update's batch is drawn (mesh transitions)."""
        return state

    def _place_batch(self, batch: dict, plan: StepPlan) -> dict:
        """Shape + device placement of the raw pipeline batch."""
        return self._shape_batch(batch, plan)

    def _execute(self, state: TrainState, batch: dict, plan: StepPlan):
        """Run one compiled optimizer update; returns (state, metrics)."""
        step = self._step_fn(plan)
        return step(state, batch, jnp.float32(plan.lr), jnp.int32(plan.stage))

    def _after_update(self, state: TrainState, update: int, plan: StepPlan) -> TrainState:
        """Called after each update (local-SGD averaging, comm accounting)."""
        return state

    def _step_unrolled(self, plan: StepPlan) -> bool:
        """Whether this update's step ran its microbatch loop unrolled."""
        return unrolls(plan.accum_steps, self.accum_mode)

    def _comm_counters(self) -> tuple[int, int]:
        """(cumulative bytes per device, cumulative sync events) for the log."""
        return 0, 0

    def _report_comm(self, comm_bytes: int, sync_events: int) -> None:
        """Re-export the logged comm counters to the registry and the trace;
        nothing here, where no update communicates."""

    def _ready_to_save(self, update: int) -> bool:
        """Whether the run state is checkpoint-consistent at this update
        (local-SGD replicas are only consistent right after an average)."""
        return True

    def _save_view(self, state: TrainState) -> TrainState:
        """The state tree to serialize (collapse replica-stacked layouts)."""
        return state

    def _finalize(self, state: TrainState) -> TrainState:
        """Called once when the loop exits, before the farewell save."""
        return state

    def _meta_extra(self) -> dict:
        return {}

    def _restore_extra(self, meta: dict) -> None:
        pass

    # -- the training loop --------------------------------------------------

    def run(
        self,
        state: TrainState,
        log_every: int = 10,
        *,
        checkpointer: Optional[CheckpointManager] = None,
        save_every: int = 0,
        resume: bool = False,
        stop_after_updates: Optional[int] = None,
    ) -> tuple[TrainState, TrainLog]:
        """Drive the schedule to its sample budget; returns (state, log).

        ``checkpointer`` + ``save_every`` snapshot the full run state every
        ``save_every`` optimizer updates (plus once at exit). ``resume``
        restores from the checkpointer's latest checkpoint when one exists
        (a fresh directory falls through to a cold start).
        ``stop_after_updates`` exits the loop after that many updates —
        the preemption hook the kill-equivalence tests and the CI resume
        smoke job use to simulate a mid-run kill.

        One update is kept in flight: update k+1 is dispatched before update
        k's loss and noise-scale norms are read back (one ``device_get``),
        so the host's bookkeeping overlaps the device. The log, losses and
        final state are those of the synchronous order. Spans: update k's
        ``train.update`` holds its ``train.data`` and ``train.dispatch``
        and the ``train.wait`` on update k-1; one ``train.after`` per
        update read follows it. Where an update is drained (read before the
        next dispatch), its own read joins that wait.
        """
        log = TrainLog()
        gns = GradientNoiseScale()
        update = 0
        save_pending = False
        if resume and checkpointer is not None:
            state, update = self._restore(checkpointer, state, log, gns)
        interrupted = False
        schedule = self.controller.schedule
        # an adaptive schedule (core.noise_scale.AdaptiveSEBS) picks its next
        # plan from the loss (Eq. 8 with observed ε): it reads every update
        # before the next is dispatched
        observes = hasattr(schedule, "observe")

        def settle(d: _Dispatched, host: dict, t1: float) -> None:
            """Update ``d``'s bookkeeping from its metrics, read back."""
            plan, loss = d.plan, float(host["loss"])
            d.span.set_arg("loss", loss)
            with self.tracer.span("train.after", update=d.update):
                self.metrics.histogram(
                    "train.update_s", labels={"stage": plan.stage}
                ).observe(t1 - d.t0)
                self.metrics.counter("train.updates").inc()
                self.metrics.counter("train.samples").inc(plan.batch_size)
                if self._step_unrolled(plan):
                    self.metrics.counter("train.updates_unrolled").inc()
                if sanitize.enabled():
                    sanitize.check_finite_update(
                        dict(d.metrics, loss=loss), update=d.update, stage=plan.stage
                    )
                if observes:
                    schedule.observe(plan.samples_after, loss)
                # the GNS estimator consumes the free per-microbatch grad
                # norms from accumulate mode
                if "grad_sq_big" in host and plan.accum_steps > 1:
                    gns.update(
                        float(host["grad_sq_small"]), float(host["grad_sq_big"]),
                        b_small=plan.microbatch, b_big=plan.batch_size,
                    )
                if d.update % log_every == 0 or plan.samples_after >= schedule.total_samples:
                    log.steps.append(d.update)
                    log.samples.append(plan.samples_after)
                    log.stages.append(plan.stage)
                    log.batch_sizes.append(plan.batch_size)
                    log.losses.append(loss)
                    log.noise_scales.append(gns.b_noise)
                    log.comm_bytes.append(d.comm[0])
                    log.sync_events.append(d.comm[1])
                    self._report_comm(*d.comm)
                    # re-export the GNS EMA through the registry — the obs
                    # layer reads the SAME number TrainLog records
                    self.metrics.gauge("train.gns").set(gns.b_noise)
                    if self.tracer.enabled and not np.isnan(gns.b_noise):
                        # NaN is invalid trace JSON
                        self.tracer.counter("train.gns", b_noise=gns.b_noise)
                if d.save:
                    # a save drains its update first: ``state`` is still its
                    # output, not yet donated to the next step
                    self._save(checkpointer, d.update, state, log, gns)

        inflight: Optional[_Dispatched] = None  # dispatched, not yet read
        for plan in self.controller.plans(start_samples=self.pipeline.samples_consumed):
            if stop_after_updates is not None and update >= stop_after_updates:
                # checked BEFORE the update so a resume whose restored
                # counter already meets the limit doesn't run one extra
                # update; exit WITHOUT a farewell save — resume must replay
                # from the last periodic checkpoint, exactly as after a
                # real kill (simulated preemption)
                interrupted = True
                break
            update += 1
            t0 = self._clock()
            # one update's phases as nested spans; with the tracer's
            # jax_profiler on they share the device's clock in a profile
            with self.tracer.span(
                "train.update", update=update, stage=plan.stage, batch=plan.batch_size
            ) as update_span:
                state = self._before_update(state, plan)
                with self.tracer.span("train.data"):
                    batch = self._place_batch(self.pipeline.next_batch(plan.batch_size), plan)
                with self.tracer.span("train.dispatch"):
                    state, metrics = self._execute(state, batch, plan)
                state = self._after_update(state, update, plan)
                save = False
                if checkpointer is not None and save_every:
                    # saves SNAP to the next checkpoint-consistent update
                    # rather than being dropped: local-SGD replicas are only
                    # consistent right after an average, and its cadence
                    # need not align with save_every
                    save_pending = save_pending or update % save_every == 0
                    save = save_pending and self._ready_to_save(update)
                    save_pending = save_pending and not save
                done = _Dispatched(update, plan, metrics, update_span, t0,
                                   self._comm_counters(), save)
                if inflight is not None:
                    self.metrics.counter("train.updates_overlapped").inc()
                # read before the next dispatch where something needs this
                # update settled: an observing schedule, a save (the next
                # step donates the state it writes), the stop limit and the
                # last plan (run() returns with nothing in flight)
                drain = (observes or save
                         or plan.samples_after >= schedule.total_samples
                         or (stop_after_updates is not None and update >= stop_after_updates))
                due = [d for d in (inflight, done if drain else None) if d is not None]
                host = []
                if due:
                    # blocks until the last of them reached the host
                    with self.tracer.span("train.wait", update=due[-1].update):
                        host = jax.device_get([{k: d.metrics[k] for k in _READ_KEYS
                                                if k in d.metrics} for d in due])
                t1 = self._clock()
            for d, h in zip(due, host):
                settle(d, h, t1)
            inflight = None if drain else done
        state = self._finalize(state)
        if sanitize.enabled():
            sanitize.audit_tracer(self.tracer, where="(train run end)")
        if checkpointer is not None:
            # farewell save unless this exact update was already persisted
            # (tracked explicitly: a periodic save can be SKIPPED when the
            # state isn't replica-consistent, so `update % save_every` alone
            # would lie about what reached disk)
            if not interrupted and update and update != self._last_saved:
                self._save(checkpointer, update, state, log, gns)  # final state
            checkpointer.wait()
        return state, log
