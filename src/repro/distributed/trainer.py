"""ElasticTrainer — SEBSTrainer with a stage-elastic data-parallel mesh.

Subclasses :class:`repro.core.trainer.SEBSTrainer` through its hook seams:
the schedule/checkpoint/GNS plumbing is inherited unchanged; this class
decides where state lives (which submesh, replica-stacked or collapsed),
how batches are placed, and when replicas synchronize.

Guarantees (exact mode, see tests/test_distributed.py):

- width equivalence: losses, stage transitions, GNS trajectory and final
  params are bit-identical at every device budget, including across an
  elastic width change at a stage boundary (at f32 compute on the CPU
  host; see invariant 2 in ``repro.distributed`` for where it fails);
- elastic kill-equivalence: a run killed at any update under budget W and
  resumed under budget W′ reproduces the uninterrupted run bit-for-bit
  (checkpoints always hold the collapsed, width-agnostic state; the
  offset-keyed data pipeline shows every width the same rows).

Local-SGD mode trades those bit guarantees for communication: replicas
drift between parameter averages (cadence keyed to the SEBS stage), so
checkpoints snap to averaging points and trajectories are width-dependent
by construction. The CommAccountant quantifies the trade on both modes.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stages import StepPlan
from repro.core.trainer import SEBSTrainer
from repro.data.pipeline import DataPipeline
from repro.distributed.planner import ElasticMeshPlanner, MeshPlan
from repro.distributed.reshard import (
    broadcast_state,
    build_sync_step,
    collapse_state,
    float_state_bytes,
    reshard_state,
)
from repro.distributed.step import build_elastic_train_step, build_local_train_step
from repro.distributed.sync import (
    CommAccountant,
    SyncScheduler,
    allreduce_bytes_per_device,
    sync_cost,
)
from repro.optim.base import Optimizer
from repro.train.state import TrainState
from repro.utils.tree import tree_size


class ElasticTrainer(SEBSTrainer):
    def __init__(
        self,
        model,
        optimizer: Optimizer,
        schedule,
        pipeline: DataPipeline,
        *,
        sync_mode: str = "exact",
        device_budget: Optional[int] = None,
        devices=None,
        microbatch: Optional[int] = None,
        grad_clip: float = 0.0,
        seed: int = 0,
        param_axes=None,
        local_interval: int = 4,
        local_growth: float = 1.0,
        tracer=None,
        metrics=None,
    ):
        super().__init__(
            model, optimizer, schedule, pipeline,
            mesh=None, microbatch=microbatch, mode="accumulate",
            accum_mode="deferred", grad_clip=grad_clip, seed=seed,
            tracer=tracer, metrics=metrics,
        )
        self.planner = ElasticMeshPlanner(device_budget=device_budget, devices=devices)
        self.sync = SyncScheduler(
            mode=sync_mode, local_interval=local_interval, local_growth=local_growth
        )
        self.accountant = CommAccountant()
        self.param_axes = param_axes
        self._width: Optional[int] = None   # realized width (None = not placed yet)
        self._stacked = False               # replica-stacked layout (local mode)
        self._mp: Optional[MeshPlan] = None
        self._last_sync = 0                 # update index of the last average
        self._updates_done = 0              # optimizer updates executed so far
        self._sync_steps: Dict[int, object] = {}
        self._grad_bytes: Optional[int] = None   # f32 gradient payload
        self._state_bytes: Optional[int] = None  # float state payload (local sync)

    # -- compiled-program caches --------------------------------------------

    def _elastic_step(self, mp: MeshPlan):
        stacked = self.sync.mode == "local" and mp.width > 1
        key = ("local" if stacked else "exact", mp.width, mp.local_accum)
        if key not in self._steps:
            mesh = self.planner.mesh_for(mp.width)
            build = build_local_train_step if stacked else build_elastic_train_step
            self._steps[key] = build(
                self.model, self.optimizer, mesh,
                width=mp.width, local_accum=mp.local_accum,
                grad_clip=self.grad_clip, donate=True,
            )
        return self._steps[key]

    def _sync_step(self, width: int):
        if width not in self._sync_steps:
            self._sync_steps[width] = build_sync_step(self.planner.mesh_for(width))
        return self._sync_steps[width]

    # -- run-loop hooks ------------------------------------------------------

    def _before_update(self, state: TrainState, plan: StepPlan) -> TrainState:
        mp = self.planner.plan_for(plan)
        if self._grad_bytes is None:
            ref = collapse_state(state) if self._stacked else state
            self._grad_bytes = tree_size(ref.params) * 4  # grads travel in f32
            self._state_bytes = float_state_bytes(ref)
        if mp.width != self._width:
            state = self._transition(state, mp, plan.stage)
        self._mp = mp
        return state

    def _transition(self, state: TrainState, mp: MeshPlan, stage: int) -> TrainState:
        """Move state to the new width. Average+collapse first if replicas
        were drifting (local mode); then replicate or re-stack. Placement
        never changes values in exact mode — the invariant the width-
        equivalence tests pin down."""
        with self.tracer.span(
            "train.reshard", old=self._width or 0, new=mp.width, stage=stage
        ):
            return self._transition_inner(state, mp, stage)

    def _transition_inner(self, state: TrainState, mp: MeshPlan, stage: int) -> TrainState:
        first_placement = self._width is None
        if self._stacked:  # leaving a local-SGD stage: one final average
            state = collapse_state(self._sync_step(self._width)(state))
            self._stacked = False
            # the boundary average IS a sync: restart the stage-keyed
            # cadence from here, or the first window of the new stage would
            # pay a second full-state all-reduce almost immediately
            self._last_sync = self._updates_done
            if not first_placement:
                self.accountant.record_reshard(
                    stage,
                    bytes_moved=allreduce_bytes_per_device(self._state_bytes, self._width),
                )
        mesh = self.planner.mesh_for(mp.width)
        if self.sync.mode == "local" and mp.width > 1:
            state = broadcast_state(state, mp.width, mesh)
            self._stacked = True
        else:
            state = reshard_state(state, mesh, self.param_axes)
        if not first_placement:
            # only WIDENING moves bytes: each joining replica receives one
            # full state copy; narrowing just drops copies already in place
            widened = mp.width > (self._width or 1)
            self.accountant.record_reshard(
                stage, bytes_moved=self._state_bytes if widened else 0
            )
        self._width = mp.width
        return state

    def _place_batch(self, batch: dict, plan: StepPlan) -> dict:
        mp = self._mp
        batch = {
            k: v.reshape((plan.accum_steps, plan.microbatch) + v.shape[1:])
            for k, v in batch.items()
        }
        if mp.width > 1:
            sharding = NamedSharding(self.planner.mesh_for(mp.width), P("data"))
            batch = {k: jax.device_put(v, sharding) for k, v in batch.items()}
        return batch

    def _execute(self, state: TrainState, batch: dict, plan: StepPlan):
        step = self._elastic_step(self._mp)
        state, metrics = step(
            state, batch, jnp.float32(plan.lr), jnp.int32(plan.stage)
        )
        if self._stacked:
            # replica-stacked metrics: report the replica mean (host-side,
            # no collective). Drop the grad-norm pair: replicas drift
            # between averages, so the McCandlish (b_small, b_big) estimator
            # does not describe the replica-local gradients — starve the GNS
            # rather than feed it a mismeasured batch size.
            metrics = {
                k: jnp.mean(v, axis=0)
                for k, v in metrics.items()
                if k not in ("grad_sq_small", "grad_sq_big")
            }
        return state, metrics

    def _step_unrolled(self, plan: StepPlan) -> bool:
        return False  # the elastic steps sum microbatches in their own loop

    def _after_update(self, state: TrainState, update: int, plan: StepPlan) -> TrainState:
        mp = self._mp
        self._updates_done = update
        if not self._stacked:
            # exact sync: the step itself all-gathered gradient partials
            collectives, bytes_moved = sync_cost(
                "exact", mp.width,
                grad_bytes=self._grad_bytes, state_bytes=self._state_bytes,
            )
            self.accountant.record_update(
                plan.stage, collectives=collectives, bytes_moved=bytes_moved
            )
            self._last_sync = update
            return state
        if self.sync.due(update, self._last_sync, plan.stage):
            state = self._sync_step(mp.width)(state)
            self._last_sync = update
            # local-SGD averages are rare by design: worth a point event
            # (exact-mode per-update syncs are implied by every span)
            self.tracer.instant("train.sync", update=update, stage=plan.stage)
            collectives, bytes_moved = sync_cost(
                "local", mp.width,
                grad_bytes=self._grad_bytes, state_bytes=self._state_bytes,
            )
            self.accountant.record_update(
                plan.stage, collectives=collectives, bytes_moved=bytes_moved
            )
        else:
            self.accountant.record_update(plan.stage)
        return state

    def _comm_counters(self) -> tuple[int, int]:
        return self.accountant.total_bytes, self.accountant.total_sync_events

    def _report_comm(self, comm_bytes: int, sync_events: int) -> None:
        # the obs layer reads the SAME cumulative numbers TrainLog records
        self.metrics.gauge("train.comm_bytes").set(comm_bytes)
        self.metrics.gauge("train.sync_events").set(sync_events)
        if self.tracer.enabled:
            self.tracer.counter("train.comm", bytes=comm_bytes, syncs=sync_events)

    def _ready_to_save(self, update: int) -> bool:
        # local-SGD replicas are only checkpoint-consistent right after an
        # average; exact mode is consistent after every update
        return not self._stacked or self._last_sync == update

    def _save_view(self, state: TrainState) -> TrainState:
        return collapse_state(state) if self._stacked else state

    def _finalize(self, state: TrainState) -> TrainState:
        if self._stacked:
            state = collapse_state(self._sync_step(self._width)(state))
            self._stacked = False
        return state

    def _meta_extra(self) -> dict:
        return {
            "accountant": self.accountant.state(),
            "data_width": self._width,
            "sync_mode": self.sync.mode,
        }

    def _restore_extra(self, meta: dict) -> None:
        if meta.get("accountant") is not None:
            self.accountant.restore(meta["accountant"])
        # state itself was restored collapsed (the only serialized layout);
        # the next _before_update reshards it onto whatever width THIS
        # run's planner assigns — elastic resume is just a cold placement
        self._width = None
        self._stacked = False
        self._last_sync = self._updates_done = int(meta.get("update", 0))
