"""Elastic data-parallel training: the mesh follows the SEBS batch ladder.

SEBS's distributed claim is that geometric batch enlargement means
geometrically fewer parameter updates and therefore fewer gradient
synchronizations. This package makes the claim structural: stage s runs
``accum = ρˢ`` microbatch gradients per update, and the
:class:`ElasticMeshPlanner` maps that accumulation count onto a
data-parallel width — narrow early stages (spare devices idle, local
accumulation), geometrically wider later stages up to the device budget.
:class:`SyncScheduler` chooses between ``exact`` sync (one collective per
update) and ``local`` SGD (parameter averages on a stage-keyed cadence),
with a :class:`CommAccountant` ledger of collectives and bytes.

Resharding invariants (enforced by tests/test_distributed.py):

1. **Placement never changes values.** Width transitions move copies of
   the train state (replicate, stack, collapse) — every leaf is bitwise
   unchanged. Rule-based placement via sharding/partitioning.py inherits
   its divisibility fallback, so an indivisible rule replicates rather
   than repartitions.
2. **The reduction tree is width-invariant.** Exact-sync gradients are
   summed by a canonical pairwise tree over the GLOBAL accumulation index
   (distributed/step.py); replicas compute subtrees and the all-gathered
   combine finishes the same tree. Hence losses, stage transitions and
   final params are bit-identical across any planner-legal width, and
   across elastic width changes at stage boundaries, where each replica's
   per-microbatch program rounds as the width-1 program does: on the CPU
   host at f32 compute (what the tests pin). At bf16 compute, and on a TPU
   at either dtype, the programs of different widths round differently and
   the runs agree closely in loss (1e-4 to 1e-2 apart), not bit for bit
   (``chip_smoke.py --chips 4`` measures it on four chips).
3. **Checkpoints are width-agnostic.** Only the collapsed single-copy
   state is ever serialized (local-SGD saves snap to averaging points), so
   a checkpoint written at width W restores at any width W′ — elastic
   kill-equivalence reduces to ordinary kill-equivalence plus invariants
   1–2.
4. **Data is offset-keyed, not replica-keyed.** Batch contents depend only
   on the consumed-sample offset (data/pipeline.py), so every width
   materializes the same rows in the same microbatch order.
"""
from repro.distributed.planner import ElasticMeshPlanner, MeshPlan
from repro.distributed.reshard import (
    broadcast_state,
    build_sync_step,
    collapse_state,
    float_state_bytes,
    reshard_state,
    state_shardings,
)
from repro.distributed.step import (
    build_elastic_train_step,
    build_local_train_step,
    span_tree_sum,
)
from repro.distributed.sync import (
    SYNC_MODES,
    CommAccountant,
    SyncScheduler,
    allgather_bytes_per_device,
    allreduce_bytes_per_device,
    sync_cost,
)
from repro.distributed.trainer import ElasticTrainer

__all__ = [
    "ElasticMeshPlanner",
    "MeshPlan",
    "ElasticTrainer",
    "SyncScheduler",
    "CommAccountant",
    "SYNC_MODES",
    "build_elastic_train_step",
    "build_local_train_step",
    "build_sync_step",
    "span_tree_sum",
    "broadcast_state",
    "collapse_state",
    "reshard_state",
    "state_shardings",
    "float_state_bytes",
    "allgather_bytes_per_device",
    "allreduce_bytes_per_device",
    "sync_cost",
]
