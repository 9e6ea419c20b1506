"""Declared compile buckets: the repo's one-executable-per-bucket contract.

Every ``jax.jit`` boundary in the serving/training/distributed paths exists
inside a *declared* builder function, and each builder owns a bounded family
of executables (its "bucket"). This registry is the single source of truth
for that contract, consumed from two sides:

- **statically** by rule R301 (``rules_compile``): a ``jax.jit`` call in an
  enforced path that is not inside a registered builder is a lint error —
  the author must either route through an existing builder or register the
  new bucket here, with its cardinality, so reviewers see the compile-cost
  budget change in the diff;
- **at runtime** by the ``REPRO_SANITIZE=1`` sanitizers (``sanitize``): the
  compile-counter audits a live engine's executable caches against the
  declared cardinality (e.g. the paged engine may hold at most one decode
  executable per admission-ladder width and one chunk-prefill executable per
  declared chunk bucket) — a recompile storm trips an assertion instead of
  silently burning the stage-ladder compile budget.

``cardinality`` is the human-readable bound stated in the owning module's
docstring; keep the two in sync when renegotiating a budget.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

#: rel-path substrings in which every jax.jit call must be declared below.
ENFORCED_JIT_PATHS: Tuple[str, ...] = (
    "repro/serve/",
    "repro/train/",
    "repro/distributed/",
    "repro/kernels/paged_decode/",  # serving hot path: ops.py builders only
)


@dataclass(frozen=True)
class CompileBucket:
    """One declared jit boundary and the executable family it may own."""

    key: str  # stable id, e.g. "serve.decode.paged"
    module: str  # rel-path suffix of the owning module
    function: str  # qualname of the builder containing the jax.jit call
    cardinality: str  # declared bound on live executables, human-readable


COMPILE_BUCKETS: Tuple[CompileBucket, ...] = (
    # -- serving ------------------------------------------------------------
    CompileBucket(
        "serve.prefill", "repro/serve/step.py", "build_prefill_step",
        "one executable per distinct prompt length (full-prompt prefill only; "
        "the paged engine prefers chunked prefill)",
    ),
    CompileBucket(
        "serve.decode.static", "repro/serve/step.py", "build_decode_step",
        "one executable per static-batch shape",
    ),
    CompileBucket(
        "serve.decode.paged", "repro/serve/step.py", "build_paged_decode_step",
        "one executable per admission-stage ring width",
    ),
    CompileBucket(
        "serve.prefill.chunk", "repro/serve/step.py", "build_chunk_prefill_step",
        "one executable per declared prefill_chunks bucket",
    ),
    CompileBucket(
        "serve.stream.export", "repro/serve/step.py", "build_page_export_step",
        "one executable per disaggregated engine (fixed (max_pages,) manifest "
        "shape; prefill worker's cross-submesh gather)",
    ),
    CompileBucket(
        "serve.stream.import", "repro/serve/step.py", "build_page_import_step",
        "one executable per disaggregated engine (decode worker's adoption "
        "scatter)",
    ),
    CompileBucket(
        "serve.engine.worker_helpers", "repro/serve/engine.py",
        "PagedWorker.__init__",
        "three fixed-shape helpers per paged worker (COW page copy, state-row "
        "zero, encoder for encoder-decoder models), one executable each",
    ),
    # -- serving kernels (paged flash decode; interpret off-TPU) ------------
    CompileBucket(
        "kernels.paged.decode", "repro/kernels/paged_decode/ops.py",
        "build_paged_flash_decode",
        "one executable per (pool geometry, head layout, window/softcap) — "
        "in practice one per model, shared across ring widths via batch dim",
    ),
    CompileBucket(
        "kernels.paged.prefill", "repro/kernels/paged_decode/ops.py",
        "build_paged_chunk_prefill",
        "one executable per declared prefill_chunks bucket (chunk size is in "
        "the query shape)",
    ),
    CompileBucket(
        "kernels.paged.sample", "repro/kernels/paged_decode/ops.py",
        "build_fused_sample",
        "one executable per (ring width, vocab) decode shape",
    ),
    # -- training -----------------------------------------------------------
    CompileBucket(
        "train.step", "repro/train/step.py", "build_train_step",
        "one executable per (microbatch, accum_steps) stage plan — S stages "
        "compile exactly S variants in accumulate mode",
    ),
    CompileBucket(
        "train.eval", "repro/train/step.py", "build_eval_step",
        "one executable per eval batch shape",
    ),
    # -- elastic data parallelism ------------------------------------------
    CompileBucket(
        "distributed.step.exact", "repro/distributed/step.py",
        "build_elastic_train_step",
        "one executable per (width, local_accum) stage placement",
    ),
    CompileBucket(
        "distributed.step.local", "repro/distributed/step.py",
        "build_local_train_step",
        "one executable per (width, local_accum) stage placement",
    ),
    CompileBucket(
        "distributed.reshard.broadcast", "repro/distributed/reshard.py",
        "broadcast_state",
        "one executable per elastic width transition (stage boundaries only)",
    ),
    CompileBucket(
        "distributed.reshard.sync", "repro/distributed/reshard.py",
        "build_sync_step",
        "one executable per local-SGD width",
    ),
)


def buckets_for(rel: str) -> Dict[str, CompileBucket]:
    """qualname -> bucket for the module at rel-path ``rel``."""
    return {
        b.function: b for b in COMPILE_BUCKETS if rel.endswith(b.module)
    }


def enforced(rel: str) -> bool:
    return any(s in rel for s in ENFORCED_JIT_PATHS)


def modules_declared() -> List[str]:
    return sorted({b.module for b in COMPILE_BUCKETS})
