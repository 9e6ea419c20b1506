"""Serving stack: engines, scheduling, and the paged KV memory model.

Engines (``repro.serve.engine``):

- :class:`ServeEngine` — static batch, dense per-row KV cache; the greedy
  reference the scheduled engine is tested against.
- :class:`PagedContinuousBatchingEngine` — the serving engine: FIFO queue +
  slot ring + stagewise (b₁ρˢ) admission ramp over a **paged** cache with
  radix prefix sharing and chunked prefill.
- :class:`DisaggregatedEngine` — the same engine laid out as a prefill
  worker and a decode worker on disjoint submeshes, each with its own page
  pool; finished prefills stream their KV pages across.

Both scheduled engines are built from :class:`~repro.serve.engine.PagedWorker`:
one page pool, its cache and its compiled steps on one device.

Memory model of the paged engine (``repro.serve.pages``):

- Attention KV is stored in a :class:`PagePool` of fixed-size pages; one
  *page* is ``page_size`` token positions across **every** attention cache
  leaf of the model (a cross-layer slab), so a single physical page id per
  logical page suffices. Page 0 is a reserved scratch page: masked decode
  lanes scatter into it harmlessly.
- Each slot owns a page *table* (logical page → physical page); position
  ``p`` lives at ``(table[p // page_size], p % page_size)``. Resident KV
  therefore scales with live tokens, not ``max_slots × cache_len``.
- Recurrent state (SSM / conv / RWKV shift) is O(1) per slot and stays
  dense at full ``max_slots`` width — stage ramps and chunk steps never
  reshape device state, keeping compile counts bounded.
- Prompt prefixes are shared through a :class:`RadixPrefixIndex`: full,
  immutable prompt pages are published to a radix trie after prefill;
  later prompts alias the matched chain (refcounted), and a divergence
  inside a page is served copy-on-write. Published pages are never written
  again; the index's own reference keeps them cached after the owning
  request finishes, until LRU eviction under pool pressure.

Two-pool handoff invariants (disaggregated serving, ``export_pages`` /
``import_pages`` + ``DisaggregatedEngine._stream``):

- **Full pages only.** A transfer carries exactly the prompt's
  ``ceil(len(prompt)/page_size)`` pages; decode writes begin at position
  ``len(prompt)``, i.e. in the import plan's ``new_pages``, so adopted
  (prefix-matched) pages are immutable on the decode side too — adoption
  is by reference, never copy-on-write.
- **Physical ids never cross pools.** A :class:`PageExport` names source
  physical ids; ``import_pages`` allocates destination pages and returns a
  ``remap`` (source id → destination id) covering only the lanes whose
  bytes must land. Lanes the destination index already holds — and the
  padding of the fixed ``(max_pages,)`` manifest — scatter to scratch
  page 0.
- **Refcounts are per pool and re-established, not transferred.** The
  source pool releases a streamed request's pages the moment the export
  gather has read the (functional, immutable) cache value; the destination
  pool's counts come entirely from its own ``import_pages`` plan and
  ``publish_prefix``. ``REPRO_SANITIZE=1`` reconstructs both pools'
  refcounts exactly, independently, after every mutating transition.
- **Each worker publishes to its own radix index.** The prefill index
  deduplicates prompt *compute*; the decode index deduplicates streamed
  *bytes* (a repeated prefix adopts resident pages instead of re-writing
  them). Nothing is ever shared by pointer across the seam.
"""
from repro.serve.step import (
    build_chunk_prefill_step,
    build_decode_step,
    build_page_export_step,
    build_page_import_step,
    build_paged_decode_step,
    build_prefill_step,
    sample_tokens,
)
from repro.serve.pages import (
    AdmissionPlan,
    PageExport,
    PageImport,
    PagePool,
    RadixPrefixIndex,
    export_pages,
    import_pages,
    plan_admission,
    publish_prefix,
    release_pages,
)
from repro.serve.scheduler import (
    AdmissionController,
    Request,
    RequestScheduler,
    Transfer,
    TransferQueue,
)
from repro.serve.slots import PagedSlotManager
from repro.serve.engine import (
    DisaggregatedEngine,
    PagedContinuousBatchingEngine,
    ServeEngine,
)

__all__ = [
    "AdmissionController",
    "AdmissionPlan",
    "DisaggregatedEngine",
    "PageExport",
    "PageImport",
    "PagePool",
    "PagedContinuousBatchingEngine",
    "PagedSlotManager",
    "RadixPrefixIndex",
    "Request",
    "RequestScheduler",
    "ServeEngine",
    "Transfer",
    "TransferQueue",
    "build_chunk_prefill_step",
    "build_decode_step",
    "build_page_export_step",
    "build_page_import_step",
    "build_paged_decode_step",
    "build_prefill_step",
    "export_pages",
    "import_pages",
    "plan_admission",
    "publish_prefix",
    "release_pages",
    "sample_tokens",
]
