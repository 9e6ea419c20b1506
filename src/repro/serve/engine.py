"""Serving engines.

- :class:`ServeEngine` — the static-batch reference: one fixed batch of
  same-length prompts, prefilled together and decoded greedily in lockstep.
- :class:`PagedContinuousBatchingEngine` — the serving path: requests enter
  a FIFO queue (:mod:`repro.serve.scheduler`), are prefilled in chunks into
  a paged KV cache with radix prefix sharing, and a fixed-shape jitted
  decode tick advances every slot at its own depth with per-slot sampling
  params. The active slot budget ramps stagewise (b₁ρˢ) under sustained
  load via :class:`~repro.serve.scheduler.AdmissionController` — the
  serving mirror of SEBS's stagewise batch enlargement — and each stage
  compiles exactly one decode variant (mirroring ``SEBSTrainer._steps``).
- :class:`DisaggregatedEngine` — the same engine laid out as a prefill
  worker and a decode worker on disjoint submeshes.

Both scheduled engines are built from :class:`PagedWorker` (one page pool,
its cache and its compiled steps on one device, with the phase bodies that
touch them) and share intake, stats and tracing through
:class:`_PagedEngine`. Each keeps its own ``run()`` loop: the order of the
phases decides the RNG draws, the stats and the trace.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.models.lm import LanguageModel
from repro.obs.metrics import NULL_METRICS, MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.pages import (
    PagePool,
    RadixPrefixIndex,
    export_pages,
    import_pages,
    plan_admission,
    publish_prefix,
    release_pages,
)
from repro.serve.scheduler import (
    DONE,
    AdmissionController,
    RequestScheduler,
    Transfer,
    TransferQueue,
)
from repro.serve.slots import PagedSlotManager
from repro.serve.step import (
    build_chunk_prefill_step,
    build_decode_step,
    build_page_export_step,
    build_page_import_step,
    build_paged_decode_step,
    build_prefill_step,
    sample_tokens,
)


class ServeEngine:
    def __init__(self, model: LanguageModel, params, cache_len: int = 256):
        self.model = model
        self.params = params
        self.cache_len = cache_len
        self._prefill = build_prefill_step(model, donate=False)
        self._decode = build_decode_step(model, donate=False)

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 16, memory=None) -> np.ndarray:
        """prompts: (B, P) int32. Greedy decode. Returns (B, P+new)."""
        b, p = prompts.shape
        assert p + max_new_tokens <= self.cache_len
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.model.cfg.is_encoder_decoder and memory is None:
            raise ValueError("encoder-decoder model requires audio memory")
        cache = self.model.init_cache(b, self.cache_len)
        if self.model.cfg.is_encoder_decoder:
            batch["audio_embeds"] = memory
            memory = self.model._encode(self.params, batch)
        logits, cache = self._prefill(self.params, batch, cache)
        out = [jnp.asarray(prompts, jnp.int32)]
        token = jnp.argmax(logits[:, -1, : self.model.cfg.vocab_size], axis=-1)[:, None].astype(jnp.int32)
        for i in range(max_new_tokens):
            out.append(token)
            if i == max_new_tokens - 1:
                break
            logits, cache = self._decode(
                self.params, token, cache, jnp.int32(p + i), memory=memory
            )
            token = jnp.argmax(logits[:, -1, : self.model.cfg.vocab_size], axis=-1)[:, None].astype(jnp.int32)
        return np.asarray(jnp.concatenate(out, axis=1))


class PagedWorker:
    """One page pool on one device, and everything that touches it.

    Holds the params, a :class:`~repro.serve.pages.PagePool` (+ optional
    :class:`~repro.serve.pages.RadixPrefixIndex`), the paged cache (KV slab
    + ``num_slots`` recurrent-state rows, allocated once — stage ramps only
    widen host arrays and the compiled tick), and the executable caches the
    sanitizer audits: ``_decodes`` per ring width, ``_chunk_steps`` per
    chunk bucket. ``admission`` bounds the tick widths the audit admits: the
    engine's SEBS controller, or a one-rung ladder at the fixed ring width
    of the disaggregated prefill worker's tail tick. A worker built with no
    ``prefill_chunks`` may never compile a chunk-prefill variant.

    The phase bodies — admit, one prefill chunk, one compiled tick, publish,
    release — are shared by both engines; the order they run in belongs to
    each engine's loop. Counters land in the engine's ``stats`` dict.
    """

    def __init__(
        self,
        name: str,
        model: LanguageModel,
        params,
        admission: AdmissionController,
        stats: Dict[str, Any],
        num_slots: int,
        num_pages: int,
        page_size: int,
        share: bool,
        prefill_chunks=(),
    ):
        self.name = name
        self.model = model
        self.params = params
        self.admission = admission
        self.stats = stats  # the engine's dict: reset in place, never replaced
        self.pool = PagePool(num_pages, page_size)
        self.index = RadixPrefixIndex(self.pool) if share else None
        self.prefill_chunks = tuple(sorted(set(int(c) for c in prefill_chunks)))
        assert min(self.prefill_chunks, default=1) >= 1
        self.cache = model.init_paged_cache(num_pages, page_size, num_slots)
        self._decodes: Dict[int, Any] = {}  # ring width -> paged decode tick
        self._chunk_steps: Dict[int, Any] = {}  # chunk size -> prefill step
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self._copy_page = jax.jit(model.paged_copy_page)
        self._zero_state = jax.jit(model.paged_zero_state_row)
        self.encode = jax.jit(model._encode) if model.cfg.is_encoder_decoder else None

    # -- compiled-step caches ------------------------------------------------
    # both steps donate the paged cache: the worker's only reference is
    # reassigned from each step's return, and without donation every call
    # pays a pool-sized memcpy before it computes anything
    def decode_for(self, width: int):
        if width not in self._decodes:
            self._decodes[width] = build_paged_decode_step(
                self.model, width, donate=True
            )
            self.decode_compiles += 1
        return self._decodes[width]

    def chunk_for(self, size: int):
        if size not in self._chunk_steps:
            self._chunk_steps[size] = build_chunk_prefill_step(self.model, donate=True)
            self.prefill_compiles += 1
        return self._chunk_steps[size]

    # page streaming (disaggregated engine): built on first use, so only the
    # worker that exports or imports holds the step. The export gather reads
    # the *current* cache value, never a donated buffer; the adoption
    # scatter donates the cache, else every adoption copies the whole pool
    # in front of the next decode tick
    @functools.cached_property
    def export_step(self):
        return build_page_export_step(self.model)

    @functools.cached_property
    def import_step(self):
        return build_page_import_step(self.model, donate=True)

    # -- sanitizer seam ------------------------------------------------------
    def audit_pages(self, slots: PagedSlotManager, what: str) -> None:
        """REPRO_SANITIZE=1 hook: exact refcount reconstruction of this
        pool after every pool-mutating transition."""
        if sanitize.enabled():
            plans = [s.plan for s in slots.slots if not s.free]
            sanitize.audit_page_pool(
                self.pool, self.index, plans, where=f"after {self.name} {what}"
            )

    # -- phase bodies --------------------------------------------------------
    def admit(self, slots: PagedSlotManager, i: int, req, total: int):
        """Plan pages for ``total`` positions of ``req`` and install it in
        slot ``i`` with a fresh state row. Returns the plan, or None when the
        pool cannot fit it even after eviction."""
        plan = plan_admission(
            self.pool, self.index, req.prompt, total, share=self.index is not None
        )
        if plan is None:
            return None
        if plan.cow_src is not None:
            # copy-on-write: duplicate the divergence page, reuse its first
            # reuse_len % page_size positions, overwrite from there on
            self.cache = self._copy_page(
                self.cache, jnp.int32(plan.cow_src), jnp.int32(plan.new_pages[0])
            )
            self.stats["cow_copies"] += 1
        self.cache = self._zero_state(self.cache, jnp.int32(i))
        slots.admit(i, req, plan)
        self.stats["prefix_tokens_reused"] += plan.reuse_len
        self.stats["prompt_tokens_total"] += len(req.prompt)
        self.audit_pages(slots, f"admit(slot {i})")
        return plan

    def prefill_chunk(self, slots: PagedSlotManager, i: int, memory=None):
        """Prefill the largest declared chunk that fits slot ``i``'s
        remaining prompt and return its logits; None for a sub-chunk tail,
        which a tick teacher-forces. ``memory`` is the engine's per-slot
        encoder buffer (encoder-decoder models only)."""
        slot = slots.slots[i]
        bucket = max(
            (c for c in self.prefill_chunks if c <= slot.prompt_remaining), default=None
        )
        if bucket is None:
            return None
        step = self.chunk_for(bucket)
        toks = jnp.asarray(slot.request.prompt[slot.fill : slot.fill + bucket][None, :])
        if memory is not None:
            memory = jax.lax.dynamic_slice_in_dim(memory, i, 1, axis=0)
        logits, self.cache = step(
            self.params,
            toks,
            self.cache,
            jnp.int32(slot.fill),
            jnp.int32(i),
            jnp.asarray(slots.page_table[i : i + 1]),
            memory=memory,
        )
        slot.fill += bucket
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens_computed"] += bucket
        return logits

    def tick(self, slots: PagedSlotManager, width: int, key, memory=None):
        """One fixed-shape tick at ``width``: decoding slots advance one
        token, prefilling slots teacher-force their sub-chunk prompt tail
        (counted as prefill). Returns the device tokens and the numbers of
        decoded and teacher-forced lanes."""
        active = slots.active_mask()
        step = self.decode_for(width)
        n_forced = sum(
            1 for i in range(width) if active[i] and slots.slots[i].prefilling
        )
        nxt, self.cache = step(
            self.params,
            jnp.asarray(slots.feed_tokens()[:, None]),
            self.cache,
            jnp.asarray(slots.positions()),
            jnp.asarray(slots.page_table),
            jnp.asarray(active),
            jnp.asarray(slots.temperatures()),
            jnp.asarray(slots.top_ks()),
            key,
            memory=memory,
        )
        self.stats["prefill_tokens_computed"] += n_forced
        return nxt, int(active.sum()) - n_forced, n_forced

    def publish(self, slots: PagedSlotManager, i: int) -> None:
        """Register slot ``i``'s full prompt pages in the radix index, once."""
        slot = slots.slots[i]
        if self.index is None or slot.published:
            return
        publish_prefix(self.index, slot.request.prompt, slot.plan.pages)
        slot.published = True
        self.audit_pages(slots, f"publish(slot {i})")

    def release(self, slots: PagedSlotManager, i: int) -> None:
        release_pages(self.pool, slots.slots[i].plan.pages)
        slots.release(i)
        self.audit_pages(slots, f"release(slot {i})")


class _PagedEngine:
    """What both scheduled engines share: request intake, the admission
    ramp, the engine's one RNG stream, stats, tracing, and the bodies that
    touch them (first-token sampling, the decode tick's record, request
    completion). Subclasses build their workers and their ``run()`` loop."""

    def __init__(
        self,
        model: LanguageModel,
        cache_len: int,
        max_slots: int,
        b1: Optional[int],
        rho: float,
        patience: int,
        admission: Optional[AdmissionController],
        seed: int,
        page_size: int,
        num_pages: Optional[int],
        prefix_cache: bool,
        kernel: str,
        tracer: Optional[Tracer],
        metrics: Optional[MetricsRegistry],
    ):
        if kernel not in ("xla", "pallas"):
            raise ValueError(f"kernel must be 'xla' or 'pallas', got {kernel!r}")
        if model.cfg.decode_kernel != kernel:
            # same params, same pytree: only the attention/sampler dispatch
            # inside the jitted steps changes
            model = type(model)(model.cfg.replace(decode_kernel=kernel))
        self.kernel = kernel
        self.model = model
        self.cache_len = cache_len
        self.page_size = page_size
        self.max_pages = -(-cache_len // page_size)  # logical pages per slot
        self.max_slots = max_slots
        # default (decode) pool: dense-equivalent capacity (+ scratch page 0);
        # pass a smaller num_pages to run under memory pressure (LRU eviction
        # / deferred admission kick in)
        self.num_pages = (
            num_pages if num_pages is not None else 1 + max_slots * self.max_pages
        )
        # recurrent-state (SSM/RWKV) and encoder-decoder prefix state is not
        # addressable by token content alone: sharing silently disables
        cfg = model.cfg
        mixers = {b.mixer for s in cfg.segments for b in s.body}
        self.prefix_sharing = (
            bool(prefix_cache)
            and not cfg.is_encoder_decoder
            and not cfg.num_vision_tokens
            and mixers <= {"attn", "swa"}
        )
        self.admission = admission or AdmissionController(
            b1=b1 if b1 is not None else max_slots,
            rho=rho,
            max_slots=max_slots,
            patience=patience,
        )
        # observability: no-op singletons unless a tracer/registry is
        # attached; ALL engine clock reads route through the tracer's
        # injected clock seam (R103: no ambient wall-clock in serve/)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_METRICS
        self._clock = self.tracer.clock
        self.scheduler = RequestScheduler(clock=self._clock, tracer=self.tracer)
        self._rng = jax.random.key(seed)
        self._chunk_rr = 0  # round-robin cursor over prefilling slots
        self.stats: Dict[str, Any] = self._fresh_stats()

    @staticmethod
    def _fresh_stats() -> Dict[str, Any]:
        return {
            "ticks": 0,
            "decoded_tokens": 0,
            "peak_width": 0,
            # bounded: a long-lived engine ticks indefinitely
            "stage_history": deque(maxlen=4096),
            "prefill_chunks": 0,
            "prefill_tokens_computed": 0,
            "prefix_tokens_reused": 0,
            "prompt_tokens_total": 0,
            "cow_copies": 0,
            # wall time per tick from the tick's start to the decode tokens
            # landing on host — the latency a decoding slot experiences per
            # token, INCLUDING any prompt chunk that the single-mesh tick
            # interleaves in front of the decode step (the head-of-line
            # block disaggregation removes). Only ticks that decoded ≥ 1
            # real (non-teacher-forced) token are recorded.
            "decode_tick_s": deque(maxlen=4096),
        }

    def reset_stats(self) -> None:
        """Zero every counter for a fresh measurement window, in place (the
        dict identity is stable — callers and workers hold a reference), and
        rebase every pool's monotonic high-water mark, so the next
        ``memory_stats()`` reports the peak of the new window — not a
        cold-start warmup's. Published prefix pages, compiled steps and the
        admission ramp are kept; pair with ``engine.admission.reset()`` to
        restart the ramp too."""
        self.stats.clear()
        self.stats.update(self._fresh_stats())
        for worker in self.workers:
            worker.pool.peak_used = worker.pool.used

    # -- request intake ------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        memory=None,
        tag: str = "",
    ) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # max_pages rounds cache_len UP to a page multiple; don't let that
        # widen the per-request bound
        assert prompt.size + max_new_tokens <= self.cache_len, "cache_len too small"
        if self.model.cfg.is_encoder_decoder and memory is None:
            raise ValueError("encoder-decoder model requires per-request audio memory")
        return self.scheduler.submit(
            prompt,
            max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            memory=memory,
            tag=tag,
        )

    # -- bodies shared by both loops -----------------------------------------
    def _next_key(self):
        self._rng, key = jax.random.split(self._rng)
        return key

    def _sample_first(self, req, logits) -> int:
        first = sample_tokens(
            logits[:, -1, : self.model.cfg.vocab_size].astype(jnp.float32),
            self._next_key(),
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
        )
        return int(first[0])

    def _decode_tick(
        self, worker: PagedWorker, slots: PagedSlotManager, width: int, t_tick: float,
        memory=None,
    ) -> np.ndarray:
        """One decode tick on ``worker`` with its stats, trace span and
        metrics; the span opens at ``t_tick``. Returns the tick's tokens on
        the host."""
        nxt, n_decoded, n_forced = worker.tick(slots, width, self._next_key(), memory)
        self.stats["ticks"] += 1
        self.stats["decoded_tokens"] += n_decoded
        self.stats["stage_history"].append(self.admission.stage)
        nxt = np.asarray(nxt)  # block: the tick's tokens reach the host
        if n_decoded > 0:
            # one clock read, shared by the stat deque and the trace span:
            # percentiles derived from either source agree on the exact
            # same floats
            t_now = self._clock()
            self.stats["decode_tick_s"].append(t_now - t_tick)
            self.tracer.complete(
                "serve.decode_tick",
                t_tick,
                t_now,
                width=width,
                decoded=n_decoded,
                forced=n_forced,
            )
            self.metrics.histogram("serve.decode_tick_s").observe(t_now - t_tick)
        self.metrics.counter("serve.decoded_tokens").inc(n_decoded)
        self.metrics.counter("serve.ticks").inc()
        return nxt

    def _trace_counters(self, width: int, pool: Dict[str, int], **queue) -> None:
        if not self.tracer.enabled:
            return
        self.tracer.counter("serve.pool", **pool)
        self.tracer.counter(
            "serve.queue",
            waiting=self.scheduler.num_waiting,
            running=self.scheduler.num_running,
            **queue,
        )
        self.tracer.counter("serve.admission", stage=self.admission.stage, budget=width)
        self.tracer.counter(
            "serve.prefix",
            reused=self.stats["prefix_tokens_reused"],
            total=self.stats["prompt_tokens_total"],
        )

    def _complete(
        self, worker: PagedWorker, slots: PagedSlotManager, i: int, completed
    ) -> None:
        """Finish slot ``i``'s request and return its pages. A request that
        finishes in the tick it started decoding (tail path,
        ``max_new_tokens == 1``) gets its handoff stamped here; both stamps
        are idempotent."""
        req = slots.slots[i].request
        self.scheduler.prefill_done(req)
        self.scheduler.first_token(req)
        self.scheduler.finish(req)
        completed[req.id] = req.tokens()
        worker.release(slots, i)

    def _audit_run_end(self) -> None:
        if sanitize.enabled():
            for worker in self.workers:
                sanitize.audit_engine_compiles(worker, where=f"(run end, {worker.name})")
            sanitize.audit_tracer(self.tracer, where="(run end)")

    # -- reporting -----------------------------------------------------------
    def memory_stats(self) -> Dict[str, Any]:
        """KV memory accounting (attention leaves only; recurrent state is
        O(1)/slot in both layouts): each pool's high-water mark vs what a
        dense per-slot cache pins for the same ring."""
        per_page = self.model.paged_kv_bytes_per_page(self.page_size)
        dense_rows = max(self.stats["peak_width"], 1)
        return {
            "page_size": self.page_size,
            **self._pool_stats(per_page),
            "kv_bytes_dense_equiv": dense_rows * self.max_pages * per_page,
            "prefix_hit_rate": (
                self.stats["prefix_tokens_reused"]
                / max(self.stats["prompt_tokens_total"], 1)
            ),
        }

    def latencies(self) -> Dict[int, float]:
        """Per-request wall-clock latency (submit → finish) for DONE requests."""
        return {
            rid: req.latency
            for rid, req in self.scheduler.requests.items()
            if req.state == DONE
        }


class PagedContinuousBatchingEngine(_PagedEngine):
    """Continuous batching over a paged KV cache with radix prefix sharing.

    Usage: ``submit()`` any number of requests (mixed prompt lengths,
    per-request ``max_new_tokens`` / ``temperature`` / ``top_k``), then
    ``run()`` to completion. ``run`` returns ``{request_id: (P+new,) tokens}``.

    - **Admission ramp**: ``b1``/``rho``/``max_slots``/``patience``
      parameterize it; the default ``b1=None`` starts at ``max_slots`` (no
      ramp). With ``b1 < max_slots`` the slot ring starts narrow and is
      enlarged geometrically only under sustained queue pressure — one
      compiled decode variant per stage.
    - **Memory**: attention KV lives in a :class:`~repro.serve.pages.PagePool`
      of ``page_size``-token pages; a slot holds a page *table*, not a dense
      ``cache_len`` row, so resident KV scales with live tokens (high-water
      mark reported by ``memory_stats()``) instead of
      ``max_slots × cache_len``.
    - **Prefix sharing**: prompts sharing a prefix alias the same published,
      immutable pages through a :class:`~repro.serve.pages.RadixPrefixIndex`
      (token-granular: the divergence page is copy-on-written). Enabled for
      attention-only decoder models; recurrent-state (SSM/RWKV) and
      encoder-decoder families silently disable it.
    - **Chunked prefill**: a prompt is computed in fixed-size chunks (one
      compiled executable per entry of ``prefill_chunks``, since position
      offsets are traced), at most one chunk per engine tick, interleaved
      with decode ticks so long prompts don't stall running requests. The
      sub-chunk tail rides the regular decode tick teacher-forced — zero
      extra compiled shapes for arbitrary prompt lengths.

    Greedy outputs are token-identical to the static :class:`ServeEngine`.
    """

    def __init__(
        self,
        model: LanguageModel,
        params,
        cache_len: int = 256,
        max_slots: int = 8,
        b1: Optional[int] = None,
        rho: float = 2.0,
        patience: int = 2,
        admission: Optional[AdmissionController] = None,
        seed: int = 0,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefill_chunks=(32,),
        kernel: str = "xla",
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        super().__init__(
            model, cache_len, max_slots, b1, rho, patience, admission, seed,
            page_size, num_pages, prefix_cache, kernel, tracer, metrics,
        )
        self.worker = PagedWorker(
            "paged", self.model, params, self.admission, self.stats, max_slots,
            self.num_pages, page_size, self.prefix_sharing, prefill_chunks,
        )
        self.workers = (self.worker,)

    @property
    def decode_compiles(self) -> int:
        return self.worker.decode_compiles

    @property
    def prefill_compiles(self) -> int:
        return self.worker.prefill_compiles

    # -- the serve loop ------------------------------------------------------
    def run(self) -> Dict[int, np.ndarray]:
        """Drive admission + chunked prefill + decode until every submitted
        request is done. Returns results completed during THIS call (re-
        running a long-lived engine does not replay old results)."""
        completed: Dict[int, np.ndarray] = {}
        worker = self.worker
        width = self.admission.budget()
        slots = PagedSlotManager(
            width, self.max_pages, chunk_floor=min(worker.prefill_chunks)
        )
        memory_buf = None
        if self.model.cfg.is_encoder_decoder:
            cfg = self.model.cfg
            memory_buf = jnp.zeros(
                (self.max_slots, cfg.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.compute_dtype),
            )

        while self.scheduler.has_work():
            # 1. stagewise ramp (host-side only: device state is full-width)
            budget = self.admission.observe(self.scheduler.demand)
            if budget > width:
                slots.grow(budget)
                width = budget
            self.stats["peak_width"] = max(self.stats["peak_width"], width)

            # 2. admit queued requests into freed slots; a request that finds
            #    no pages (even after LRU eviction) waits for releases
            admitted = 0
            for i in slots.free_indices():
                req = self.scheduler.pop_waiting()
                if req is None:
                    break
                if worker.admit(slots, i, req, len(req.prompt) + req.max_new_tokens) is None:
                    self.scheduler.requeue(req)
                    break
                if memory_buf is not None:
                    row = worker.encode(worker.params, {"audio_embeds": jnp.asarray(req.memory)})
                    memory_buf = jax.lax.dynamic_update_slice_in_dim(
                        memory_buf, row.astype(memory_buf.dtype), i, axis=0
                    )
                admitted += 1
            if slots.num_active() == 0:
                if admitted == 0 and self.scheduler.has_work():
                    # the requeued head was replanned with the unshared
                    # fallback (full index eviction allowed) and still found
                    # no pages, with no live slot left to release any: the
                    # request is genuinely larger than the pool. Before the
                    # fallback existed, a prefix hit whose pinned pages
                    # wedged eviction raised here spuriously — and a request
                    # requeued at the final tick was lost with the run.
                    raise RuntimeError(
                        f"page pool ({worker.pool.capacity} pages of {self.page_size}) "
                        "cannot fit the next request even after eviction"
                    )
                if not self.scheduler.has_work():
                    break

            # 3. one prefill chunk (round-robin over prefilling slots, so a
            #    long prompt neither stalls decode nor starves other
            #    prefills of their chunk turn)
            t_tick = self._clock()
            prefilling = slots.prefilling_indices()
            self._chunk_rr += 1
            off = self._chunk_rr % max(len(prefilling), 1)
            for i in prefilling[off:] + prefilling[:off]:
                logits = worker.prefill_chunk(slots, i, memory_buf)
                if logits is None:
                    continue  # sub-chunk tail: teacher-forced by the tick below
                slot = slots.slots[i]
                req = slot.request
                if slot.prompt_remaining == 0:
                    slots.start_decoding(i, self._sample_first(req, logits))
                    self.scheduler.prefill_done(req)
                    self.scheduler.first_token(req)
                    worker.publish(slots, i)
                    if len(req.generated) >= req.max_new_tokens:
                        self._complete(worker, slots, i, completed)
                break

            # 4. one fixed-shape decode tick: decoding slots advance one
            #    token, prefilling slots teacher-force their prompt tail
            if not slots.active_mask().any():
                continue
            nxt = self._decode_tick(worker, slots, width, t_tick, memory_buf)
            self._trace_counters(
                width, {"used": worker.pool.used, "capacity": worker.pool.capacity}
            )
            self.metrics.gauge("serve.pool_used").set(worker.pool.used)

            # 5. bookkeeping: newly-decoding slots timestamp their handoff
            #    and publish their prefix, finished requests release pages
            for i in slots.advance(nxt):
                worker.publish(slots, i)
                self._complete(worker, slots, i, completed)
            for i, slot in enumerate(slots.slots):
                if not slot.decoding:
                    continue
                if slot.request.t_prefill_done == 0.0:
                    # tail-path handoff: advance() appended the first token
                    # inside this tick
                    self.scheduler.prefill_done(slot.request)
                    self.scheduler.first_token(slot.request)
                worker.publish(slots, i)

        self._audit_run_end()
        return completed

    def _pool_stats(self, per_page: int) -> Dict[str, Any]:
        pool = self.worker.pool
        return {
            "pages_capacity": pool.capacity,
            "pages_peak": pool.peak_used,
            "kv_bytes_peak": pool.peak_used * per_page,
        }


class DisaggregatedEngine(_PagedEngine):
    """Disaggregated prefill/decode serving across two submeshes.

    The paged engine laid out as two workers (:class:`PagedWorker`) on
    disjoint device groups (:func:`~repro.launch.mesh.make_disagg_submeshes` carves
    them from one ``("pod", "data", "model")`` host mesh; each worker
    anchors to its submesh's lead device):

    - the **prefill worker** runs chunked prefill at its own ring width
      (``prefill_slots``) and chunk shape against a private
      :class:`~repro.serve.pages.PagePool` — long prompts no longer share a
      tick with decode, so they can use large chunk buckets without
      stretching any running request's inter-token latency. Prompt tails
      shorter than the smallest chunk ride the worker's own teacher-forced
      tick, compiled once at the fixed prefill ring width;
    - the **decode worker** runs pure fixed-shape decode ticks behind the
      SEBS admission ladder against its own pool; it compiles *no*
      chunk-prefill variants (one executable per ladder stage, period).

    A finished prefill streams to the decode submesh as a
    :class:`~repro.serve.scheduler.Transfer`: the prompt's full KV pages
    plus the recurrent-state row are gathered into a pool-size-free block
    (``step.build_page_export_step``), ``device_put`` toward the decode
    device — the engine's ONE cross-submesh transfer, pinned to
    :meth:`_stream` by lint rule R105 — and adopted into the decode pool by
    :func:`~repro.serve.pages.import_pages`: page ids remapped, refcounts
    re-established in the destination pool, and the prompt's full pages
    re-published to the decode-side radix index. The prefix index therefore
    spans the seam *at page granularity*: a transfer whose full-page prefix
    is already resident decode-side adopts those pages by reference (its
    streamed lanes scatter to the scratch page), and the prefill worker's
    own index skips recomputing shared prefixes exactly as the single-mesh
    engine does.

    Greedy output is bit-identical to the single-mesh paged engine given
    the same ``prefill_chunks`` (``tests/test_disagg_serve.py`` property-
    tests this, including deferred admission under pool pressure and
    cross-pool prefix adoption): chunk-path KV equals decode-path KV per
    token, sub-chunk prompt tails use the same teacher-forced tick builder
    as the single-mesh engine (at the prefill ring width — rows of the tick
    are independent), streamed pages are bit-exact copies, and greedy
    sampling is argmax, indifferent to the engines' different RNG-stream
    consumption. Encoder-decoder models are not supported (per-request
    encoder memory is dense per-slot state and does not page-stream);
    recurrent-state families are — the state row rides the block.

    With a single visible device both workers share it (degraded mode:
    still two pools, two caches, and a real ``device_put`` seam), so every
    identity property holds under plain CPU tests.
    """

    def __init__(
        self,
        model: LanguageModel,
        params,
        cache_len: int = 256,
        max_slots: int = 8,
        b1: Optional[int] = None,
        rho: float = 2.0,
        patience: int = 2,
        admission: Optional[AdmissionController] = None,
        seed: int = 0,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefill_chunks=(32,),
        kernel: str = "xla",
        prefill_slots: int = 2,
        prefill_pages: Optional[int] = None,
        prefill_device=None,
        decode_device=None,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        super().__init__(
            model, cache_len, max_slots, b1, rho, patience, admission, seed,
            page_size, num_pages, prefix_cache, kernel, tracer, metrics,
        )
        if model.cfg.is_encoder_decoder:
            raise NotImplementedError(
                "disaggregated serving does not support encoder-decoder models: "
                "per-request encoder memory is dense per-slot state and does "
                "not page-stream"
            )
        self.prefill_slots = int(prefill_slots)
        assert self.prefill_slots >= 1
        devices = jax.devices()
        if prefill_device is None:
            prefill_device = devices[0]
        if decode_device is None:
            decode_device = devices[1] if len(devices) > 1 else devices[0]
        self.prefill_device = prefill_device
        self.decode_device = decode_device
        self.transfers = TransferQueue()
        # independent pools: decode sized like the single-mesh engine,
        # prefill sized to its own (smaller) ring — prompts only
        self.prefill_pages = (
            prefill_pages
            if prefill_pages is not None
            else 1 + self.prefill_slots * self.max_pages
        )
        # ALL cross-device placement happens here and in _stream (rule R105
        # pins device_put in serve/ to exactly those two sites): params are
        # replicated per worker, each cache is committed to its worker's
        # device, so every executable dispatches on its own submesh and the
        # only bytes crossing at runtime are streamed page blocks
        self.prefill = PagedWorker(
            "prefill", self.model, jax.device_put(params, prefill_device),
            AdmissionController(b1=self.prefill_slots, max_slots=self.prefill_slots),
            self.stats, self.prefill_slots, self.prefill_pages, page_size,
            self.prefix_sharing, prefill_chunks,
        )
        self.decode = PagedWorker(
            "decode", self.model, jax.device_put(params, decode_device),
            self.admission, self.stats, max_slots, self.num_pages, page_size,
            self.prefix_sharing,
        )
        self.prefill.cache = jax.device_put(self.prefill.cache, prefill_device)
        self.decode.cache = jax.device_put(self.decode.cache, decode_device)
        self.workers = (self.prefill, self.decode)

    def _fresh_stats(self) -> Dict[str, Any]:
        stats = super()._fresh_stats()
        stats.update(transfers=0, pages_streamed=0, pages_adopted=0, seam_bytes=0)
        return stats

    # compiled-variant counters, shaped like the single-mesh engine's for
    # launcher logging: decode variants only ever live on the
    # decode worker, chunk variants only on the prefill worker
    @property
    def decode_compiles(self) -> int:
        return self.decode.decode_compiles

    @property
    def prefill_compiles(self) -> int:
        return self.prefill.prefill_compiles

    # -- the streaming seam --------------------------------------------------
    def _stream(self, block):
        """The one runtime cross-submesh transfer: commit an exported page
        block toward the decode device. jax transfers are async — the copy
        overlaps subsequent prefill chunks and decode ticks; the decode-side
        import scatter synchronizes on arrival. Seam bytes are accounted
        here — the span measures enqueue cost, not arrival (which the
        adoption scatter pays)."""
        nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(block))
        self.stats["seam_bytes"] += nbytes
        self.metrics.counter("serve.seam_bytes").inc(nbytes)
        with self.tracer.span("serve.stream", bytes=nbytes):
            out = jax.device_put(block, self.decode_device)
        if self.tracer.enabled:
            self.tracer.counter("serve.seam", cum_bytes=self.stats["seam_bytes"])
        return out

    # -- prefill side --------------------------------------------------------
    def _chunk_tick(self, pslots: PagedSlotManager, completed) -> None:
        """One chunk per prefilling slot per engine tick (round-robin start,
        so no slot starves inside the ring). Each slot takes the largest
        declared bucket that fits its remaining prompt; a sub-chunk tail is
        left for :meth:`_tail_tick`. A prompt that completes exactly on a
        chunk is sampled from the chunk's logits and handed off before the
        next slot's chunk runs."""
        prefilling = pslots.prefilling_indices()
        if not prefilling:
            return
        self._chunk_rr += 1
        off = self._chunk_rr % len(prefilling)
        for i in prefilling[off:] + prefilling[:off]:
            logits = self.prefill.prefill_chunk(pslots, i)
            if logits is not None and pslots.slots[i].prompt_remaining == 0:
                first = self._sample_first(pslots.slots[i].request, logits)
                self._handoff(pslots, i, first, completed)

    def _tail_tick(self, pslots: PagedSlotManager, completed) -> None:
        """One teacher-forced tick over the prefill ring for prompt tails
        shorter than the smallest chunk bucket — the exact single-mesh tail
        path (the chunked-attention branch needs ≥ 2 tokens), at the fixed
        prefill ring width. A lane consuming its LAST prompt token keeps the
        tick's sample as the request's first generated token and is handed
        off; every prefill-side sample before that is discarded."""
        if not pslots.active_mask().any():
            return
        nxt, _, _ = self.prefill.tick(pslots, self.prefill_slots, self._next_key())
        for i in pslots.advance(np.asarray(nxt)):
            # prompt done AND max_new_tokens == 1: finished without ever
            # touching the seam (advance appended the first token already)
            self.prefill.publish(pslots, i)
            self._complete(self.prefill, pslots, i, completed)
        for i, slot in enumerate(pslots.slots):
            if slot.decoding:
                # prompt completed this tick: reclaim the first token
                # advance() appended (the decode worker re-appends it at
                # adoption) and hand the slot off
                self._handoff(pslots, i, slot.request.generated.pop(), completed)

    def _handoff(self, pslots: PagedSlotManager, i: int, first: int, completed):
        """Prompt fully computed and ``first`` sampled (not yet appended):
        publish the prefix prefill-side, then stream the slot's pages to the
        decode worker — or, for single-token requests, complete right here
        without touching the seam."""
        slot = pslots.slots[i]
        req = slot.request
        self.prefill.publish(pslots, i)
        if req.max_new_tokens <= 1:
            req.generated.append(int(first))
            self._complete(self.prefill, pslots, i, completed)
            return
        export = export_pages(
            slot.plan, req.prompt, page_size=self.page_size, first_token=first
        )
        ids = np.zeros((self.max_pages,), np.int32)
        ids[: len(export.pages)] = export.pages
        block = self.prefill.export_step(self.prefill.cache, jnp.asarray(ids), jnp.int32(i))
        self.transfers.push(Transfer(export=export, block=self._stream(block), request=req))
        self.scheduler.prefill_done(req)
        # the first token was sampled from the final chunk's logits just
        # now — TTFT is the handoff, not the (later) decode-side adoption
        self.scheduler.first_token(req)
        self.stats["transfers"] += 1
        self.stats["pages_streamed"] += len(export.pages)
        # prefill pages release immediately: the export gather above read the
        # functional cache *value*, so reallocating these physical pages to
        # the next admission cannot race the in-flight stream; published
        # pages live on under the prefill index for future prefix hits
        self.prefill.release(pslots, i)

    # -- decode side ---------------------------------------------------------
    def _adopt(self, dslots: PagedSlotManager, i: int, transfer, imp) -> None:
        """Adopt a streamed prefill into decode slot ``i``: scatter the block
        into the decode pool at the remapped physical ids (lanes the local
        prefix index already holds — and padding — route to scratch page 0),
        install the state row, and re-publish the prompt's full pages to the
        decode-side index so later transfers with the same prefix adopt by
        reference instead of re-writing bytes."""
        req = transfer.request
        export = transfer.export
        ids = np.zeros((self.max_pages,), np.int32)
        for j, src in enumerate(export.pages):
            if src in imp.remap:
                ids[j] = imp.remap[src]
        self.decode.cache = self.decode.import_step(
            self.decode.cache, transfer.block, jnp.asarray(ids), jnp.int32(i)
        )
        dslots.admit(i, req, imp.plan)
        dslots.slots[i].fill = len(req.prompt)  # nothing left to prefill: KV arrived by stream
        dslots.start_decoding(i, export.first_token)
        self.decode.publish(dslots, i)
        self.stats["pages_adopted"] += imp.adopted
        self.decode.audit_pages(dslots, f"adopt(slot {i})")

    # -- the serve loop ------------------------------------------------------
    def run(self) -> Dict[int, np.ndarray]:
        """Drive both workers until every submitted request is done. Each
        engine tick: ramp the decode ladder, admit prompts into the prefill
        ring, adopt queued transfers into freed decode slots, run one
        fixed-shape decode tick TO COMPLETION (tokens fetched to host), and
        only then run one chunk per prefilling slot (completions stream
        across, adopted next tick) — so a decode token never waits behind a
        prompt chunk. Returns results completed during THIS call."""
        completed: Dict[int, np.ndarray] = {}
        width = self.admission.budget()
        dslots = PagedSlotManager(width, self.max_pages)
        pslots = PagedSlotManager(
            self.prefill_slots,
            self.max_pages,
            chunk_floor=min(self.prefill.prefill_chunks),
        )

        while self.scheduler.has_work():
            # 1. decode-side stagewise ramp (host arrays only)
            budget = self.admission.observe(self.scheduler.demand)
            if budget > width:
                dslots.grow(budget)
                width = budget
            self.stats["peak_width"] = max(self.stats["peak_width"], width)

            # 2. prefill admission: FIFO into the prefill ring, decoupled
            #    from the decode ladder — a burst of long prompts saturates
            #    prefill without waiting for (or widening) decode slots
            prefill_admitted = 0
            for i in pslots.free_indices():
                req = self.scheduler.pop_waiting()
                if req is None:
                    break
                # prefill holds prompt pages only
                if self.prefill.admit(pslots, i, req, len(req.prompt)) is None:
                    self.scheduler.requeue(req)
                    break
                prefill_admitted += 1
            # the queue head found no prefill pages with the ring empty:
            # no prefill-side release is pending and the unshared-replan
            # fallback already evicted the whole index, so no future tick
            # can do better (decode releases go to the OTHER pool)
            if (
                prefill_admitted == 0
                and pslots.num_active() == 0
                and self.scheduler.num_waiting > 0
            ):
                raise RuntimeError(
                    f"prefill page pool ({self.prefill.pool.capacity} pages of "
                    f"{self.page_size}) cannot fit the next request even "
                    "after eviction"
                )

            # 3. decode admission: adopt blocks streamed by PREVIOUS ticks,
            #    strictly FIFO — a transfer the pool cannot place yet blocks
            #    the queue head and retries next tick, after decode releases
            #    free pages
            decode_admitted = 0
            for i in dslots.free_indices():
                transfer = self.transfers.peek()
                if transfer is None:
                    break
                req = transfer.request
                imp = import_pages(
                    self.decode.pool,
                    self.decode.index,
                    transfer.export,
                    len(req.prompt) + req.max_new_tokens,
                    share=self.prefix_sharing,
                )
                if imp is None:
                    break
                self.transfers.pop()
                self._adopt(dslots, i, transfer, imp)
                decode_admitted += 1
            # the head transfer found no decode pages with the decode ring
            # empty: no decode-side release is pending and import_pages
            # already fell back to unshared planning (full index eviction) —
            # the request's total footprint exceeds the decode pool, forever
            if (
                decode_admitted == 0
                and dslots.num_active() == 0
                and len(self.transfers) > 0
            ):
                raise RuntimeError(
                    f"decode page pool ({self.decode.pool.capacity} pages of "
                    f"{self.page_size}) cannot fit the next streamed transfer "
                    "even after eviction"
                )

            # 4. one pure decode tick, run to completion BEFORE any prefill
            #    work: the decode ring never holds a prefilling slot, so no
            #    lane is teacher-forced — and because the tick's tokens are
            #    fetched before a single prompt chunk is dispatched, a
            #    decode token never waits on concurrent prefill. That is the
            #    head-of-line block the single-mesh engine suffers (its tick
            #    runs chunk-then-decode on one device), measured by
            #    ``stats["decode_tick_s"]`` in both engines.
            if dslots.active_mask().any():
                nxt = self._decode_tick(self.decode, dslots, width, self._clock())
                # 5. finished requests release their decode-pool pages
                for i in dslots.advance(nxt):
                    self._complete(self.decode, dslots, i, completed)
            self._trace_counters(
                width,
                {"decode_used": self.decode.pool.used, "prefill_used": self.prefill.pool.used},
                transfers=len(self.transfers),
            )

            # 6. chunk steps, then one teacher-forced tick for sub-chunk
            #    prompt tails; completions export + stream (adopted at the
            #    next tick's step 3, behind the decode tokens already out)
            self._chunk_tick(pslots, completed)
            self._tail_tick(pslots, completed)

        self._audit_run_end()
        return completed

    def _pool_stats(self, per_page: int) -> Dict[str, Any]:
        # peaks are per worker: they live on different submeshes, and
        # summing them would compare apples to a dense single-device slab
        return {
            "pages_capacity": self.decode.pool.capacity,
            "pages_peak": self.decode.pool.peak_used,
            "prefill_pages_capacity": self.prefill.pool.capacity,
            "prefill_pages_peak": self.prefill.pool.peak_used,
            "kv_bytes_peak": (
                max(self.prefill.pool.peak_used, self.decode.pool.peak_used) * per_page
            ),
        }
