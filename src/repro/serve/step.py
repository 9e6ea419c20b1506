"""Serving steps.

``decode_step`` — one new token per request against a KV/state cache of
``cache_len`` (this is what the decode_32k / long_500k dry-run shapes
lower). The attention KV caches carry a ``kv_seq → data`` sharding so the
524 288-token cache of the long-context shape is distributed over the data
axis (sequence/context parallelism at decode); SSM states are O(1) and
shard over heads.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.models.lm import LanguageModel


def build_prefill_step(model: LanguageModel, *, donate: bool = True):
    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache)

    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(prefill, **kwargs)


def build_decode_step(model: LanguageModel, *, donate: bool = True):
    def decode(params, token, cache, cache_index, memory=None):
        logits, new_cache = model.decode_step(params, token, cache, cache_index, memory=memory)
        return logits, new_cache

    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(decode, **kwargs)


def sample_tokens(logits, key, temperature, top_k):
    """Per-row token sampling. logits: (B, V) f32; temperature: (B,) f32
    (0 → greedy); top_k: (B,) int32 (0 → full vocab). Rows are independent,
    so mixed greedy/sampled requests share one decode step."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # per-row top-k truncation: drop logits below the k-th largest value
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=1
    )
    masked = jnp.where((top_k[:, None] > 0) & (logits < kth), -jnp.inf, logits)
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jnp.argmax(
        scaled + jax.random.gumbel(key, (b, v), jnp.float32), axis=-1
    ).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def build_paged_decode_step(model: LanguageModel, width: int, *, donate: bool = True):
    """Fixed-shape decode tick over a paged slot ring.

    Every slot advances one token at its own cache depth; freed slots ride
    along masked out (their sampled token is discarded), so the compiled
    shape depends only on the ring width — one compile per admission stage.
    KV reads/writes go through a per-slot ``page_table`` (B, max_pages) into
    the shared page pool, and the cache's recurrent-state leaves stay at the
    full ``max_slots`` width: the step slices the first ``width`` rows
    (static per compile), advances them, and writes them back — so stage
    ramps never reshape device state and the chunk-prefill executable (which
    sees the full-width tree) never recompiles.

    The tick doubles as the tail of a chunked prefill: a slot still being
    prefilled rides along *teacher-forced* — the host feeds the next prompt
    token instead of the last sample, the KV/state write at its position is
    exactly what prefill would have produced, and the sampled output is
    discarded until the final prompt token (whose sample is the request's
    first generated token).

    With ``cfg.decode_kernel == "pallas"`` the tick samples through the
    fused logits→token kernel (kernels/paged_decode), which reproduces
    :func:`sample_tokens` token-for-token from the same key.
    """
    vocab = model.cfg.vocab_size
    if model.cfg.decode_kernel == "pallas":
        from repro.kernels.paged_decode import ops as paged_ops

        sample = paged_ops.fused_sample
    else:
        sample = sample_tokens

    def step(params, tokens, cache, cache_pos, page_table, active, temperature, top_k, key, memory=None):
        sliced = model.paged_state_slice(cache, width)
        mem = None if memory is None else memory[:width]
        logits, new_sliced = model.decode_step(
            params, tokens, sliced, cache_pos, memory=mem, page_table=page_table
        )
        logits = logits[:, -1, :vocab].astype(jnp.float32)
        nxt = sample(logits, key, temperature, top_k)
        nxt = jnp.where(active, nxt, tokens[:, 0])
        new_cache = model.paged_state_merge(cache, new_sliced, width, active=active)
        return nxt, new_cache

    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(step, **kwargs)


def build_chunk_prefill_step(model: LanguageModel, *, donate: bool = True):
    """Paged chunk prefill: one call computes ``chunk`` prompt tokens. The
    chunk size is baked into the token shape and everything else (position
    offset, state row, page table content) is traced — one compiled
    executable per chunk-size bucket, regardless of prompt length mix."""

    def step(params, tokens, cache, pos_start, slot, page_table, memory=None):
        return model.prefill_chunk(
            params, tokens, cache, pos_start, slot, page_table, memory=memory
        )

    kwargs = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(step, **kwargs)


def build_page_export_step(model: LanguageModel):
    """Page-streaming gather (disaggregated serving, prefill side): pull one
    slot's prompt pages + recurrent state row out of the prefill pool as a
    pool-size-free block ready for ``device_put`` to the decode submesh.
    ``page_ids`` is always ``(max_pages,)`` (scratch-0 padded), so one
    executable per engine covers every prompt length."""

    def step(cache, page_ids, slot):
        return model.paged_export_slot(cache, page_ids, slot)

    return jax.jit(step)


def build_page_import_step(model: LanguageModel, *, donate: bool = False):
    """Page-streaming scatter (disaggregated serving, decode side): write a
    streamed block into this pool at the remapped ``page_ids`` (0 routes a
    lane to the scratch page: padding, or pages the local prefix index
    already holds) and the state row at ``slot``. One executable per
    engine."""

    def step(cache, block, page_ids, slot):
        return model.paged_import_slot(cache, block, page_ids, slot)

    kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(step, **kwargs)
