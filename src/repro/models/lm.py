"""The composable language model: embeddings → scanned segments → logits.

Covers all assigned families through :class:`ModelConfig`:

- decoder-only (dense / MoE / SSM / hybrid): ``forward`` (train),
  ``prefill`` and ``decode_step`` (serving, KV/state cache);
- encoder-decoder (whisper): an extra non-causal encoder segment consuming
  stubbed frame embeddings (the conv/mel frontend is out of scope per the
  brief); the decoder cross-attends to encoder memory;
- VLM backbone (internvl2): stubbed patch embeddings enter through a
  trainable 2-layer projector and replace the first ``num_vision_tokens``
  token embeddings.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SegmentSpec, BlockSpec, VISION_EMBED_DIM
from repro.models import blocks
from repro.models.layers import embedding, norm, mlp
from repro.sharding import constrain
from repro.utils.prng import fold_in_name



class LanguageModel:
    """Functional model: ``params = lm.init(key)``, then ``lm.forward`` etc.

    Stateless; all methods are pure functions of (params, inputs) and are
    safe to ``jax.jit`` / ``shard_map``.
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ---------------------------------------------------------------
    def init(self, key) -> tuple[Any, Any]:
        """Returns (params, logical_axes) trees with matching structure."""
        cfg = self.cfg
        params, axes = {}, {}
        p, a = embedding.init(key, cfg)
        params["embed"], axes["embed"] = p, a
        for i, seg in enumerate(cfg.segments):
            p, a = blocks.init_segment(key, cfg, seg, name=f"seg{i}")
            params[f"seg{i}"], axes[f"seg{i}"] = p, a
        p, a = norm.init(cfg.d_model, jnp.dtype(cfg.param_dtype))
        params["final_norm"], axes["final_norm"] = p, a

        if cfg.is_encoder_decoder:
            enc_seg = self.encoder_segment()
            p, a = blocks.init_segment(key, cfg, enc_seg, name="encoder")
            params["encoder"], axes["encoder"] = p, a
            p, a = norm.init(cfg.d_model, jnp.dtype(cfg.param_dtype))
            params["encoder_norm"], axes["encoder_norm"] = p, a
        if cfg.num_vision_tokens:
            k = fold_in_name(key, "vision_proj")
            dtype = jnp.dtype(cfg.param_dtype)
            params["vision_proj"] = {
                "w1": jax.random.normal(k, (VISION_EMBED_DIM, cfg.d_model), dtype)
                * VISION_EMBED_DIM**-0.5,
                "w2": jax.random.normal(fold_in_name(k, "2"), (cfg.d_model, cfg.d_model), dtype)
                * cfg.d_model**-0.5,
            }
            axes["vision_proj"] = {"w1": (None, "embed"), "w2": ("embed", "embed")}
        return params, axes

    def encoder_segment(self) -> SegmentSpec:
        return SegmentSpec(body=(BlockSpec(mixer="attn", ffn="dense"),), repeat=self.cfg.encoder_layers)

    # -- embedding helpers ----------------------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = embedding.embed(params["embed"], batch["tokens"], cfg)
        if cfg.num_vision_tokens and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(x.dtype)
            h = jnp.einsum("bpe,ed->bpd", ve, params["vision_proj"]["w1"].astype(x.dtype))
            h = jax.nn.gelu(h)
            h = jnp.einsum("bpd,de->bpe", h, params["vision_proj"]["w2"].astype(x.dtype))
            nv = cfg.num_vision_tokens
            x = jnp.concatenate([h[:, :nv, :], x[:, nv:, :]], axis=1)
        return x

    def _encode(self, params, batch):
        cfg = self.cfg
        if not cfg.is_encoder_decoder:
            return None
        mem = batch["audio_embeds"].astype(jnp.dtype(cfg.compute_dtype))
        pos = jnp.arange(mem.shape[1])[None, :]
        mem, _, _ = blocks.apply_segment(
            params["encoder"], mem, cfg, self.encoder_segment(),
            positions=pos, causal=False,
        )
        return norm.apply(params["encoder_norm"], mem, cfg.norm_eps)

    # -- train forward --------------------------------------------------------
    def forward(self, params, batch):
        """batch: {tokens (B,S) int32, [audio_embeds], [vision_embeds]}.
        Returns (logits (B,S,V) f32, aux_loss)."""
        cfg = self.cfg
        # jax.named_scope names each part's ops (op_name) in a profile
        with jax.named_scope("embed"):
            x = self._embed_inputs(params, batch)
        memory = self._encode(params, batch)
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        aux = jnp.zeros((), jnp.float32)
        for i, seg in enumerate(cfg.segments):
            x, _, a = blocks.apply_segment(
                params[f"seg{i}"], x, cfg, seg, positions=positions, memory=memory
            )
            aux = aux + a
        with jax.named_scope("final_norm"):
            x = norm.apply(params["final_norm"], x, cfg.norm_eps)
        with jax.named_scope("head"):
            return embedding.logits(params["embed"], x, cfg), aux

    # -- serving ----------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        cache = {}
        for i, seg in enumerate(cfg.segments):
            c = blocks.init_segment_cache(cfg, seg, batch, cache_len, dtype)
            if c:
                cache[f"seg{i}"] = c
        return cache

    def cache_axes(self):
        cfg = self.cfg
        axes = {}
        for i, seg in enumerate(cfg.segments):
            a = blocks.segment_cache_axes(seg)
            if a:
                axes[f"seg{i}"] = a
        return axes

    # -- paged KV cache (continuous batching v2) ------------------------------
    # One merged tree: attention leaves live in a shared page pool
    # ((layers, num_pages, hkv, page_size, hd) — a page id indexes axis 1 of
    # every attention leaf at once), while O(1) recurrent state (SSM, conv,
    # RWKV shift) stays per-slot dense ((layers, state_batch, ...)). The
    # helpers below walk the tree and dispatch on which side of that split a
    # leaf is on (anything under an "attn" key is paged KV).

    def init_paged_cache(self, num_pages: int, page_size: int, state_batch: int,
                         dtype=jnp.bfloat16):
        cfg = self.cfg
        cache = {}
        for i, seg in enumerate(cfg.segments):
            c = blocks.init_segment_cache_paged(
                cfg, seg, num_pages, page_size, state_batch, dtype
            )
            if c:
                cache[f"seg{i}"] = c
        return cache

    @staticmethod
    def _map_paged(tree, kv_fn, state_fn, _in_attn=False):
        if isinstance(tree, dict):
            return {
                k: LanguageModel._map_paged(v, kv_fn, state_fn, _in_attn or k == "attn")
                for k, v in tree.items()
            }
        return kv_fn(tree) if _in_attn else state_fn(tree)

    @staticmethod
    def _map2_paged(a, b, kv_fn, state_fn, _in_attn=False):
        if isinstance(a, dict):
            return {
                k: LanguageModel._map2_paged(a[k], b[k], kv_fn, state_fn, _in_attn or k == "attn")
                for k in a
            }
        return kv_fn(a, b) if _in_attn else state_fn(a, b)

    def paged_state_slice(self, cache, width: int):
        """Static-width view: state rows [:width], paged KV untouched."""
        return self._map_paged(cache, lambda l: l, lambda l: l[:, :width])

    def paged_state_merge(self, full, new, width: int, active=None):
        """Write a width-sliced step's updated state rows back into the
        full-width buffer; the paged KV slab is taken from the step. With
        ``active`` (width,) bool, only active rows take the new state —
        masked lanes must NOT advance their recurrence (a slot awaiting its
        next prefill chunk rides the tick as a dead lane; its attention
        writes land at positions the chunk will overwrite, but a recurrent
        state update would be irreversible corruption)."""
        def upd(f, n):
            n = n.astype(f.dtype)
            if active is not None:
                mask = active.reshape((1, active.shape[0]) + (1,) * (n.ndim - 2))
                n = jnp.where(mask, n, f[:, :width])
            return f.at[:, :width].set(n)

        return self._map2_paged(full, new, lambda f, n: n, upd)

    def paged_state_row(self, cache, slot):
        """Batch-1 view for a chunk prefill: state row ``slot`` (traced),
        the full paged KV slab riding along."""
        return self._map_paged(
            cache, lambda l: l,
            lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
        )

    def paged_state_merge_row(self, full, new, slot):
        return self._map2_paged(
            full, new, lambda f, n: n,
            lambda f, n: jax.lax.dynamic_update_slice_in_dim(f, n.astype(f.dtype), slot, axis=1),
        )

    def paged_zero_state_row(self, cache, slot):
        """Clear slot ``slot``'s recurrent state at admission (the row may
        hold a previous occupant's state; attention pages need no clearing —
        the causal mask never reads unwritten positions)."""
        return self._map_paged(
            cache, lambda l: l,
            lambda l: jax.lax.dynamic_update_slice_in_dim(
                l, jnp.zeros((l.shape[0], 1) + l.shape[2:], l.dtype), slot, axis=1
            ),
        )

    def paged_copy_page(self, cache, src, dst):
        """Copy-on-write: duplicate physical page ``src`` into ``dst`` across
        every attention leaf (the divergence page of a partial prefix match)."""
        def cp(l):
            row = jax.lax.dynamic_slice_in_dim(l, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(l, row, dst, axis=1)
        return self._map_paged(cache, cp, lambda l: l)

    def paged_export_slot(self, cache, page_ids, slot):
        """Gather one slot's streamable state (disaggregated serving):
        attention pages ``page_ids`` ((K,) int32, scratch-0 padded past the
        prompt) stacked along the page axis, plus the slot's recurrent state
        row. The result has the cache's tree structure with pool-size-free
        shapes — ``(layers, K, hkv, page_size, hd)`` KV and ``(layers, 1, ...)``
        state — so it can be device_put to another submesh and scattered
        into a pool of any size there."""
        return self._map_paged(
            cache,
            lambda l: jnp.take(l, page_ids, axis=1),
            lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
        )

    def paged_import_slot(self, cache, block, page_ids, slot):
        """Scatter a streamed export into this pool's pages and state row.
        ``page_ids`` lanes mapped to 0 write the scratch page — pad lanes
        and pages already resident locally (adopted via the prefix index)
        land there harmlessly, so the scatter shape never depends on how
        much of the block was deduplicated."""
        return self._map2_paged(
            cache, block,
            lambda f, b: f.at[:, page_ids].set(b.astype(f.dtype)),
            lambda f, b: jax.lax.dynamic_update_slice_in_dim(
                f, b.astype(f.dtype), slot, axis=1
            ),
        )

    def paged_kv_bytes_per_page(self, page_size: int) -> int:
        """Host-side accounting: bytes one page occupies across all
        attention leaves (the unit of the pool's memory high-water mark)."""
        import numpy as np

        cache = jax.eval_shape(lambda: self.init_paged_cache(2, page_size, 1))
        total = 0

        def count(l):
            nonlocal total
            total += int(np.prod(l.shape)) // l.shape[1] * jnp.dtype(l.dtype).itemsize
            return l

        self._map_paged(cache, count, lambda l: l)
        return total

    def prefill(self, params, batch, cache, memory=None):
        """Full-sequence forward filling the cache. Returns (logits, cache).
        ``memory`` may carry a precomputed encoder output (else it is
        encoded from ``batch`` here)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        if memory is None:
            memory = self._encode(params, batch)
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        new_cache = {}
        for i, seg in enumerate(cfg.segments):
            x, c, _ = blocks.apply_segment(
                params[f"seg{i}"], x, cfg, seg, positions=positions,
                cache=cache.get(f"seg{i}"), memory=memory,
            )
            if c is not None:
                new_cache[f"seg{i}"] = c
        x = norm.apply(params["final_norm"], x, cfg.norm_eps)
        logits = embedding.logits(params["embed"], x[:, -1:, :], cfg)
        return logits, new_cache

    def decode_step(self, params, token, cache, cache_index, memory=None, page_table=None):
        """One-token decode. token: (B,1) int32; cache_index: scalar int32, or
        (B,) int32 when every batch row (slot) decodes at its own depth —
        the continuous-batching path. With ``page_table`` (B, max_pages) the
        attention cache is paged (see :meth:`init_paged_cache`).
        Returns (logits (B,1,V), new_cache)."""
        cfg = self.cfg
        x = embedding.embed(params["embed"], token, cfg)
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 0:
            positions = jnp.full((token.shape[0], 1), idx, jnp.int32)
        else:
            positions = idx[:, None]
        cache_index = idx
        new_cache = {}
        for i, seg in enumerate(cfg.segments):
            x, c, _ = blocks.apply_segment(
                params[f"seg{i}"], x, cfg, seg, positions=positions,
                cache=cache.get(f"seg{i}"), cache_index=cache_index, memory=memory,
                page_table=page_table,
            )
            if c is not None:
                new_cache[f"seg{i}"] = c
        x = norm.apply(params["final_norm"], x, cfg.norm_eps)
        return embedding.logits(params["embed"], x, cfg), new_cache

    def prefill_chunk(self, params, tokens, cache, pos_start, slot, page_table, memory=None):
        """One chunk of a paged, chunked prefill: ``tokens`` (1, C) are the
        prompt positions ``[pos_start, pos_start + C)`` of the request in
        state row ``slot``. Attention KV is scattered into the request's
        pages and attends to everything already written (shared prefix pages
        included); recurrent state resumes from — and is written back to —
        row ``slot``. ``pos_start``/``slot`` are traced, so one compiled
        executable serves every prompt length and offset at this chunk size.
        Returns (logits (1,1,V) for the chunk's last token, new full cache)."""
        cfg = self.cfg
        x = embedding.embed(params["embed"], tokens, cfg)
        c_len = tokens.shape[1]
        positions = pos_start + jnp.arange(c_len, dtype=jnp.int32)[None, :]
        row = self.paged_state_row(cache, slot)
        new_row = {}
        for i, seg in enumerate(cfg.segments):
            x, c, _ = blocks.apply_segment(
                params[f"seg{i}"], x, cfg, seg, positions=positions,
                cache=row.get(f"seg{i}"), memory=memory, page_table=page_table,
            )
            if c is not None:
                new_row[f"seg{i}"] = c
        x = norm.apply(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
        logits = embedding.logits(params["embed"], x, cfg)
        return logits, self.paged_state_merge_row(cache, new_row, slot)
