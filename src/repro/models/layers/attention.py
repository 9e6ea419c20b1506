"""Grouped-query attention with the features the assigned archs need:

- GQA (num_kv_heads <= num_heads), optional QKV bias (qwen2.5),
- rotary embeddings, or none (``cfg.rope`` False: NoPE),
- the config's softmax scale (``cfg.attn_scale``; default 1/sqrt(head_dim)),
- causal / sliding-window (gemma2 local, long-context dense variant) masks,
- attention logit soft-capping (gemma2),
- cross-attention (whisper decoder),
- three execution modes: full-sequence (train / prefill, optionally via the
  Pallas flash kernel), and single-token decode against a KV cache whose
  length dimension is sharded over the ``data`` mesh axis for long-context.

The paged decode/chunked-prefill branches dispatch on ``cfg.decode_kernel``:
``"xla"`` gathers a contiguous KV view through the page table and reuses
``_sdpa``; ``"pallas"`` calls kernels/paged_decode, which fuses the table
gather into the flash inner loop (no materialized view).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import rope
from repro.sharding import constrain
from repro.utils.prng import fold_in_name

NEG_INF = -2.0e38


def init(key, cfg, name: str = "attn", cross: bool = False):
    d = cfg.d_model
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.param_dtype)
    k = fold_in_name(key, name)
    ks = jax.random.split(k, 4)
    scale_in = d**-0.5
    params = {
        "wq": jax.random.normal(ks[0], (d, hq, hd), dtype) * scale_in,
        "wk": jax.random.normal(ks[1], (d, hkv, hd), dtype) * scale_in,
        "wv": jax.random.normal(ks[2], (d, hkv, hd), dtype) * scale_in,
        "wo": jax.random.normal(ks[3], (hq, hd, d), dtype) * ((hq * hd) ** -0.5),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias and not cross:
        params["bq"] = jnp.zeros((hq, hd), dtype)
        params["bk"] = jnp.zeros((hkv, hd), dtype)
        params["bv"] = jnp.zeros((hkv, hd), dtype)
        axes["bq"] = ("heads", "head_dim")
        axes["bk"] = ("kv_heads", "head_dim")
        axes["bv"] = ("kv_heads", "head_dim")
    return params, axes


def init_cache(cfg, batch: int, cache_len: int, dtype):
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, hkv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, hkv, hd), dtype),
    }


CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
    "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
}


def init_paged_cache(cfg, num_pages: int, page_size: int, dtype):
    """Paged KV store: ``(num_pages, hkv, page_size, hd)`` per leaf, so one
    page of one kv head is a contiguous (page_size, hd) tile — the block the
    Pallas kernels stream. Page ids are global across layers (one logical
    page = a slab through every attention leaf); slots map logical→physical
    pages via a page table."""
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((num_pages, hkv, page_size, hd), dtype),
        "v": jnp.zeros((num_pages, hkv, page_size, hd), dtype),
    }


PAGED_CACHE_AXES = {
    "k": (None, "kv_heads", None, "head_dim"),
    "v": (None, "kv_heads", None, "head_dim"),
}


def _paged_write(leaf, val, page_table, positions):
    """Scatter ``val`` (B, S, hkv, hd) into the paged ``leaf``
    (P, hkv, ps, hd) at logical ``positions`` (B, S) through ``page_table``
    (B, max_pages). Rows whose table entry is 0 land in the scratch page."""
    ps = leaf.shape[2]
    rows = jnp.arange(page_table.shape[0])[:, None]
    phys = page_table[rows, positions // ps].reshape(-1)
    off = (positions % ps).reshape(-1)
    flat = val.reshape((-1,) + val.shape[2:]).astype(leaf.dtype)
    # advanced indices split by a slice: the (N,) index dim leads, so the
    # update is (N, hkv, hd) — exactly ``flat``
    return leaf.at[phys, :, off].set(flat, mode="drop")


def _paged_gather(leaf, page_table):
    """Gather a slot-major dense view (B, max_pages * ps, hkv, hd) of the
    paged ``leaf`` in logical-position order."""
    b, mp = page_table.shape
    _, hkv, ps, hd = leaf.shape
    out = leaf[page_table.reshape(-1)].swapaxes(1, 2)  # (B*mp, ps, hkv, hd)
    return out.reshape(b, mp * ps, hkv, hd)


def _project_qkv(params, x, memory, cfg):
    dtype = x.dtype
    wq = params["wq"].astype(dtype)
    wk = params["wk"].astype(dtype)
    wv = params["wv"].astype(dtype)
    kv_in = x if memory is None else memory
    q = jnp.einsum("bsd,dnh->bsnh", x, wq)
    k = jnp.einsum("btd,dnh->btnh", kv_in, wk)
    v = jnp.einsum("btd,dnh->btnh", kv_in, wv)
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return q, k, v


def _mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """Boolean mask (.., q, k): True = attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        m = m & (k_pos[..., None, :] <= q_pos[..., :, None])
    if window is not None:
        m = m & (k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def _sdpa(q, k, v, mask, cfg):
    """Reference scaled-dot-product GQA attention (einsum path).

    KV heads are repeated up to the full head count so every tensor keeps a
    single flat ``heads`` dim — scores then share q's heads→model sharding
    with no SPMD resharding (the factored (kv, group) form triggered XLA's
    "involuntary full rematerialization" replication). Where heads don't
    divide the model axis (arctic 56, whisper 6) the scores fall back to
    query-seq sharding via the rule ladder.
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    logits = jnp.einsum("bsnh,btnh->bnst", q, k).astype(jnp.float32)
    score_axes = ("batch", "heads", "seq_sp", None)
    logits = constrain(logits, score_axes)
    logits *= hd**-0.5
    cap = cfg.attn_logit_softcap
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    probs = constrain(probs, score_axes)
    out = jnp.einsum("bnst,btnh->bsnh", probs, v)
    return out


def _sdpa_chunked(q, k, v, cfg, *, chunk: int, causal: bool, window: Optional[int]):
    """Flash-style query chunking: scan over query blocks, full K/V resident.

    Memory per block: (B, heads, chunk, S) logits instead of (B, heads, S, S)
    — the pure-JAX stand-in for the Pallas flash kernel's VMEM tiling (the
    kernel is used on real TPU; this path keeps CPU/compile memory honest).
    Each block is rematerialised in the backward, as a flash backward
    recomputes its scores, so the gradient keeps one block's scores and not
    every block's.
    """
    b, s, hq, hd = q.shape
    nc = s // chunk
    assert nc * chunk == s, f"seq {s} % chunk {chunk} != 0"
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, hq, hd), 1, 0)  # (nc,B,chunk,hq,hd)
    k_pos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def body(_, args):
        i, qblk = args
        q_pos = i * chunk + jnp.arange(chunk)[None, :]
        mask = _mask(
            jnp.broadcast_to(q_pos, (b, chunk)),
            jnp.broadcast_to(k_pos, (b, s)),
            causal,
            window,
        )
        return None, _sdpa(qblk, k, v, mask, cfg)

    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qc))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq, hd)


def apply(
    params,
    x,
    cfg,
    *,
    positions,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    cache=None,
    cache_index=None,
    memory=None,
    page_table=None,
):
    """Returns (out, new_cache).

    train/prefill: ``cache`` is None (train) or a zero cache to fill
    (prefill). decode: ``x`` is (B, 1, d) and ``cache_index`` a scalar.
    ``memory`` (B, T, d) switches to cross-attention (no cache, no causal).

    ``page_table`` (B, max_pages) int32 switches the cache to the paged
    layout (leaves (num_pages, hkv, page_size, hd)): decode scatters the new
    KV at ``page_table[b, pos // ps]`` and attends over the table-gathered
    view; with s > 1 it is a *chunked prefill* continuation — the chunk's KV
    is written at its absolute ``positions`` and queries attend to every
    previously-written position (shared prefix pages included) plus the
    chunk itself, under the usual causal/window mask.
    """
    b, s, d = x.shape
    decode = cache is not None and s == 1 and cache_index is not None
    chunked = cache is not None and s > 1 and page_table is not None and memory is None
    q, k, v = _project_qkv(params, x, memory, cfg)
    q = constrain(q, ("batch", "seq", "heads", None))
    if cfg.attn_scale is not None:
        # every path (einsum, flash, paged) scales q.k by head_dim**-0.5:
        # folding the rest into q gives the config's scale on all of them
        q = q * jnp.asarray(cfg.attn_scale * cfg.resolved_head_dim**0.5, q.dtype)

    if memory is None and cfg.rope:
        q = rope.apply_rope(q, positions, cfg.rope_theta)
        if decode or chunked:
            k = rope.apply_rope(k, positions, cfg.rope_theta)
        else:
            k = rope.apply_rope(k, jnp.arange(k.shape[1])[None, :], cfg.rope_theta)

    new_cache = cache
    if chunked:
        k_cache = constrain(_paged_write(cache["k"], k, page_table, positions), PAGED_CACHE_AXES["k"])
        v_cache = constrain(_paged_write(cache["v"], v, page_table, positions), PAGED_CACHE_AXES["v"])
        new_cache = {"k": k_cache, "v": v_cache}
        if cfg.decode_kernel == "pallas" and causal:
            from repro.kernels.paged_decode import ops as paged_ops

            # chunk positions are contiguous (lm.prefill_chunk builds them as
            # pos_start + arange), so the kernel only needs each row's start
            pos_start = jnp.broadcast_to(positions, (b, s))[:, 0]
            out = paged_ops.paged_chunk_prefill(
                q, k_cache, v_cache, page_table, pos_start,
                sliding_window=sliding_window, softcap=cfg.attn_logit_softcap,
            )
        else:
            kg = _paged_gather(k_cache, page_table)
            vg = _paged_gather(v_cache, page_table)
            k_pos = jnp.arange(kg.shape[1])[None, :]
            mask = _mask(
                jnp.broadcast_to(positions, (b, s)),
                jnp.broadcast_to(k_pos, (b, kg.shape[1])),
                causal,
                sliding_window,
            )
            out = _sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype), mask, cfg)
    elif decode and page_table is not None:
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim == 0:
            idx = jnp.full((b,), idx, jnp.int32)
        k_cache = constrain(_paged_write(cache["k"], k, page_table, idx[:, None]), PAGED_CACHE_AXES["k"])
        v_cache = constrain(_paged_write(cache["v"], v, page_table, idx[:, None]), PAGED_CACHE_AXES["v"])
        new_cache = {"k": k_cache, "v": v_cache}
        if cfg.decode_kernel == "pallas":
            from repro.kernels.paged_decode import ops as paged_ops

            out = paged_ops.paged_flash_decode(
                q[:, 0], k_cache, v_cache, page_table, idx,
                sliding_window=sliding_window, softcap=cfg.attn_logit_softcap,
            )[:, None]
        else:
            kg = _paged_gather(k_cache, page_table)
            vg = _paged_gather(v_cache, page_table)
            k_pos = jnp.arange(kg.shape[1])[None, :]
            write_pos = idx[:, None]
            valid = k_pos <= write_pos
            if sliding_window is not None:
                valid = valid & (k_pos > write_pos - sliding_window)
            mask = jnp.broadcast_to(valid[:, None, :], (b, 1, kg.shape[1]))
            out = _sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype), mask, cfg)
    elif decode:
        # write new kv at cache_index; attend to the full (seq-sharded) cache.
        # cache_index may be a scalar (static batch: all rows at one depth) or
        # a (B,) vector (slot ring: each request at its own decode depth).
        idx = jnp.asarray(cache_index, jnp.int32)
        k_pos = jnp.arange(cache["k"].shape[1])[None, :]
        if idx.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
            write_pos = idx
        else:
            rows = jnp.arange(idx.shape[0])
            k_cache = cache["k"].at[rows, idx].set(k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[rows, idx].set(v[:, 0].astype(cache["v"].dtype))
            write_pos = idx[:, None]
        k_cache = constrain(k_cache, CACHE_AXES["k"])
        v_cache = constrain(v_cache, CACHE_AXES["v"])
        new_cache = {"k": k_cache, "v": v_cache}
        valid = k_pos <= write_pos
        if sliding_window is not None:
            valid = valid & (k_pos > write_pos - sliding_window)
        mask = valid[:, None, :]  # (1 or B, q=1, K)
        mask = jnp.broadcast_to(mask, (b, 1, k_cache.shape[1]))
        out = _sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), mask, cfg)
    else:
        k = constrain(k, ("batch", "seq", "kv_heads", None))
        v = constrain(v, ("batch", "seq", "kv_heads", None))
        if cache is not None:  # prefill: write the whole kv into the cache
            kc = jnp.zeros_like(cache["k"])
            vc = jnp.zeros_like(cache["v"])
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}
        if memory is not None:
            mask = jnp.ones((b, s, k.shape[1]), bool)
        else:
            k_pos = jnp.arange(k.shape[1])[None, :]
            mask = _mask(jnp.broadcast_to(positions, (b, s)), jnp.broadcast_to(k_pos, (b, k.shape[1])), causal, sliding_window)
        if cfg.use_flash_kernel and memory is None and cfg.attn_logit_softcap is None:
            from repro.kernels.flash_attention import ops as flash_ops

            out = flash_ops.flash_attention(
                q, k, v, causal=causal, sliding_window=sliding_window
            )
        elif (
            memory is None
            and cfg.attn_chunk is not None
            and s > cfg.attn_chunk
            and s % cfg.attn_chunk == 0
        ):
            out = _sdpa_chunked(
                q, k, v, cfg, chunk=cfg.attn_chunk, causal=causal, window=sliding_window
            )
        else:
            out = _sdpa(q, k, v, mask, cfg)

    out = constrain(out, ("batch", "seq", "heads", None))
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(out.dtype))
    out_axes = (
        ("batch", "seq_sp", "embed")
        if getattr(cfg, "tp_reduce_scatter", False)
        else ("batch", "seq", "embed")
    )
    return constrain(y, out_axes), new_cache
