# Pure-jnp oracles for the paged-decode kernel family. Each mirrors the
# exact masking/scaling/softcap semantics of the serving attention path
# (models/layers/attention.py) but materializes the table-gathered KV view —
# the thing the Pallas kernels exist to avoid. The property harness in
# tests/test_paged_decode_kernel.py asserts kernel == ref in interpret mode;
# chip_smoke.py does the same on the chip, both on random_paged_pool's data.
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -2.0e38  # matches attention.py's mask fill


def random_paged_pool(seed, *, slots, ps, mp, hkv, d, share=False, dtype=np.float32):
    """Random page pool (P, hkv, ps, d) + per-slot tables for comparing a
    kernel with its oracle. Returns (k_pages, v_pages, table, positions) with
    positions[b] = the slot's current decode write position (ragged). With
    ``share`` every odd slot aliases slot 0's first page (a published COW
    prefix page)."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + slots * mp
    k_pages = rng.normal(size=(num_pages, hkv, ps, d)).astype(np.float32)
    v_pages = rng.normal(size=(num_pages, hkv, ps, d)).astype(np.float32)
    lengths = rng.integers(1, mp * ps + 1, size=slots)
    table = np.zeros((slots, mp), np.int32)
    nxt = 1
    for b in range(slots):
        n = -(-int(lengths[b]) // ps)
        table[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    if share and slots > 1:
        for b in range(1, slots, 2):
            table[b, 0] = table[0, 0]
    positions = (lengths - 1).astype(np.int32)
    return (
        jnp.asarray(k_pages.astype(dtype)),
        jnp.asarray(v_pages.astype(dtype)),
        jnp.asarray(table),
        jnp.asarray(positions),
    )


def _gather(leaf, page_table):
    """(P, hkv, ps, hd), (B, MP) -> slot-major dense (B, MP*ps, hkv, hd)."""
    b, mp = page_table.shape
    _, hkv, ps, hd = leaf.shape
    out = leaf[page_table.reshape(-1)].swapaxes(1, 2)
    return out.reshape(b, mp * ps, hkv, hd)


def paged_attention_ref(
    q,
    k_pages,
    v_pages,
    page_table,
    positions,
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
):
    """Single-token paged decode attention, gather-then-attend.

    q: (B, Hq, D); k_pages/v_pages: (P, Hkv, ps, D); page_table: (B, MP)
    int32; positions: (B,) int32 — the write position of the current token
    (so KV at logical positions <= positions[b] is attended). Returns
    (B, Hq, D) in q.dtype; math in float32.
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[1]
    kg = _gather(k_pages, page_table).astype(jnp.float32)
    vg = _gather(v_pages, page_table).astype(jnp.float32)
    if hkv != hq:
        kg = jnp.repeat(kg, hq // hkv, axis=2)
        vg = jnp.repeat(vg, hq // hkv, axis=2)
    s = jnp.einsum("bnh,btnh->bnt", q.astype(jnp.float32), kg) * d**-0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = jnp.arange(kg.shape[1])[None, None, :]
    mask = k_pos <= positions[:, None, None]
    if sliding_window is not None:
        mask = mask & (k_pos > positions[:, None, None] - sliding_window)
    s = jnp.where(mask, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnt,btnh->bnh", pr, vg).astype(q.dtype)


def paged_prefill_ref(
    q,
    k_pages,
    v_pages,
    page_table,
    pos_start,
    *,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
):
    """Chunked-prefill paged attention: queries at contiguous positions
    ``[pos_start[b], pos_start[b] + C)`` attend causally over the table view.

    q: (B, C, Hq, D); pos_start: (B,) int32. Returns (B, C, Hq, D).
    """
    b, c, hq, d = q.shape
    hkv = k_pages.shape[1]
    kg = _gather(k_pages, page_table).astype(jnp.float32)
    vg = _gather(v_pages, page_table).astype(jnp.float32)
    if hkv != hq:
        kg = jnp.repeat(kg, hq // hkv, axis=2)
        vg = jnp.repeat(vg, hq // hkv, axis=2)
    s = jnp.einsum("bqnh,btnh->bnqt", q.astype(jnp.float32), kg) * d**-0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = pos_start[:, None] + jnp.arange(c)[None, :]  # (B, C)
    k_pos = jnp.arange(kg.shape[1])  # (T,)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]  # (B, C, T)
    if sliding_window is not None:
        mask = mask & (k_pos[None, None, :] > q_pos[:, :, None] - sliding_window)
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqt,btnh->bqnh", pr, vg).astype(q.dtype)


def fused_sample_ref(logits, noise, temperature, top_k):
    """Oracle for the fused sampler: serve/step.py's sample_tokens with the
    gumbel noise precomputed (the kernel wrapper draws the identical stream
    from the same key). logits: (B, V) f32; noise: (B, V) f32;
    temperature: (B,) f32; top_k: (B,) int32. Returns (B,) int32 tokens."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k[:, None] - 1, 0, v - 1), axis=-1
    )
    masked = jnp.where((top_k[:, None] > 0) & (logits < kth), -jnp.inf, logits)
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jnp.argmax(scaled + noise, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
