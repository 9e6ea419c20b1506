"""Paged flash-decode kernels for TPU (Pallas): the serving hot path.

Layout (shared with serve/pages.py and attention.init_paged_cache):

    k_pages, v_pages : (num_pages, Hkv, page_size, D)   page 0 = scratch
    page_table       : (B, max_pages) int32             logical -> physical
    positions        : (B,) int32                       per-slot decode depth

The page-table gather is fused into the online-softmax inner loop via
``pltpu.PrefetchScalarGridSpec``: the table and positions are scalar-prefetch
operands, and the K/V BlockSpec index maps read ``pt[b, j]`` to stream
logical page ``j`` of slot ``b`` straight from its physical page — no
materialized contiguous KV view (the XLA path's ``_paged_gather``). Each
K/V block is one (page_size, D) tile of one page and one kv head: the last
two block dims span whole array dims, which is what the TPU compiler
requires of a block whose sublane extent is not a multiple of 8.

Grid: (B, Hkv, max_pages) — the innermost page axis accumulates into VMEM
scratch (o_acc f32, running max m, running sum l) with @pl.when init at the
first page and normalization at the last. GQA is blocked as (group, D)
query tiles per kv head; pages past a slot's decode depth still iterate
(TPU grids are static) but are fully masked, so their contribution is the
identity of the online-softmax update — scratch page 0 (table entry 0 for
unallocated logical pages) is streamed but never unmasked.

The fused sampler runs one grid step per batch row and reproduces
serve/step.py's ``sample_tokens`` bit-for-bit: first-occurrence argmax for
greedy, k-th-largest extraction by repeated max-removal for top-k, gumbel
noise added by the ops wrapper from the identical PRNG stream. A row's
logits arrive as a dense (V/128, 128) tile (the wrapper pads V to a lane
multiple with -inf), per-row temperature and top-k are scalar-prefetch
operands in SMEM, and the token leaves as a lane-wide (1, 128) row.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _decode_kernel(
    pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc,
    *, ps: int, group: int, scale: float,
    window: Optional[int], softcap: Optional[float],
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (ps, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, ps)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    pos = pos_ref[b]
    k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (group, ps), 1)
    mask = k_pos <= pos
    if window is not None:
        mask &= k_pos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_acc[...]                                  # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (G, ps)
    alpha = jnp.exp(m_prev - m_new)
    m_acc[...] = m_new
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_acc[...] = o_acc[...] * alpha + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ()))
    )

    @pl.when(j == nj - 1)
    def _finalize():
        o_ref[0, 0] = (o_acc[...] / jnp.maximum(l_acc[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode_grouped(
    q: jnp.ndarray,           # (B, Hkv, G, D) — grouped query, one token/slot
    k_pages: jnp.ndarray,     # (P, Hkv, ps, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP) int32
    positions: jnp.ndarray,   # (B,) int32
    *,
    window: Optional[int],
    softcap: Optional[float],
    interpret: bool = False,
) -> jnp.ndarray:
    b, hkv, g, d = q.shape
    ps = k_pages.shape[2]
    mp = page_table.shape[1]
    grid = (b, hkv, mp)
    kernel = functools.partial(
        _decode_kernel, ps=ps, group=g, scale=d**-0.5, window=window, softcap=softcap
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, positions
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda bi, h, j, pt, pos: (bi, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, d), lambda bi, h, j, pt, pos: (pt[bi, j], h, 0, 0)),
                pl.BlockSpec((1, 1, ps, d), lambda bi, h, j, pt, pos: (pt[bi, j], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, h, j, pt, pos: (bi, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),  # o accumulator
                pltpu.VMEM((g, 1), jnp.float32),  # running max
                pltpu.VMEM((g, 1), jnp.float32),  # running sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(page_table, positions, q, k_pages, v_pages)


def _prefill_kernel(
    pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc,
    *, ps: int, group: int, chunk: int, scale: float,
    window: Optional[int], softcap: Optional[float],
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    rows = group * chunk  # row r = query (head g=r//chunk, chunk offset r%chunk)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = q_ref[0, 0].astype(jnp.float32).reshape(rows, -1) * scale  # (G*C, D)
    k = k_ref[0, 0].astype(jnp.float32)                            # (ps, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))        # (G*C, ps)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = pos_ref[b] + (
        jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0) % chunk
    )
    k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
    mask = k_pos <= q_pos  # causal: also masks pages beyond the chunk's writes
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_acc[...]                                            # (G*C, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    m_acc[...] = m_new
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    o_acc[...] = o_acc[...] * alpha + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ()))
    )

    @pl.when(j == nj - 1)
    def _finalize():
        out = o_acc[...] / jnp.maximum(l_acc[...], 1e-30)
        o_ref[0, 0] = out.reshape(group, chunk, -1).astype(o_ref.dtype)


def paged_chunk_prefill_grouped(
    q: jnp.ndarray,           # (B, Hkv, G, C, D) — contiguous chunk of queries
    k_pages: jnp.ndarray,     # (P, Hkv, ps, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (B, MP) int32
    pos_start: jnp.ndarray,   # (B,) int32 — position of the chunk's first query
    *,
    window: Optional[int],
    softcap: Optional[float],
    interpret: bool = False,
) -> jnp.ndarray:
    b, hkv, g, c, d = q.shape
    ps = k_pages.shape[2]
    mp = page_table.shape[1]
    grid = (b, hkv, mp)
    kernel = functools.partial(
        _prefill_kernel, ps=ps, group=g, chunk=c, scale=d**-0.5,
        window=window, softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table, pos_start
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, c, d), lambda bi, h, j, pt, pos: (bi, h, 0, 0, 0)),
                pl.BlockSpec((1, 1, ps, d), lambda bi, h, j, pt, pos: (pt[bi, j], h, 0, 0)),
                pl.BlockSpec((1, 1, ps, d), lambda bi, h, j, pt, pos: (pt[bi, j], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, c, d), lambda bi, h, j, pt, pos: (bi, h, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g * c, d), jnp.float32),  # o accumulator
                pltpu.VMEM((g * c, 1), jnp.float32),  # running max
                pltpu.VMEM((g * c, 1), jnp.float32),  # running sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, c, d), q.dtype),
        interpret=interpret,
    )(page_table, pos_start, q, k_pages, v_pages)


def _sample_kernel(t_ref, k_ref, x_ref, n_ref, o_ref, *, vocab: int):
    b = pl.program_id(0)
    x = x_ref[0]                                          # (V/128, 128) f32
    idx = (
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * x.shape[1]
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    )
    size = x.shape[0] * x.shape[1]  # padded vocab: a sentinel past every index

    def first_argmax(vals):  # argmax, ties -> lowest index (= jnp.argmax)
        return jnp.min(jnp.where(vals == jnp.max(vals), idx, size))

    greedy = first_argmax(x)
    top_k = k_ref[b]
    # k-th largest (duplicates counted, like sort-descending[k-1]): strip the
    # first occurrence of the max, top_k - 1 times, then take the max.
    def strip_max(_, vals):
        return jnp.where(idx == first_argmax(vals), -jnp.inf, vals)

    rest = jax.lax.fori_loop(0, jnp.clip(top_k - 1, 0, vocab - 1), strip_max, x)
    kth = jnp.max(rest)
    masked = jnp.where((top_k > 0) & (x < kth), -jnp.inf, x)
    t = t_ref[b]
    scaled = masked / jnp.maximum(t, 1e-6)
    sampled = first_argmax(scaled + n_ref[0])
    token = jnp.where(t > 0, sampled, greedy).astype(jnp.int32)
    o_ref[...] = jnp.full(o_ref.shape, token, jnp.int32)


def fused_sample_rows(
    logits: jnp.ndarray,       # (B, V) f32
    noise: jnp.ndarray,        # (B, V) f32 gumbel
    temperature: jnp.ndarray,  # (B,) f32
    top_k: jnp.ndarray,        # (B,) int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    b, v = logits.shape
    pad = -v % 128
    if pad:  # padded lanes can never win an argmax or count toward top-k
        logits = jnp.pad(logits, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        noise = jnp.pad(noise, ((0, 0), (0, pad)))
    rows = (v + pad) // 128
    kernel = functools.partial(_sample_kernel, vocab=v)
    row_spec = pl.BlockSpec((1, rows, 128), lambda i, t, k: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # temperature, top_k
            grid=(b,),
            in_specs=[row_spec, row_spec],
            out_specs=pl.BlockSpec((1, 1, 128), lambda i, t, k: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, 128), jnp.int32),
        interpret=interpret,
    )(temperature, top_k, logits.reshape(b, rows, 128), noise.reshape(b, rows, 128))
    return out[:, 0, 0]
