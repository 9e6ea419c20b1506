"""Public jit'd wrappers for the paged-decode kernel family: flat-head
layouts in, GQA grouping + int32 table/position casts handled here, TPU
kernel or interpret fallback on CPU.

Each public name is built by a ``build_*`` builder containing the module's
only ``jax.jit`` boundary — the shape the compile-bucket registry
(analysis/contracts.py, ``kernels.paged.*``) declares and R301/R302 audit.

``fused_sample`` draws its gumbel noise from the caller's key exactly like
serve/step.py's ``sample_tokens`` does, so a fixed seed yields the identical
sampled stream on either path (tested token-for-token).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode.kernel import (
    fused_sample_rows,
    paged_chunk_prefill_grouped,
    paged_flash_decode_grouped,
)


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def build_paged_flash_decode():
    def decode(
        q: jnp.ndarray,           # (B, Hq, D) — one query token per slot
        k_pages: jnp.ndarray,     # (P, Hkv, ps, D)
        v_pages: jnp.ndarray,
        page_table: jnp.ndarray,  # (B, max_pages)
        positions: jnp.ndarray,   # (B,) — per-slot decode write position
        *,
        sliding_window: Optional[int] = None,
        softcap: Optional[float] = None,
        interpret: Optional[bool] = None,
    ) -> jnp.ndarray:
        b, hq, d = q.shape
        hkv = k_pages.shape[1]
        assert hq % hkv == 0, f"q heads {hq} % kv heads {hkv} != 0"
        interp = (not _is_tpu()) if interpret is None else interpret
        out = paged_flash_decode_grouped(
            q.reshape(b, hkv, hq // hkv, d),
            k_pages,
            v_pages,
            page_table.astype(jnp.int32),
            positions.astype(jnp.int32),
            window=sliding_window,
            softcap=softcap,
            interpret=interp,
        )
        return out.reshape(b, hq, d)

    return jax.jit(
        decode, static_argnames=("sliding_window", "softcap", "interpret")
    )


def build_paged_chunk_prefill():
    def prefill(
        q: jnp.ndarray,           # (B, C, Hq, D) — contiguous chunk of queries
        k_pages: jnp.ndarray,     # (P, Hkv, ps, D)
        v_pages: jnp.ndarray,
        page_table: jnp.ndarray,  # (B, max_pages)
        pos_start: jnp.ndarray,   # (B,) — position of each chunk's first query
        *,
        sliding_window: Optional[int] = None,
        softcap: Optional[float] = None,
        interpret: Optional[bool] = None,
    ) -> jnp.ndarray:
        b, c, hq, d = q.shape
        hkv = k_pages.shape[1]
        assert hq % hkv == 0, f"q heads {hq} % kv heads {hkv} != 0"
        interp = (not _is_tpu()) if interpret is None else interpret
        qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, hq // hkv, c, d)
        out = paged_chunk_prefill_grouped(
            qg,
            k_pages,
            v_pages,
            page_table.astype(jnp.int32),
            pos_start.astype(jnp.int32),
            window=sliding_window,
            softcap=softcap,
            interpret=interp,
        )
        return out.reshape(b, hq, c, d).transpose(0, 2, 1, 3)

    return jax.jit(
        prefill, static_argnames=("sliding_window", "softcap", "interpret")
    )


def build_fused_sample():
    def sample(
        logits: jnp.ndarray,       # (B, V)
        key: jnp.ndarray,
        temperature: jnp.ndarray,  # (B,)
        top_k: jnp.ndarray,        # (B,)
        *,
        interpret: Optional[bool] = None,
    ) -> jnp.ndarray:
        b, v = logits.shape
        interp = (not _is_tpu()) if interpret is None else interpret
        # identical stream to sample_tokens' draw
        noise = jax.random.gumbel(key, (b, v), jnp.float32)
        return fused_sample_rows(
            logits.astype(jnp.float32),
            noise,
            temperature.astype(jnp.float32),
            top_k.astype(jnp.int32),
            interpret=interp,
        )

    return jax.jit(sample, static_argnames=("interpret",))


paged_flash_decode = build_paged_flash_decode()
paged_chunk_prefill = build_paged_chunk_prefill()
fused_sample = build_fused_sample()
