"""Kernel-layer benchmark.

Wall-clock on this host measures the *pure-JAX algorithmic* paths (chunked
vs dense attention; chunked-checkpoint GLA vs naive scan) — the Pallas
kernels themselves only run in interpret mode on CPU (Python-step
execution, not meaningful to time), so their entry here is a correctness
sweep pass/fail plus the analytic VMEM footprint of their BlockSpecs.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._schema import Record, print_csv
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gla.ops import gla_chunked
from repro.kernels.gla.ref import gla_ref
from repro.kernels.paged_decode import ops as paged_ops
from repro.kernels.paged_decode import ref as paged_ref
from repro.serve.step import sample_tokens


def _time(fn, *args, n=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run(out_dir: str = "benchmarks/results") -> List[Record]:
    records: List[Record] = []
    # dense vs chunked attention (pure jnp), B=2 S=2048 H=4 D=64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 2048, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2048, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2048, 4, 64), jnp.float32)
    dense = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    t_dense = _time(dense, q, k, v)
    records.append(Record(
        "attention_dense_jnp_s2048", t_dense, "us/call", direction="lower",
        derived="O(S^2) logits materialized",
        context={"batch": 2, "seq": 2048, "heads": 4, "head_dim": 64},
    ))

    # flash kernel correctness sweep (interpret)
    out = flash_attention(q[:, :256], k[:, :256], v[:, :256], causal=True)
    ref = attention_ref(q[:, :256], k[:, :256], v[:, :256], causal=True)
    err = float(jnp.abs(out - ref).max())
    vmem_kb = (128 * 64 * 3 + 128 * 64 + 128 * 2) * 4 / 1024  # q,k,v blocks + acc
    records.append(Record(
        "flash_kernel_interpret_max_err", err, "max_abs_err", direction="lower",
        derived=f"max_err={err:.1e} blockspec_vmem~{vmem_kb:.0f}KiB",
        # fp noise moves tiny errors by large relative factors; gate only
        # on an order-of-magnitude blowup (a real numerics regression)
        context={"blockspec_vmem_kib": vmem_kb, "seq": 256, "tolerance": 9.0},
    ))

    # GLA: naive scan vs chunked-checkpoint jnp vs kernel correctness
    B, S, H, K, V = 2, 1024, 4, 32, 64
    ks = jax.random.split(jax.random.key(1), 4)
    gq = 0.5 * jax.random.normal(ks[0], (B, S, H, K))
    gk = 0.5 * jax.random.normal(ks[1], (B, S, H, K))
    gv = 0.5 * jax.random.normal(ks[2], (B, S, H, V))
    glw = -jnp.abs(jax.random.normal(ks[3], (B, S, H, K)))
    scan_fn = jax.jit(lambda *a: gla_ref(*a)[0])
    t_scan = _time(scan_fn, gq, gk, gv, glw)
    records.append(Record(
        "gla_seq_scan_jnp_s1024", t_scan, "us/call", direction="lower",
        derived="per-step recurrence (production lowering path)",
        context={"batch": B, "seq": S, "heads": H, "key_dim": K, "value_dim": V},
    ))
    yk, fk = gla_chunked(gq, gk, gv, glw, chunk=128)
    yr, fr = gla_ref(gq, gk, gv, glw)
    err = float(jnp.abs(yk - yr).max())
    records.append(Record(
        "gla_kernel_interpret_max_err", err, "max_abs_err", direction="lower",
        derived=f"max_err={err:.1e} chunk=128",
        context={"chunk": 128, "tolerance": 9.0},
    ))

    # paged flash decode: time the XLA gather-then-attend serving path
    # (the baseline the Pallas kernel replaces on TPU), then the kernel's
    # interpret-mode correctness vs the same oracle
    B, MP, PS, HQ, HKV, D = 8, 16, 16, 4, 2, 64  # 256 tokens/slot
    rng = np.random.default_rng(2)
    num_pages = 1 + B * MP
    kp = jnp.asarray(rng.normal(size=(num_pages, HKV, PS, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(num_pages, HKV, PS, D)), jnp.float32)
    table = jnp.asarray(
        1 + rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    )
    pos = jnp.asarray(rng.integers(0, MP * PS, B), jnp.int32)
    pq = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.float32)
    gather_fn = jax.jit(paged_ref.paged_attention_ref)
    t_gather = _time(gather_fn, pq, kp, vp, table, pos)
    records.append(Record(
        "paged_decode_gather_jnp_b8", t_gather, "us/call", direction="lower",
        derived="XLA gather + sdpa (serve decode tick, paged engine)",
        context={"slots": B, "pages_per_slot": MP, "page_size": PS,
                 "q_heads": HQ, "kv_heads": HKV, "head_dim": D},
    ))
    out = paged_ops.paged_flash_decode(pq, kp, vp, table, pos)
    ref_out = paged_ref.paged_attention_ref(pq, kp, vp, table, pos)
    err = float(jnp.abs(out - ref_out).max())
    # VMEM per grid step: q/o (G, D) + one (PS, D) tile each of K and V
    # (pages are (P, HKV, PS, D)) + f32 accumulators
    vmem_kb = ((HQ // HKV) * D * 2 + PS * D * 2 + (HQ // HKV) * (D + 2)) * 4 / 1024
    records.append(Record(
        "paged_decode_kernel_interpret_max_err", err, "max_abs_err",
        direction="lower",
        derived=f"max_err={err:.1e} blockspec_vmem~{vmem_kb:.0f}KiB",
        context={"blockspec_vmem_kib": vmem_kb, "page_size": PS,
                 "tolerance": 9.0},
    ))

    # fused sampler: must be BIT-identical to serve/step.py's sample_tokens
    # (zero tolerance — any mismatch silently changes served streams)
    logits = jnp.asarray(rng.normal(size=(64, 512)) * 4, jnp.float32)
    temp = jnp.asarray(rng.choice([0.0, 0.3, 0.7, 1.0, 1.5], 64), jnp.float32)
    top_k = jnp.asarray(rng.choice([0, 1, 5, 50, 512], 64), jnp.int32)
    key = jax.random.key(3)
    mismatches = int(
        (paged_ops.fused_sample(logits, key, temp, top_k)
         != sample_tokens(logits, key, temp, top_k)).sum()
    )
    records.append(Record(
        "fused_sample_token_mismatches", mismatches, "tokens",
        direction="exact",
        derived="fused logits->token kernel vs step.sample_tokens, 64 rows",
        context={"rows": 64, "vocab": 512},
    ))
    return records


if __name__ == "__main__":
    print_csv(run())
