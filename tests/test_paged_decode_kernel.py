"""Property-test harness for the paged flash-decode kernel family.

The Pallas kernels (kernels/paged_decode) only ever run in interpret mode in
this container, so correctness is proven, not eyeballed:

- property sweeps (hypothesis via _propcheck, fixed-example fallback without
  it) over page size, slot count, ragged sequence lengths, GQA ratios and
  COW-shared page tables, asserting kernel == ref.py allclose;
- adversarial page-table shapes: KV ending exactly on a page boundary,
  scratch page 0 poisoned-but-masked, a freshly admitted one-token slot,
  and a preempt-style release/re-admit over dirty reused pages;
- the fused sampler is bit-identical to serve/step.py's sample_tokens
  (greedy == argmax including ties; temperature/top-k streams match
  token-for-token from the same key);
- the full PagedContinuousBatchingEngine produces token-identical greedy
  output with kernel="pallas" vs kernel="xla" on qwen (GQA) and gemma
  (sliding window + logit softcap) configs, and the paged decode step's
  logits agree between the kernels within bf16 rounding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.paged_decode import ops as pops
from repro.kernels.paged_decode import ref as pref
from repro.models import build_model
from repro.models.layers.attention import _paged_write
from repro.serve import PagedContinuousBatchingEngine
from repro.serve.pages import PagePool
from repro.serve.step import build_chunk_prefill_step, sample_tokens


def _assert_close(out, expect, dtype):
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        atol=tol, rtol=tol,
    )


# ---------------------------------------------------------------------------
# decode kernel vs ref: property sweeps
# ---------------------------------------------------------------------------

@given(
    ps=st.sampled_from([2, 3, 4, 8]),
    slots=st.integers(min_value=1, max_value=5),
    heads=st.sampled_from([(1, 1), (4, 1), (4, 2), (4, 4), (6, 3)]),
    share=st.sampled_from([False, True]),
    seed=st.integers(min_value=0, max_value=10_000),
)
@settings(max_examples=10, deadline=None)
def test_decode_matches_ref_property(ps, slots, heads, share, seed):
    hq, hkv, d, mp = heads[0], heads[1], 16, 4
    kp, vp, table, pos = pref.random_paged_pool(
        seed, slots=slots, ps=ps, mp=mp, hkv=hkv, d=d, share=share
    )
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.normal(size=(slots, hq, d)).astype(np.float32))
    out = pops.paged_flash_decode(q, kp, vp, table, pos)
    expect = pref.paged_attention_ref(q, kp, vp, table, pos)
    _assert_close(out, expect, np.float32)


@pytest.mark.parametrize("window,softcap", [(None, None), (5, None), (None, 30.0), (7, 30.0)])
def test_decode_window_softcap(window, softcap):
    kp, vp, table, pos = pref.random_paged_pool(3, slots=3, ps=4, mp=4, hkv=2, d=32)
    q = jnp.asarray(np.random.default_rng(4).normal(size=(3, 4, 32)).astype(np.float32))
    out = pops.paged_flash_decode(
        q, kp, vp, table, pos, sliding_window=window, softcap=softcap
    )
    expect = pref.paged_attention_ref(
        q, kp, vp, table, pos, sliding_window=window, softcap=softcap
    )
    _assert_close(out, expect, np.float32)


def test_decode_bf16_pages():
    kp, vp, table, pos = pref.random_paged_pool(
        5, slots=2, ps=4, mp=3, hkv=2, d=16, dtype=np.float32
    )
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    q = jnp.asarray(
        np.random.default_rng(6).normal(size=(2, 4, 16)), jnp.bfloat16
    )
    out = pops.paged_flash_decode(q, kp, vp, table, pos)
    expect = pref.paged_attention_ref(q, kp, vp, table, pos)
    _assert_close(out, expect, np.float16)  # bf16 tolerance band


# ---------------------------------------------------------------------------
# chunk-prefill kernel vs ref
# ---------------------------------------------------------------------------

@given(
    ps=st.sampled_from([2, 4, 8]),
    chunk=st.sampled_from([1, 2, 4, 8]),
    heads=st.sampled_from([(4, 1), (4, 2), (6, 3)]),
    seed=st.integers(min_value=0, max_value=10_000),
)
@settings(max_examples=10, deadline=None)
def test_chunk_prefill_matches_ref_property(ps, chunk, heads, seed):
    hq, hkv, d, mp, slots = heads[0], heads[1], 16, 4, 3
    kp, vp, table, pos = pref.random_paged_pool(seed, slots=slots, ps=ps, mp=mp, hkv=hkv, d=d)
    # the chunk's last token sits at the slot's write position: the queries
    # [pos - chunk + 1, pos] are the chunk being prefilled (KV already
    # scattered, like attention.apply's chunked branch after _paged_write)
    pos_start = jnp.maximum(pos - (chunk - 1), 0)
    rng = np.random.default_rng(seed + 2)
    q = jnp.asarray(rng.normal(size=(slots, chunk, hq, d)).astype(np.float32))
    out = pops.paged_chunk_prefill(q, kp, vp, table, pos_start)
    expect = pref.paged_prefill_ref(q, kp, vp, table, pos_start)
    _assert_close(out, expect, np.float32)


@pytest.mark.parametrize("window,softcap", [(3, None), (None, 20.0)])
def test_chunk_prefill_window_softcap(window, softcap):
    kp, vp, table, pos = pref.random_paged_pool(7, slots=2, ps=4, mp=4, hkv=2, d=16)
    pos_start = jnp.maximum(pos - 3, 0)
    q = jnp.asarray(np.random.default_rng(8).normal(size=(2, 4, 4, 16)).astype(np.float32))
    out = pops.paged_chunk_prefill(
        q, kp, vp, table, pos_start, sliding_window=window, softcap=softcap
    )
    expect = pref.paged_prefill_ref(
        q, kp, vp, table, pos_start, sliding_window=window, softcap=softcap
    )
    _assert_close(out, expect, np.float32)


# ---------------------------------------------------------------------------
# adversarial page-table edge cases
# ---------------------------------------------------------------------------

def test_kv_ends_exactly_on_page_boundary():
    """positions + 1 a multiple of ps: the last valid token is the last row
    of its page; every later logical page is table entry 0 (scratch)."""
    ps, mp, hkv, d = 4, 4, 2, 16
    rng = np.random.default_rng(11)
    kp = jnp.asarray(rng.normal(size=(1 + 2 * mp, hkv, ps, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(1 + 2 * mp, hkv, ps, d)).astype(np.float32))
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 6]], jnp.int32)
    pos = jnp.asarray([2 * ps - 1, 4 * ps - 1], jnp.int32)  # page-boundary ends
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))
    out = pops.paged_flash_decode(q, kp, vp, table, pos)
    expect = pref.paged_attention_ref(q, kp, vp, table, pos)
    _assert_close(out, expect, np.float32)


def test_scratch_page_never_contributes():
    """Poison scratch page 0 with huge values: if any masked-out (scratch)
    position leaked into the softmax it would dominate the output. The
    kernel on the poisoned pool must match the ref on a zeroed-scratch pool."""
    ps, mp, hkv, d = 4, 4, 2, 16
    rng = np.random.default_rng(12)
    kp = rng.normal(size=(1 + 2 * mp, hkv, ps, d)).astype(np.float32)
    vp = rng.normal(size=(1 + 2 * mp, hkv, ps, d)).astype(np.float32)
    clean_k, clean_v = kp.copy(), vp.copy()
    clean_k[0], clean_v[0] = 0.0, 0.0
    kp[0], vp[0] = 1e4, 1e4  # poisoned scratch
    table = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([5, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, d)).astype(np.float32))
    out = pops.paged_flash_decode(q, jnp.asarray(kp), jnp.asarray(vp), table, pos)
    expect = pref.paged_attention_ref(
        q, jnp.asarray(clean_k), jnp.asarray(clean_v), table, pos
    )
    assert bool(jnp.isfinite(out).all())
    _assert_close(out, expect, np.float32)


def test_freshly_admitted_single_token_slot():
    """A slot right after admission: one page, one written token, pos 0."""
    ps, hkv, d = 8, 2, 16
    rng = np.random.default_rng(13)
    kp = jnp.asarray(rng.normal(size=(3, hkv, ps, d)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(3, hkv, ps, d)).astype(np.float32))
    table = jnp.asarray([[1, 0, 0]], jnp.int32)
    pos = jnp.asarray([0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 4, d)).astype(np.float32))
    out = pops.paged_flash_decode(q, kp, vp, table, pos)
    expect = pref.paged_attention_ref(q, kp, vp, table, pos)
    # with a single valid position, attention must return exactly v[pos 0]
    # (repeated over the GQA group), softmax weight 1 on one key
    v0 = np.repeat(np.asarray(vp)[1, :, 0], 2, axis=0)  # (hkv, d) -> (hq, d)
    _assert_close(out, expect, np.float32)
    _assert_close(out[0], v0, np.float32)


def test_preempt_release_readmit_dirty_pages():
    """Preempt-style reuse: request A's pages are released and re-allocated
    to request B; B overwrites only its own positions. Decode for B over the
    dirty pool must match a pool where B's KV was written onto zeroed pages
    (the stale tail beyond B's write position is masked)."""
    ps, mp, hkv, d = 4, 4, 2, 16
    pool = PagePool(1 + mp, ps)
    pages_a = pool.alloc(3)  # A holds 3 pages
    for pid in pages_a:
        pool.release(pid)
    pages_b = pool.alloc(2)  # B re-admits over A's freed pages
    assert set(pages_b) <= set(pages_a)  # genuinely dirty reuse
    pool.check()

    rng = np.random.default_rng(14)
    dirty_k = jnp.asarray(rng.normal(size=(1 + mp, hkv, ps, d)).astype(np.float32))
    dirty_v = jnp.asarray(rng.normal(size=(1 + mp, hkv, ps, d)).astype(np.float32))
    table = np.zeros((1, mp), np.int32)
    table[0, :2] = pages_b
    table = jnp.asarray(table)

    n_b = 6  # B has written positions 0..5 of its 8 addressable
    kv_b = rng.normal(size=(2, 1, n_b, hkv, d)).astype(np.float32)
    positions = jnp.asarray(np.arange(n_b)[None], jnp.int32)
    dirty_k = _paged_write(dirty_k, jnp.asarray(kv_b[0]), table, positions)
    dirty_v = _paged_write(dirty_v, jnp.asarray(kv_b[1]), table, positions)
    clean_k = _paged_write(jnp.zeros_like(dirty_k), jnp.asarray(kv_b[0]), table, positions)
    clean_v = _paged_write(jnp.zeros_like(dirty_v), jnp.asarray(kv_b[1]), table, positions)

    q = jnp.asarray(rng.normal(size=(1, 4, d)).astype(np.float32))
    pos = jnp.asarray([n_b - 1], jnp.int32)
    out = pops.paged_flash_decode(q, dirty_k, dirty_v, table, pos)
    out_clean = pops.paged_flash_decode(q, clean_k, clean_v, table, pos)
    expect = pref.paged_attention_ref(q, clean_k, clean_v, table, pos)
    _assert_close(out, out_clean, np.float32)
    _assert_close(out, expect, np.float32)


def test_cow_shared_prefix_pages_alias():
    """Two slots alias the same physical prefix page (published prefix);
    per-slot outputs must each match the ref over their own table view."""
    kp, vp, table, pos = pref.random_paged_pool(15, slots=4, ps=4, mp=4, hkv=2, d=16, share=True)
    assert int(table[1, 0]) == int(table[0, 0])  # aliased prefix page
    q = jnp.asarray(np.random.default_rng(16).normal(size=(4, 4, 16)).astype(np.float32))
    out = pops.paged_flash_decode(q, kp, vp, table, pos)
    expect = pref.paged_attention_ref(q, kp, vp, table, pos)
    _assert_close(out, expect, np.float32)


# ---------------------------------------------------------------------------
# fused sampler: bit-identical to serve/step.py's sample_tokens
# ---------------------------------------------------------------------------

def test_fused_sample_greedy_equals_argmax():
    rng = np.random.default_rng(20)
    logits = rng.normal(size=(8, 64)).astype(np.float32) * 3
    logits[0] = 0.0                     # full-row tie -> index 0
    logits[1, 7] = logits[1].max() + 1  # unique max
    logits[2, 5] = logits[2, 9] = logits[2].max() + 1  # two-way tie -> 5
    lj = jnp.asarray(logits)
    zeros = jnp.zeros((8,), jnp.float32)
    out = pops.fused_sample(lj, jax.random.key(0), zeros, jnp.zeros((8,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.argmax(logits, axis=-1))


@given(
    v=st.sampled_from([8, 50, 257]),
    seed=st.integers(min_value=0, max_value=100_000),
)
@settings(max_examples=10, deadline=None)
def test_fused_sample_matches_sample_tokens_property(v, seed):
    rng = np.random.default_rng(seed)
    b = 16
    logits = jnp.asarray(rng.normal(size=(b, v)).astype(np.float32) * 4)
    temp = jnp.asarray(rng.choice([0.0, 0.3, 0.7, 1.0, 1.5], b).astype(np.float32))
    top_k = jnp.asarray(rng.choice([0, 1, 2, 5, v, v + 7], b).astype(np.int32))
    key = jax.random.key(seed)
    out = pops.fused_sample(logits, key, temp, top_k)
    expect = sample_tokens(logits, key, temp, top_k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_fused_sample_topk_with_duplicate_kth_value():
    """Duplicates exactly at the k-th largest value: the iterative max-strip
    must agree with sort-descending[k-1] (both keep every duplicate)."""
    logits = jnp.asarray(
        [[1.0, 5.0, 5.0, 5.0, 2.0, 0.0]], jnp.float32
    ).repeat(4, axis=0)
    temp = jnp.full((4,), 0.9, jnp.float32)
    for k in (1, 2, 3, 4):
        top_k = jnp.full((4,), k, jnp.int32)
        for s in range(6):
            key = jax.random.key(s)
            out = pops.fused_sample(logits, key, temp, top_k)
            expect = sample_tokens(logits, key, temp, top_k)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# full-engine identity: kernel="pallas" vs kernel="xla"
# ---------------------------------------------------------------------------

def _engine_tokens(arch, kernel, *, temperature=0.0, top_k=0):
    cfg = get_config(arch, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, 8)
    prompts = [
        np.asarray(
            np.concatenate([sys_prompt, rng.integers(0, cfg.vocab_size, 4 + i)]),
            np.int32,
        )
        for i in range(4)
    ]
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=2, page_size=4,
        prefill_chunks=(4,), kernel=kernel, seed=0,
    )
    assert engine.kernel == kernel
    assert engine.model.cfg.decode_kernel == kernel
    ids = [
        engine.submit(p, max_new_tokens=6, temperature=temperature, top_k=top_k)
        for p in prompts
    ]
    results = engine.run()
    engine.worker.pool.check()
    return [results[r] for r in ids]


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma2-9b"])
def test_engine_greedy_token_identical(arch):
    """Acceptance: greedy decode through the paged engine is token-identical
    between the pallas and xla kernels (gemma covers sliding window +
    softcap; qwen covers GQA + qkv-bias)."""
    xla = _engine_tokens(arch, "xla")
    pallas = _engine_tokens(arch, "pallas")
    for i, (a, b) in enumerate(zip(xla, pallas)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_engine_sampled_token_identical():
    """Fixed engine seed, temperature + top-k: both kernels draw the same key
    at every tick and the fused sampler maps identical logits to identical
    tokens, so the sampled streams can part only where the kernels' logits
    differ. Those differ by bf16 rounding (the smoke config computes in
    bf16): engine runs on this config saw at most 3.5e-2 per logit, enough
    to flip a near-tie between two Gumbel-perturbed scores or at the top-k
    boundary, so sampled engine tokens are not compared. Held here: the
    paged decode step's logits agree between the kernels within 6.25e-2
    (two bf16 ulps at logit magnitude 4-8) on a cache filled by chunked
    prefill, and both samplers draw the same tokens from them."""
    cfg = get_config("qwen2.5-3b", "smoke")
    models = {k: build_model(cfg.replace(decode_kernel=k)) for k in ("xla", "pallas")}
    params, _ = models["xla"].init(jax.random.key(0))
    ps, mp, lengths = 4, 6, [13, 6, 9]
    slots = len(lengths)
    cache = models["xla"].init_paged_cache(1 + slots * mp, ps, slots)
    table = np.arange(1, 1 + slots * mp, dtype=np.int32).reshape(slots, mp)
    rng = np.random.default_rng(0)
    prefill = build_chunk_prefill_step(models["xla"], donate=False)
    for s, n in enumerate(lengths):
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n)), jnp.int32)
        _, cache = prefill(
            params, prompt, cache, jnp.int32(0), jnp.int32(s), jnp.asarray(table[s : s + 1])
        )
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 1)), jnp.int32)
    logits = {}
    for kernel, model in models.items():
        out, _ = jax.jit(model.decode_step)(
            params, tokens, cache, jnp.asarray(lengths, jnp.int32),
            page_table=jnp.asarray(table),
        )
        logits[kernel] = out[:, -1, : cfg.vocab_size].astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(logits["pallas"]), np.asarray(logits["xla"]), atol=6.25e-2, rtol=0
    )
    temp = jnp.full((slots,), 0.8, jnp.float32)
    top_k = jnp.full((slots,), 5, jnp.int32)
    for seed in range(4):
        key = jax.random.key(seed)
        np.testing.assert_array_equal(
            np.asarray(pops.fused_sample(logits["xla"], key, temp, top_k)),
            np.asarray(sample_tokens(logits["xla"], key, temp, top_k)),
        )


def test_engine_kernel_arg_validated():
    cfg = get_config("qwen2.5-3b", "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="kernel"):
        PagedContinuousBatchingEngine(model, params, kernel="cuda")
