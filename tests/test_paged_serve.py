"""Paged continuous-batching engine: token-identity vs the static
``ServeEngine`` across architecture families (with and without prefix
sharing), compile-count bounds under randomized prompt lengths, page-pool
pressure behaviour, and memory accounting. Tests parametrized over
``engine_cls`` also run the disaggregated layout of the same engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import DisaggregatedEngine, PagedContinuousBatchingEngine, ServeEngine

# the single-mesh engine and its disaggregated layout (one device here)
ENGINES = pytest.mark.parametrize(
    "engine_cls", [PagedContinuousBatchingEngine, DisaggregatedEngine],
    ids=["paged", "disagg"],
)

# fast subset runs two families (dense attn + rwkv); the rest ride -m slow
ARCHS = [
    "qwen2.5-3b",
    "rwkv6-1.6b",
    pytest.param("zamba2-2.7b", marks=pytest.mark.slow),
    pytest.param("gemma2-9b", marks=pytest.mark.slow),
]


def _setup(arch, key=0):
    cfg = get_config(arch, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(key))
    return cfg, model, params


def _shared_prefix_prompts(cfg, n=6, prefix_len=9, suffix_len=3, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len)
    out = [
        np.asarray(
            np.concatenate([prefix, rng.integers(0, cfg.vocab_size, suffix_len)]),
            np.int32,
        )
        for _ in range(n)
    ]
    out.append(np.asarray(prefix, np.int32))  # fully-cached prompt (COW cap)
    return out


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_paged_matches_static_greedy(arch, prefix_cache):
    """Paged greedy output is token-identical to the static ServeEngine on
    every family: page-table gather/scatter reads, chunked prefill, the
    teacher-forced prompt tail, and prefix-shared pages must not perturb a
    single argmax. The shared-prefix workload makes sharing actually fire
    where supported (attention-only models)."""
    cfg, model, params = _setup(arch)
    prompts = _shared_prefix_prompts(cfg, n=3)
    static = ServeEngine(model, params, cache_len=64)
    ref = [static.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=2, page_size=4,
        prefill_chunks=(4,), prefix_cache=prefix_cache,
    )
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    out = engine.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(out[rid], ref[i], err_msg=f"request {i}")
    if prefix_cache and engine.prefix_sharing:
        assert engine.stats["prefix_tokens_reused"] > 0
        assert engine.stats["cow_copies"] > 0  # the fully-cached prompt
    else:
        assert engine.stats["prefix_tokens_reused"] == 0
    # reused prefill work really was skipped, not recomputed
    total_prompt = sum(len(p) for p in prompts)
    assert (
        engine.stats["prefill_tokens_computed"]
        == total_prompt - engine.stats["prefix_tokens_reused"]
    )


@pytest.mark.slow
def test_paged_whisper_enc_dec():
    """Encoder-decoder path: per-request audio memory through chunked
    prefill + paged decode; prefix sharing must auto-disable (decoder KV
    depends on the audio, not on token content alone)."""
    cfg, model, params = _setup("whisper-tiny")
    prompts = np.zeros((2, 6), np.int32)
    audio = 0.1 * np.asarray(
        jax.random.normal(jax.random.key(2), (2, cfg.encoder_seq, cfg.d_model))
    )
    mem = jnp.asarray(audio, jnp.bfloat16)
    ref = ServeEngine(model, params, cache_len=32).generate(
        prompts, max_new_tokens=4, memory=mem
    )
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=32, max_slots=2, page_size=4, prefill_chunks=(4,)
    )
    assert not engine.prefix_sharing
    ids = [
        engine.submit(prompts[i], max_new_tokens=4, memory=mem[i : i + 1])
        for i in range(2)
    ]
    out = engine.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(out[rid], ref[i])


def test_compile_counts_bounded_under_random_prompt_lengths():
    """Regression: full-prompt prefill compiles one prefill executable per
    distinct prompt length; the paged engine must stay bounded by the
    chunk-size bucket count (sub-chunk tails ride already-compiled decode
    ticks), and decode compiles stay one per admission stage."""
    cfg, model, params = _setup("qwen2.5-3b")
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=4, b1=1, rho=2.0, patience=2,
        page_size=4, prefill_chunks=(4, 8),
    )
    assert engine.admission.ladder == [1, 2, 4]
    rng = np.random.default_rng(3)
    lengths = rng.integers(1, 24, size=10)  # many distinct prompt lengths
    assert len(set(lengths)) > len(engine.worker.prefill_chunks)
    ids = [
        engine.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=4)
        for n in lengths
    ]
    out = engine.run()
    assert set(ids) == set(out)
    # chunk-prefill executables: one per bucket, NOT one per prompt length
    worker = engine.worker
    assert engine.prefill_compiles <= len(worker.prefill_chunks)
    assert (
        sum(step._cache_size() for step in worker._chunk_steps.values())
        <= len(worker.prefill_chunks)
    )
    # decode: at most one executable per admission stage (a stage whose only
    # work was chunk prefill never ticks), each compiled exactly once
    assert engine.admission.stage == engine.admission.num_stages - 1
    assert set(worker._decodes) <= {1, 2, 4} and 4 in worker._decodes
    assert engine.decode_compiles == len(worker._decodes) <= engine.admission.num_stages
    assert all(step._cache_size() == 1 for step in worker._decodes.values())
    # re-serving at known widths/buckets adds no executables
    ids2 = [engine.submit(rng.integers(0, cfg.vocab_size, 13), max_new_tokens=3)]
    engine.run()
    assert engine.prefill_compiles <= len(worker.prefill_chunks)
    assert all(step._cache_size() == 1 for step in worker._decodes.values())


def test_paged_slot_recycling_and_memory_high_water():
    """More requests than slots complete through recycled pages, and the
    pool's high-water mark stays below a dense per-slot cache's resident
    KV for the same ring."""
    cfg, model, params = _setup("qwen2.5-3b")
    n_requests, n_slots = 6, 2
    prompts = np.asarray(
        jax.random.randint(jax.random.key(1), (n_requests, 6), 0, cfg.vocab_size)
    )
    ref = ServeEngine(model, params, cache_len=64).generate(prompts, max_new_tokens=5)
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=n_slots, page_size=4,
        prefill_chunks=(4,), prefix_cache=False,
    )
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    out = engine.run()
    assert len(out) == n_requests
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(out[rid], ref[i], err_msg=f"request {i}")
    mem = engine.memory_stats()
    assert mem["kv_bytes_peak"] < mem["kv_bytes_dense_equiv"]
    engine.worker.pool.check()
    assert engine.worker.pool.used == 0  # every page returned after the drain


def test_paged_pool_pressure_defers_admission():
    """A pool smaller than (slots × slot budget) forces deferred admission
    (requeue) and LRU eviction of published pages; every request still
    completes with correct greedy output."""
    cfg, model, params = _setup("qwen2.5-3b")
    prompts = _shared_prefix_prompts(cfg, n=4, prefix_len=6, suffix_len=2)
    static = ServeEngine(model, params, cache_len=64)
    ref = [static.generate(p[None, :], max_new_tokens=5)[0] for p in prompts]
    # each request needs ceil((8+5)/4) = 4 pages; capacity 5 ⇒ one at a time
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=32, max_slots=2, page_size=4,
        num_pages=6, prefill_chunks=(4,),
    )
    ids = [engine.submit(p, max_new_tokens=5) for p in prompts]
    out = engine.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(out[rid], ref[i], err_msg=f"request {i}")
    engine.worker.pool.check()


@ENGINES
def test_paged_mixed_lengths_and_budgets(engine_cls):
    """Mixed prompt lengths and per-request max_new_tokens share one ring:
    a request finishing right at prefill completion (max_new_tokens=1), a
    1-token prompt (pure teacher-forced prefill, no chunk fits), and chunked
    prompts all match the static engine."""
    cfg, model, params = _setup("qwen2.5-3b")
    engine = engine_cls(
        model, params, cache_len=64, max_slots=2, page_size=4, prefill_chunks=(4,)
    )
    p = np.asarray(jax.random.randint(jax.random.key(1), (8,), 0, cfg.vocab_size))
    a = engine.submit(p[:4], max_new_tokens=1)
    b = engine.submit(p, max_new_tokens=8)
    c = engine.submit(p[:6], max_new_tokens=3)
    d = engine.submit(p[:1], max_new_tokens=4)
    out = engine.run()
    se = ServeEngine(model, params, cache_len=64)
    for rid, (prompt, n) in ((a, (p[:4], 1)), (b, (p, 8)), (c, (p[:6], 3)), (d, (p[:1], 4))):
        np.testing.assert_array_equal(
            out[rid], se.generate(prompt[None, :], max_new_tokens=n)[0]
        )
    for worker in engine.workers:
        worker.pool.check()
        assert worker.pool.used == worker.index.num_pages  # only published pages live


def test_requeued_request_survives_one_page_pool():
    """Regression: on a 1-page pool every admission beyond the first is
    requeued until the resident request releases its page — including a
    requeue that lands on what would otherwise be the final tick. The run
    must drain the requeue list before declaring the pool idle; dropping the
    tail request (or raising) loses a submitted result."""
    cfg, model, params = _setup("qwen2.5-3b")
    # capacity 1 page of 8: each request (4 prompt + 3 new = 7 positions)
    # needs exactly that page, so the ring serves strictly one at a time
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=8, max_slots=2, page_size=8, num_pages=2,
        prefill_chunks=(4,), prefix_cache=False,
    )
    prompts = np.asarray(
        jax.random.randint(jax.random.key(1), (3, 4), 0, cfg.vocab_size)
    )
    ids = [engine.submit(p, max_new_tokens=3) for p in prompts]
    out = engine.run()
    assert set(out) == set(ids), "a requeued request was dropped at the drain"
    static = ServeEngine(model, params, cache_len=8)
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            out[rid], static.generate(prompts[i][None, :], max_new_tokens=3)[0]
        )
    engine.worker.pool.check()
    assert engine.worker.pool.used == 0


@ENGINES
def test_request_larger_than_pool_raises_not_hangs(engine_cls):
    """A request whose footprint exceeds the whole pool (even after full
    index eviction) must fail loudly at admission — the complement of the
    requeue-drain guarantee above. The disaggregated engine admits prompts
    into its prefill pool, so that is the pool made too small."""
    cfg, model, params = _setup("qwen2.5-3b")
    disagg = engine_cls is DisaggregatedEngine
    engine = engine_cls(
        model, params, cache_len=16, max_slots=2, page_size=4, prefill_chunks=(4,),
        **{"prefill_pages" if disagg else "num_pages": 2},
    )
    engine.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size, max_new_tokens=2)
    pool = "prefill page pool" if disagg else "page pool"
    with pytest.raises(RuntimeError, match=f"^{pool} .* cannot fit"):
        engine.run()


@ENGINES
def test_paged_sampling_params_per_slot(engine_cls):
    """top_k=1 reduces to greedy (identical to static); temperature sampling
    is reproducible per engine seed and stays in-vocab. The two engines draw
    from their RNG stream in different orders, so their sampled tokens are
    not compared with each other."""
    cfg, model, params = _setup("qwen2.5-3b")
    prompts = np.asarray(jax.random.randint(jax.random.key(1), (2, 6), 0, cfg.vocab_size))
    ref = ServeEngine(model, params, cache_len=64).generate(prompts, max_new_tokens=6)

    eng = engine_cls(model, params, cache_len=64, max_slots=2, page_size=4, seed=7)
    ids = [eng.submit(p, max_new_tokens=6, temperature=1.0, top_k=1) for p in prompts]
    out = eng.run()
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(out[rid], ref[i])

    def sample_run():
        e = engine_cls(model, params, cache_len=64, max_slots=2, page_size=4, seed=7)
        rids = [e.submit(p, max_new_tokens=6, temperature=0.8, top_k=16) for p in prompts]
        res = e.run()
        return [res[r] for r in rids]

    a, b = sample_run(), sample_run()
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra, rb)
        assert (ra < cfg.vocab_size).all()
