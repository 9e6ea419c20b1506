"""Serving engines: static-batch greedy consistency, the stagewise admission
ramp's gating and compile accounting, and request lifecycle stamps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (
    AdmissionController,
    DisaggregatedEngine,
    PagedContinuousBatchingEngine,
    ServeEngine,
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


@pytest.mark.parametrize("arch", ARCHS)
def test_greedy_generation_matches_teacher_forced_forward(arch):
    """Feed the generated sequence back through forward(): every generated
    token must equal the forward argmax at its position (greedy decode
    consistency across prefill + decode cache paths)."""
    cfg, model, params = _setup(arch)
    engine = ServeEngine(model, params, cache_len=64)
    prompts = np.asarray(jax.random.randint(jax.random.key(1), (2, 6), 0, cfg.vocab_size))
    out = engine.generate(prompts, max_new_tokens=5)
    assert out.shape == (2, 11)
    full_logits, _ = model.forward(params, {"tokens": jnp.asarray(out)})
    preds = np.asarray(jnp.argmax(full_logits[:, :, : cfg.vocab_size], axis=-1))
    # token t+1 of the generated sequence == forward argmax at position t
    gen_region = slice(5, 10)  # positions whose next token was generated
    agreement = (preds[:, gen_region] == out[:, 6:11]).mean()
    assert agreement >= 0.8, agreement


def test_whisper_generation_with_audio_memory():
    cfg, model, params = _setup("whisper-tiny")
    engine = ServeEngine(model, params, cache_len=32)
    prompts = np.zeros((2, 4), np.int32)
    audio = 0.1 * np.asarray(
        jax.random.normal(jax.random.key(2), (2, cfg.encoder_seq, cfg.d_model))
    )
    out = engine.generate(prompts, max_new_tokens=4, memory=jnp.asarray(audio, jnp.bfloat16))
    assert out.shape == (2, 8)
    assert (out[:, 4:] < cfg.vocab_size).all()


# ---------------------------------------------------------------------------
# scheduled engines
# ---------------------------------------------------------------------------


def test_admission_ramp_compiles_one_decode_variant_per_stage():
    """The stagewise ramp mirrors StageController's compile-cache design:
    exactly one compiled decode step per admission stage (asserted via the
    engine's compile-count hook, as test_trainer_modes does for train
    steps), and re-serving at known widths adds none."""
    cfg, model, params = _setup("qwen2.5-3b")
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=4, b1=1, rho=2.0, patience=2,
        page_size=4, prefill_chunks=(4,),
    )
    assert engine.admission.ladder == [1, 2, 4]
    prompts = np.asarray(jax.random.randint(jax.random.key(1), (8, 4), 0, cfg.vocab_size))
    ids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    out = engine.run()
    assert set(out) == set(ids)
    # sustained 8-deep queue must ramp through every stage
    assert engine.admission.stage == engine.admission.num_stages - 1
    assert sorted(engine.worker._decodes) == [1, 2, 4]
    assert engine.decode_compiles == engine.admission.num_stages
    # serving more traffic at the same widths reuses the compiled variants
    ids2 = [engine.submit(p, max_new_tokens=4) for p in prompts[:3]]
    out2 = engine.run()
    assert set(ids2) <= set(out2)
    assert engine.decode_compiles == engine.admission.num_stages


def test_admission_controller_sustained_load_gating():
    """Budget follows b₁ρˢ only under sustained pressure; transient bursts
    (shorter than ``patience``) never bump the stage."""
    ctl = AdmissionController(b1=2, rho=2.0, max_slots=8, patience=2)
    assert ctl.ladder == [2, 4, 8]
    assert ctl.observe(10) == 2  # pressure tick 1 of 2
    assert ctl.observe(1) == 2  # pressure reset: burst was transient
    assert ctl.observe(10) == 2
    assert ctl.observe(10) == 4  # sustained → stage 1
    assert ctl.observe(10) == 4
    assert ctl.observe(10) == 8  # stage 2 (cap)
    assert ctl.observe(100) == 8  # saturated: never exceeds max_slots


def test_request_latency_guarded_until_done():
    """Regression: latency was t_finish - t_submit even for QUEUED/RUNNING
    requests (t_finish == 0.0) — a huge negative number that would silently
    poison any averaged latency metric. It must be NaN until DONE."""
    from repro.serve.scheduler import DONE, RequestScheduler

    sched = RequestScheduler()
    rid = sched.submit(np.array([1, 2, 3]), max_new_tokens=2)
    req = sched.requests[rid]
    assert np.isnan(req.latency)  # queued
    req.state = "running"
    assert np.isnan(req.latency)  # running
    req.state = DONE
    req.t_finish = req.t_submit + 0.125
    assert req.latency == pytest.approx(0.125)


@pytest.mark.parametrize(
    "engine_cls", [PagedContinuousBatchingEngine, DisaggregatedEngine],
    ids=["paged", "disagg"],
)
def test_engine_stamps_full_request_lifecycle(engine_cls):
    """Every request served by a scheduled engine carries real monotonic
    lifecycle stamps (submit < admit ≤ prefill_done ≤ first_token < finish)
    and the derived phase durations are finite and add up — the contract
    the obs tracer and the SLO percentile reports are built on."""
    cfg, model, params = _setup("qwen2.5-3b")
    engine = engine_cls(
        model, params, cache_len=64, max_slots=2, page_size=4, prefill_chunks=(4,)
    )
    prompts = np.asarray(jax.random.randint(jax.random.key(1), (4, 6), 0, cfg.vocab_size))
    ids = [engine.submit(p, max_new_tokens=4) for p in prompts]
    engine.run()
    for rid in ids:
        req = engine.scheduler.requests[rid]
        assert 0.0 < req.t_submit < req.t_admit
        assert req.t_admit <= req.t_prefill_done <= req.t_first_token < req.t_finish
        for phase in (req.queue_s, req.prefill_s, req.ttft_s, req.decode_s):
            assert np.isfinite(phase) and phase >= 0.0
        assert req.ttft_s + req.decode_s == pytest.approx(req.latency)
