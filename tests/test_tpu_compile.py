"""Compile the hot paths for a TPU v5e chip that is described, not attached:
the three paged-decode Pallas kernels (``interpret=False``), one paged decode
tick of the XLA path and the SEBS accumulate-4 train step, at qwen2.5-3b
widths.

Interpret mode cannot see what the chip's compiler refuses (block shapes off
the (8, 128) tiling, VMEM overuse, HBM overuse); these compiles do. The
topology is described only inside a fixture: only one process at a time may
load the TPU library, and a module-level call would make the test workers
collect different tests.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode import ops
from repro.models import build_model
from repro.optim import make_optimizer
from repro.serve.step import build_paged_decode_step
from repro.train.state import TrainState
from repro.train.step import build_train_step

CFG = get_config("qwen2.5-3b", "full").replace(param_dtype="bfloat16")
SLOTS, PAGE, MAX_PAGES, CHUNK = 8, 16, 18, 128
PAGES = 1 + SLOTS * MAX_PAGES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _pages(spec):
    hkv, d = CFG.num_kv_heads, CFG.resolved_head_dim
    return spec((PAGES, hkv, PAGE, d), jnp.bfloat16), spec((PAGES, hkv, PAGE, d), jnp.bfloat16)


def _compile_kernel(fn, *args):
    lowered = fn.lower(*args, interpret=False)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_flash_decode_compiles(spec):
    k, v = _pages(spec)
    q = spec((SLOTS, CFG.num_heads, CFG.resolved_head_dim), jnp.bfloat16)
    _compile_kernel(ops.paged_flash_decode, q, k, v,
                    spec((SLOTS, MAX_PAGES), jnp.int32), spec((SLOTS,), jnp.int32))


def test_paged_chunk_prefill_compiles(spec):
    k, v = _pages(spec)
    q = spec((1, CHUNK, CFG.num_heads, CFG.resolved_head_dim), jnp.bfloat16)
    _compile_kernel(ops.paged_chunk_prefill, q, k, v,
                    spec((1, MAX_PAGES), jnp.int32), spec((1,), jnp.int32))


def test_fused_sample_compiles(spec):
    key = jax.eval_shape(lambda: jax.random.key(0))
    _compile_kernel(ops.fused_sample, spec((SLOTS, CFG.vocab_size), jnp.float32),
                    spec(key.shape, key.dtype), spec((SLOTS,), jnp.float32),
                    spec((SLOTS,), jnp.int32))


def test_paged_decode_tick_xla_compiles(spec):
    """All 36 layers with bf16 weights fit one chip (f32 weights do not)."""
    model = build_model(CFG)

    def shapes(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = shapes(jax.eval_shape(lambda: model.init(jax.random.key(0))[0]))
    cache = shapes(jax.eval_shape(lambda: model.init_paged_cache(PAGES, PAGE, SLOTS)))
    key = shapes(jax.eval_shape(lambda: jax.random.key(0)))
    compiled = build_paged_decode_step(model, SLOTS).lower(
        params, spec((SLOTS, 1), jnp.int32), cache, spec((SLOTS,), jnp.int32),
        spec((SLOTS, MAX_PAGES), jnp.int32), spec((SLOTS,), jnp.bool_),
        spec((SLOTS,), jnp.float32), spec((SLOTS,), jnp.int32), key,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30


def test_accumulate4_train_step_compiles_unrolled(spec):
    """The SEBS stage-2 step (4 layers, f32 params, bf16 compute, psgd,
    4 microbatches of 2 x 512) runs its microbatches as straight-line code,
    no microbatch ``while``, and fits one chip."""
    cfg = get_config("qwen2.5-3b", "full")
    (seg,) = cfg.segments
    cfg = cfg.replace(segments=(dataclasses.replace(seg, repeat=4),),
                      param_dtype="float32", compute_dtype="bfloat16")
    model = build_model(cfg)
    optimizer = make_optimizer("psgd", gamma=1e4)

    def shapes(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    def init():
        params, _ = model.init(jax.random.key(0))
        return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))

    state = shapes(jax.eval_shape(init))
    step = build_train_step(model, optimizer, accum_steps=4)
    compiled = step.lower(state, {"tokens": spec((4, 2, 512), jnp.int32)},
                          spec((), jnp.float32), spec((), jnp.int32)).compile()
    assert not re.search(r'op_name="jit\(step\)/while"', compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= 12.3e9


def test_hybrid_reshape_stage2_step_fits(spec):
    """train-sebs-hybrid's largest step: granite-4.0-h-micro cut to one
    period (mamba x5, attention, mamba x4) and 25,088 vocabulary rows, f32
    params, bf16 compute, psgd, the reshape-mode batch of stage 2 (4 rows of
    2,048 tokens), fits one chip with room to spare."""
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from bench import program_hybrid

    c = json.loads((root / "bench/configs/granite-4.0-h-micro-train10l.json").read_text())
    mix = json.loads((root / "bench/traffic/sebs-ladder-hybrid.json").read_text())
    model = program_hybrid.build(c)
    optimizer = make_optimizer("psgd", gamma=mix["gamma"])

    def init():
        params, _ = model.init(jax.random.key(0))
        return TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))

    state = jax.tree.map(lambda x: spec(x.shape, x.dtype), jax.eval_shape(init))
    rows = mix["b1"] * mix["rho"] ** (mix["stages"] - 1)
    compiled = build_train_step(model, optimizer).lower(
        state, {"tokens": spec((rows, mix["seq"]), jnp.int32)},
        spec((), jnp.float32), spec((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= 15.0e9
