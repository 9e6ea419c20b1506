"""Serving throughput: static batching vs continuous batching + admission ramp.

For each load level (number of simultaneously-arriving requests) measures
tokens/sec and per-request latency percentiles (p50/p99, all requests
arriving at t=0):

- ``static``: requests are served in consecutive fixed-size batches through
  :class:`ServeEngine` — a batch must fully finish before the next starts,
  so early finishers wait for stragglers and queued requests wait for whole
  batches.
- ``continuous``: all requests enter the FIFO queue of
  :class:`ContinuousBatchingEngine`; freed slots are recycled
  mid-decode-loop and the slot budget ramps stagewise (b₁ρˢ) under
  sustained load.
- ``paged_xla`` / ``paged_pallas``: :class:`PagedContinuousBatchingEngine`
  under both decode-kernel paths. The pallas row runs the interpret-mode
  lowering on this host (pallas under jit lowers to XLA ops off-TPU), so its
  absolute number is a liveness/trajectory signal, not the TPU win — the
  kernel's on-TPU claim is gated by the correctness records in
  ``kernel_bench`` instead. The pallas case runs at the light load only to
  keep the CI subset cheap.

A separate **prefill-interference** scenario measures what disaggregation is
for: long prompts admitted while a full ring of short requests decodes. The
interleaved paged engine threads the long prompts' chunked prefill through
the decode tick loop (small chunks, to bound the per-tick stall), while
:class:`DisaggregatedEngine` prefills them on its own submesh at a
whole-prompt chunk shape and streams finished KV pages across. Reported:
p50/p99 of the per-tick decode-token latency (``stats["decode_tick_s"]`` —
wall time until a decode tick's tokens reach the host, which for the
interleaved engine includes the prompt chunk its tick ran first) with and
without disaggregation, and ``serve_disagg_tok_per_s``. Because the CI box's
wall-clock speed drifts by more than the effect under test, the two engines
are timed in alternating passes and each reports the median across passes
(see :func:`_interfere_child`). This scenario runs in a subprocess with
``xla_force_host_platform_device_count=2`` so the two workers really occupy
disjoint devices and the page stream crosses a real ``device_put`` seam —
the parent process stays pinned to the one-device env of
:mod:`benchmarks._env`. The child runs to its end before the parent
initialises a JAX backend (:func:`before_backend`): a chip belongs to one
process at a time. On an accelerator the child gets the devices present;
with fewer than two, both workers share one and the records say so
(``devices`` and ``note`` in their context).

Compilation is excluded from both timings via a warmup pass that visits
every decode shape; the continuous engine's per-stage compile cache is kept
and the public ``admission.reset()`` / ``reset_stats()`` seams restart the
ramp and counters for the timed run.

Usage: ``PYTHONPATH=src python -m benchmarks.serve_throughput`` (or through
``python -m benchmarks.run --only serve``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List, Optional

import jax
import numpy as np

from benchmarks._schema import Record, print_csv
from repro.configs import get_config
from repro.models import build_model
from repro.obs import Tracer
from repro.obs.metrics import nearest_rank
from repro.serve import (
    ContinuousBatchingEngine,
    DisaggregatedEngine,
    PagedContinuousBatchingEngine,
    ServeEngine,
)

ARCH = "qwen2.5-3b"
PROMPT_LEN = 8
NEW_TOKENS = 16
CACHE_LEN = 64
SLOTS = 4  # static batch size == continuous max ring width
LOADS = (4, 16)
PAGE_SIZE = 8
PALLAS_LOAD = 4  # interpret-mode pallas case runs at the light load only

# prefill-interference scenario: a full ring of short decoders + a burst of
# long prompts. The interleaved engine prefills the long prompts in small
# chunks between decode ticks; the disaggregated engine prefills each whole
# prompt as one chunk on its own submesh and streams the pages across.
I_SLOTS = 16  # decode ring width
I_SHORT = 12  # short decoders (PROMPT_LEN prompt, I_NEW new tokens)
I_LONG = 4  # long prompts admitted into the remaining slots at t=0 —
# in the interleaved engine their chunked prefill rides every decode tick
# for the shorts' whole decode window; the disagg engine keeps them off it
I_LONG_LEN = 192
I_NEW = 32
I_CACHE = 224  # cache_len per slot: fits I_LONG_LEN + I_NEW exactly
I_CHUNK_INTERLEAVED = (PROMPT_LEN, 16)  # small chunks bound the tick stall
I_CHUNK_DISAGG = (PROMPT_LEN, I_LONG_LEN)  # whole-prompt prefill shape
I_REPS = 3  # alternating timed repetitions per engine (see _interfere_child)


def _prompts(cfg, n: int, key: int = 1) -> np.ndarray:
    return np.asarray(
        jax.random.randint(jax.random.key(key), (n, PROMPT_LEN), 0, cfg.vocab_size)
    )


PERCENTILE_METHOD = "nearest-rank"  # p_q = sorted(x)[ceil(q/100 * n) - 1]


def _pct(lat, q):
    """Nearest-rank percentile: the smallest observed value with at least
    q% of samples at or below it — always an actual measurement (np's
    default linear interpolation invents latencies between samples, and at
    small n its p99 understates the true worst tail). Delegates to
    :func:`repro.obs.metrics.nearest_rank` so the benchmark, the metrics
    registry, and tools/trace_view.py all report the same number for the
    same samples."""
    assert len(lat) > 0
    return nearest_rank([float(x) for x in lat], q)


def _bench_static(model, params, prompts) -> tuple[float, list]:
    engine = ServeEngine(model, params, cache_len=CACHE_LEN)
    engine.generate(prompts[:SLOTS], max_new_tokens=NEW_TOKENS)  # warmup/compile
    lat = []
    t0 = time.perf_counter()
    done = 0
    while done < len(prompts):
        chunk = prompts[done : done + SLOTS]
        if len(chunk) < SLOTS:  # pad to the compiled batch shape
            chunk = np.concatenate(
                [chunk, np.repeat(chunk[-1:], SLOTS - len(chunk), axis=0)]
            )
        engine.generate(chunk, max_new_tokens=NEW_TOKENS)
        batch_done = time.perf_counter() - t0
        n = min(SLOTS, len(prompts) - done)
        lat.extend([batch_done] * n)  # every request in the batch waits for it
        done += n
    elapsed = time.perf_counter() - t0
    return elapsed, lat


def _bench_continuous(model, params, prompts) -> tuple[float, list]:
    engine = ContinuousBatchingEngine(
        model, params, cache_len=CACHE_LEN, max_slots=SLOTS, b1=1, rho=2.0, patience=1
    )
    # warmup: same load shape, visits every stage width once (compile cache
    # is per-engine and keyed on ring width)
    for p in prompts:
        engine.submit(p, max_new_tokens=NEW_TOKENS)
    engine.run()
    # restart the ramp + zero the counters; compiled decode variants stay warm
    engine.admission.reset()
    engine.reset_stats()

    t0 = time.perf_counter()
    ids = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    engine.run()
    elapsed = time.perf_counter() - t0
    lat = [engine.scheduler.requests[r].latency for r in ids]
    return elapsed, lat


def _bench_paged(model, params, prompts, kernel: str) -> tuple[float, list]:
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=CACHE_LEN, max_slots=SLOTS, b1=1, rho=2.0,
        patience=1, page_size=PAGE_SIZE, prefill_chunks=(PROMPT_LEN,),
        kernel=kernel,
    )
    for p in prompts:  # warmup: visits every stage width + chunk bucket
        engine.submit(p, max_new_tokens=NEW_TOKENS)
    engine.run()
    engine.admission.reset()
    engine.reset_stats()

    t0 = time.perf_counter()
    ids = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    engine.run()
    elapsed = time.perf_counter() - t0
    lat = [engine.scheduler.requests[r].latency for r in ids]
    return elapsed, lat


def _interfere_workload(cfg):
    """16 short decoders submitted first (they fill the decode ring), then
    the long-prompt burst behind them — FIFO admission approximates 'long
    prompts arrive while everyone else is decoding'."""
    rng = np.random.default_rng(5)
    shorts = [
        rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
        for _ in range(I_SHORT)
    ]
    longs = [
        rng.integers(0, cfg.vocab_size, I_LONG_LEN).astype(np.int32)
        for _ in range(I_LONG)
    ]
    return shorts, longs


def _interfere_timed(engine, shorts, longs):
    """One timed pass of the interference workload on ``engine``. Returns
    (elapsed, per-tick decode latencies, short-request full latencies,
    total new tokens, streaming counters). The per-tick latency —
    ``stats["decode_tick_s"]``, wall time until a tick's decode tokens
    reach the host — is the interference metric: in the interleaved
    engine a decode token only lands after the tick's prompt chunk also
    ran (the head-of-line block), while the disaggregated decode worker's
    tick carries no prefill at all. Request wall-clock latency is kept
    alongside for context; on a serialized CPU harness it cannot separate
    the two designs (same total FLOPs either way), the per-token tick
    latency can."""
    t0 = time.perf_counter()
    sids = [engine.submit(p, max_new_tokens=I_NEW) for p in shorts]
    lids = [engine.submit(p, max_new_tokens=I_NEW) for p in longs]
    engine.run()
    elapsed = time.perf_counter() - t0
    ticks = list(engine.stats["decode_tick_s"])
    if engine.tracer.enabled:
        # the tracer's serve.decode_tick spans and stats["decode_tick_s"]
        # share one clock read per tick, so the durations are the SAME
        # floats — any drift means an instrumentation site forked the timing
        traced = engine.tracer.durations("serve.decode_tick")
        assert traced == ticks, (
            f"tracer decode_tick spans ({len(traced)}) drifted from "
            f"stats['decode_tick_s'] ({len(ticks)})"
        )
        ticks = traced
        engine.tracer.clear()  # pass isolation, like reset_stats below
    full_lat = [engine.scheduler.requests[r].latency for r in sids]
    streaming = {
        k: engine.stats[k]
        for k in ("transfers", "pages_streamed", "pages_adopted", "seam_bytes")
        if k in engine.stats
    }
    engine.admission.reset()
    engine.reset_stats()
    return elapsed, ticks, full_lat, (len(sids) + len(lids)) * I_NEW, streaming


def _interfere_child() -> dict:
    """Runs inside the 2-device subprocess: both engines on the interference
    workload. Returns the raw measurements (the parent owns Record making).

    Measurement design, forced by the harness: wall-clock speed of the CI
    box drifts by 2-3x over minutes, far larger than the effect under
    test, so timing one engine and then the other lets the drift pick the
    winner. Instead both engines are warmed up once (visiting every
    compile shape), then timed in ``I_REPS`` alternating passes
    (paged, disagg, paged, disagg, ...) so drift hits both equally, and
    each engine reports the *median across passes* of its per-pass tick
    percentiles. The prefix cache is disabled for this scenario only:
    the same prompts recur every pass, and radix hits would let later
    passes skip exactly the prefill compute whose interference is being
    measured."""
    cfg = get_config(ARCH, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    shorts, longs = _interfere_workload(cfg)

    devs = jax.devices()
    engines = {
        "paged": PagedContinuousBatchingEngine(
            model, params, cache_len=I_CACHE, max_slots=I_SLOTS,
            page_size=PAGE_SIZE, prefill_chunks=I_CHUNK_INTERLEAVED,
            prefix_cache=False, tracer=Tracer(),
        ),
        "disagg": DisaggregatedEngine(
            model, params, cache_len=I_CACHE, max_slots=I_SLOTS,
            page_size=PAGE_SIZE, prefill_chunks=I_CHUNK_DISAGG,
            prefill_slots=2, prefill_device=devs[0], decode_device=devs[-1],
            prefix_cache=False, tracer=Tracer(),
        ),
    }
    for engine in engines.values():
        _interfere_timed(engine, shorts, longs)  # warmup: compile shapes

    reps = {name: [] for name in engines}
    for _ in range(I_REPS):
        for name, engine in engines.items():
            reps[name].append(_interfere_timed(engine, shorts, longs))

    out = {"num_devices": jax.device_count(), "timed_reps": I_REPS}
    for name, runs in reps.items():
        p99s = [_pct(ticks, 99) for _, ticks, _, _, _ in runs]
        out[name] = {
            "tok_per_s": float(np.median(
                [total / elapsed for elapsed, _, _, total, _ in runs])),
            "decode_p50": float(np.median(
                [_pct(ticks, 50) for _, ticks, _, _, _ in runs])),
            "decode_p99": float(np.median(p99s)),
            "decode_p99_reps": p99s,
            "request_p99": float(np.median(
                [_pct(full, 99) for _, _, full, _, _ in runs])),
            **runs[-1][4],
        }
    return out


_interference: Optional[dict] = None  # the child's measurements


def before_backend() -> None:
    """Run the interference scenario in a subprocess whose host platform is
    forced to TWO devices (the parent env pins one), and keep its result for
    :func:`run`. Call it before this process initialises a JAX backend
    (``benchmarks.run`` does, before any module runs): on a chip the parent
    would hold the device the child needs. The child prints one JSON object
    on the last stdout line."""
    global _interference
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "the interference child must run before this process initialises "
            "a JAX backend (a chip belongs to one process)"
        )
    env = dict(os.environ)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_throughput", "--interfere-child"],
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"interference child failed ({proc.returncode}):\n{proc.stderr[-2000:]}"
        )
    _interference = json.loads(proc.stdout.strip().splitlines()[-1])


def run(out_dir: str = "benchmarks/results") -> List[Record]:
    if _interference is None:
        before_backend()
    cfg = get_config(ARCH, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    records: List[Record] = []
    details = {"percentile_method": PERCENTILE_METHOD, "results": []}
    for load in LOADS:
        prompts = _prompts(cfg, load)
        total_tokens = load * NEW_TOKENS
        benches = [
            ("static", lambda p: _bench_static(model, params, p)),
            ("continuous", lambda p: _bench_continuous(model, params, p)),
            ("paged_xla", lambda p: _bench_paged(model, params, p, "xla")),
        ]
        if load == PALLAS_LOAD:
            benches.append(
                ("paged_pallas", lambda p: _bench_paged(model, params, p, "pallas"))
            )
        for name, bench in benches:
            elapsed, lat = bench(prompts)
            tps = total_tokens / elapsed
            p50, p99 = _pct(lat, 50), _pct(lat, 99)
            details["results"].append(
                {
                    "engine": name,
                    "load": load,
                    "tok_per_s": tps,
                    "latency_p50_s": p50,
                    "latency_p99_s": p99,
                }
            )
            ctx = {
                "arch": ARCH, "load": load, "new_tokens": NEW_TOKENS,
                "slots": SLOTS, "percentile_method": PERCENTILE_METHOD,
            }
            derived = f"{tps:.1f} tok/s p50={p50 * 1e3:.0f}ms p99={p99 * 1e3:.0f}ms"
            records.append(Record(
                f"serve_{name}_load{load}_tok_per_s", tps, "tok/s",
                direction="higher", derived=derived, context=ctx,
            ))
            records.append(Record(
                f"serve_{name}_load{load}_us_per_token",
                round(elapsed / total_tokens * 1e6, 1), "us/token",
                direction="lower", derived=derived, context=ctx,
            ))
            records.append(Record(
                f"serve_{name}_load{load}_latency_p50", p50, "s",
                direction="lower", context=ctx,
            ))
            records.append(Record(
                f"serve_{name}_load{load}_latency_p99", p99, "s",
                direction="lower", context=ctx,
            ))
    interfere = _interference
    details["interference"] = interfere
    ictx = {
        "arch": ARCH, "slots": I_SLOTS, "short_requests": I_SHORT,
        "long_requests": I_LONG, "long_prompt_len": I_LONG_LEN,
        "new_tokens": I_NEW, "chunks_interleaved": list(I_CHUNK_INTERLEAVED),
        "chunks_disagg": list(I_CHUNK_DISAGG), "devices": interfere["num_devices"],
        "percentile_method": PERCENTILE_METHOD, "timed_reps": I_REPS,
        "prefix_cache": False,
    }
    if interfere["num_devices"] < 2:
        ictx["note"] = "one device: the prefill and decode workers share it"
    for name, key in (("paged", "paged"), ("disagg", "disagg")):
        m = interfere[key]
        records.append(Record(
            f"serve_interfere_{name}_decode_p99", m["decode_p99"], "s",
            direction="lower", context=ictx,
            derived=f"per-tick decode-token latency "
                    f"p50={m['decode_p50'] * 1e3:.1f}ms "
                    f"p99={m['decode_p99'] * 1e3:.1f}ms",
        ))
    records.append(Record(
        "serve_disagg_tok_per_s", interfere["disagg"]["tok_per_s"], "tok/s",
        direction="higher", context=ictx,
        derived=f"{interfere['disagg']['tok_per_s']:.1f} tok/s "
                f"({interfere['disagg']['transfers']} transfers, "
                f"{interfere['disagg']['pages_streamed']} pages streamed)",
    ))
    records.append(Record(
        "serve_interfere_disagg_p99_speedup",
        interfere["paged"]["decode_p99"] / interfere["disagg"]["decode_p99"],
        "ratio", direction="higher", context={**ictx, "tolerance": 0.25},
        derived=f"interleaved tick p99 / disagg tick p99 under long-prompt "
                f"interference (medians over {I_REPS} alternating passes)",
    ))
    _dump(details, out_dir, "serve_throughput.json")
    return records


def _dump(obj, out_dir: str, name: str) -> None:
    import json
    import os

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(obj, f, indent=2)


def main() -> None:
    if "--interfere-child" in sys.argv:
        print(json.dumps(_interfere_child()))
        return
    print_csv(run())


if __name__ == "__main__":
    main()
