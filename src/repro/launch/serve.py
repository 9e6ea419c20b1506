"""Serving launcher CLI: batched generation through the KV-cache serve path.

    # static batch (seed behaviour)
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --batch 4

    # paged KV cache + radix prefix sharing + chunked prefill, with a
    # stagewise admission ramp
    PYTHONPATH=src python -m repro.launch.serve --engine paged \
        --requests 12 --slots 4 --b1 1 --rho 2.0 --page-size 16 --chunk 32

    # disaggregated prefill/decode across two submeshes (8 host devices)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --engine disagg \
        --requests 12 --slots 4 --prefill-devices 4 --decode-devices 4

    # qwen2.5-3b at published widths on one TPU chip (weights in bf16)
    PYTHONPATH=src python -m repro.launch.serve --variant full --engine paged \
        --param-dtype bfloat16 --prompt-len 256 --new-tokens 32 --cache-len 288 \
        --slots 8 --chunk 128 --kernel pallas

``main(argv)`` can also be called in-process (``chip_smoke.py`` does): it
returns the engine, the prompts, the request ids and the results.
"""
from __future__ import annotations

import argparse
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_disagg_submeshes
from repro.models import build_model
from repro.obs import MetricsRegistry, Tracer
from repro.serve import (
    DisaggregatedEngine,
    PagedContinuousBatchingEngine,
    ServeEngine,
)
from repro.utils.log import get_logger

log = get_logger("serve")


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--param-dtype", default=None, choices=["float32", "bfloat16"],
                    help="weight dtype (default: the config's own)")
    ap.add_argument("--engine", choices=["static", "paged", "disagg"],
                    default="static")
    ap.add_argument("--batch", type=int, default=4, help="static: batch size")
    ap.add_argument("--requests", type=int, default=8, help="paged/disagg: request count")
    ap.add_argument("--slots", type=int, default=4, help="paged/disagg: max slot-ring width")
    ap.add_argument("--b1", type=int, default=None,
                    help="paged/disagg: initial slot budget (default: --slots, no ramp)")
    ap.add_argument("--rho", type=float, default=2.0, help="paged/disagg: stage growth factor")
    ap.add_argument("--patience", type=int, default=2,
                    help="paged/disagg: sustained-load ticks before a stage bump")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per KV page")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged: pool size in pages (default: dense-equivalent)")
    ap.add_argument("--chunk", type=int, action="append", default=None,
                    help="paged: prefill chunk size (repeatable for multiple buckets)")
    ap.add_argument("--prefix-cache", dest="prefix_cache", action="store_true",
                    default=True, help="paged: share prompt-prefix pages (default)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache", action="store_false")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="paged: give all requests a common prompt prefix of this length")
    ap.add_argument("--kernel", choices=["xla", "pallas"], default="xla",
                    help="paged: decode attention/sampler path (pallas = "
                         "kernels/paged_decode; interpret mode off-TPU)")
    ap.add_argument("--prefill-devices", type=int, default=1,
                    help="disagg: pods in the prefill submesh")
    ap.add_argument("--decode-devices", type=int, default=1,
                    help="disagg: pods in the decode submesh")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="disagg: prefill worker ring width")
    ap.add_argument("--prefill-pages", type=int, default=None,
                    help="disagg: prefill pool size in pages (default: "
                         "prompt-dense-equivalent for the prefill ring)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(per-request lifecycle spans, per-tick spans and "
                         "counters; open in Perfetto, summarize with "
                         "tools/trace_view.py)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the metrics registry snapshot (counters, "
                         "gauges, histogram percentiles) as JSON")
    args = ap.parse_args(argv)

    for flag, value, low in (
        ("--batch", args.batch, 1),
        ("--requests", args.requests, 1),
        ("--slots", args.slots, 1),
        ("--patience", args.patience, 1),
        ("--prompt-len", args.prompt_len, 1),
        ("--new-tokens", args.new_tokens, 1),
        ("--cache-len", args.cache_len, 1),
        ("--page-size", args.page_size, 1),
        ("--shared-prefix", args.shared_prefix, 0),
        ("--top-k", args.top_k, 0),
    ):
        if value < low:
            ap.error(f"{flag} must be >= {low} (got {value})")
    if args.temperature < 0:
        ap.error(f"--temperature must be >= 0 (got {args.temperature})")
    if args.prompt_len + args.new_tokens > args.cache_len:
        ap.error(
            f"--prompt-len {args.prompt_len} + --new-tokens {args.new_tokens} "
            f"exceeds --cache-len {args.cache_len}"
        )
    if args.b1 is not None and not 1 <= args.b1 <= args.slots:
        ap.error(f"--b1 must be in [1, --slots={args.slots}] (got {args.b1})")
    if args.b1 is not None and args.b1 < args.slots and args.rho <= 1.0:
        ap.error(f"--rho must be > 1.0 to ramp {args.b1} -> {args.slots} slots")
    if args.shared_prefix > args.prompt_len:
        ap.error(
            f"--shared-prefix {args.shared_prefix} exceeds --prompt-len {args.prompt_len}"
        )
    if args.chunk and any(c < 1 for c in args.chunk):
        ap.error(f"--chunk sizes must be >= 1 (got {args.chunk})")
    if args.pages is not None and args.pages < 2:
        ap.error(f"--pages must be >= 2 (pool reserves scratch page 0; got {args.pages})")
    if args.engine == "static" and args.b1 is not None:
        ap.error("--b1 requires --engine paged or disagg")
    if args.engine == "static" and (args.trace or args.metrics):
        ap.error("--trace/--metrics require a scheduled engine "
                 "(--engine paged or disagg)")
    if args.engine == "static":
        if args.pages is not None:
            ap.error("--pages requires --engine paged or disagg")
        if args.chunk is not None:
            ap.error("--chunk requires --engine paged or disagg")
        if args.shared_prefix:
            ap.error("--shared-prefix requires --engine paged or disagg (prefix sharing)")
        if args.kernel != "xla":
            ap.error("--kernel pallas requires --engine paged or disagg")
    if args.engine != "disagg":
        for flag, value, default in (
            ("--prefill-devices", args.prefill_devices, 1),
            ("--decode-devices", args.decode_devices, 1),
            ("--prefill-slots", args.prefill_slots, 2),
            ("--prefill-pages", args.prefill_pages, None),
        ):
            if value != default:
                ap.error(f"{flag} requires --engine disagg")
    else:
        if args.prefill_devices < 1 or args.decode_devices < 1:
            ap.error("--prefill-devices and --decode-devices must each be >= 1")
        if args.prefill_slots < 1:
            ap.error("--prefill-slots must be >= 1")
        if args.prefill_pages is not None and args.prefill_pages < 2:
            ap.error(
                f"--prefill-pages must be >= 2 (pool reserves scratch page 0; "
                f"got {args.prefill_pages})"
            )

    enable_compile_cache()
    cfg = get_config(args.arch, args.variant)
    if args.param_dtype is not None:
        cfg = cfg.replace(param_dtype=args.param_dtype)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))

    tracer = Tracer() if args.trace else None
    metrics = MetricsRegistry() if args.metrics else None
    obs_kwargs = {"tracer": tracer, "metrics": metrics}

    if args.engine == "static":
        engine = ServeEngine(model, params, cache_len=args.cache_len)
        prompts = np.asarray(
            jax.random.randint(jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size)
        )
        out = engine.generate(prompts, max_new_tokens=args.new_tokens)
        for i, row in enumerate(out):
            log.info("req %d: %s -> %s", i, row[: args.prompt_len].tolist(),
                     row[args.prompt_len:].tolist())
        return {"engine": engine, "prompts": prompts, "outputs": out}

    if args.engine == "disagg":
        prefill_mesh, decode_mesh = make_disagg_submeshes(
            prefill_pods=args.prefill_devices, decode_pods=args.decode_devices
        )
        engine = DisaggregatedEngine(
            model, params, cache_len=args.cache_len, max_slots=args.slots,
            b1=args.b1, rho=args.rho, patience=args.patience,
            page_size=args.page_size, num_pages=args.pages,
            prefix_cache=args.prefix_cache,
            prefill_chunks=tuple(args.chunk) if args.chunk else (32,),
            kernel=args.kernel,
            prefill_slots=args.prefill_slots, prefill_pages=args.prefill_pages,
            prefill_device=prefill_mesh.devices.flat[0],
            decode_device=decode_mesh.devices.flat[0],
            **obs_kwargs,
        )
        log.info(
            "disagg submeshes: prefill %s on %s | decode %s on %s",
            dict(zip(prefill_mesh.axis_names, prefill_mesh.devices.shape)),
            engine.prefill_device,
            dict(zip(decode_mesh.axis_names, decode_mesh.devices.shape)),
            engine.decode_device,
        )
    else:
        engine = PagedContinuousBatchingEngine(
            model, params, cache_len=args.cache_len, max_slots=args.slots,
            b1=args.b1, rho=args.rho, patience=args.patience,
            page_size=args.page_size, num_pages=args.pages,
            prefix_cache=args.prefix_cache,
            prefill_chunks=tuple(args.chunk) if args.chunk else (32,),
            kernel=args.kernel,
            **obs_kwargs,
        )
    prompts = np.asarray(
        jax.random.randint(jax.random.key(1), (args.requests, args.prompt_len), 0, cfg.vocab_size)
    )
    if args.shared_prefix:
        prompts = prompts.copy()
        prompts[:, : args.shared_prefix] = prompts[0, : args.shared_prefix]
    ids = [
        engine.submit(p, max_new_tokens=args.new_tokens,
                      temperature=args.temperature, top_k=args.top_k)
        for p in prompts
    ]
    results = engine.run()
    for rid in ids:
        row = results[rid]
        log.info("req %d: %s -> %s", rid, row[: args.prompt_len].tolist(),
                 row[args.prompt_len:].tolist())
    log.info(
        "admission ladder %s | peak width %d | %d decode ticks | %d tokens | %d compiled stage(s)",
        engine.admission.ladder, engine.stats["peak_width"], engine.stats["ticks"],
        engine.stats["decoded_tokens"], engine.decode_compiles,
    )
    mem = engine.memory_stats()
    log.info(
        "pages peak %d/%d | prefix hit-rate %.0f%% | prefill computed %d "
        "(%d reused) | %d chunk step(s) compiled | kv peak %d KiB "
        "(dense-equiv %d KiB)",
        mem["pages_peak"], mem["pages_capacity"],
        100 * mem["prefix_hit_rate"],
        engine.stats["prefill_tokens_computed"],
        engine.stats["prefix_tokens_reused"], engine.prefill_compiles,
        mem["kv_bytes_peak"] // 1024, mem["kv_bytes_dense_equiv"] // 1024,
    )
    if args.engine == "disagg":
        log.info(
            "streamed %d transfer(s), %d page(s), %d KiB over the seam | "
            "adopted %d page(s) decode-side | prefill pool peak %d/%d",
            engine.stats["transfers"], engine.stats["pages_streamed"],
            engine.stats["seam_bytes"] // 1024,
            engine.stats["pages_adopted"],
            mem["prefill_pages_peak"], mem["prefill_pages_capacity"],
        )
    if tracer is not None:
        tracer.dump_chrome(args.trace)
        log.info("chrome trace (%d events, %d dropped) written to %s — "
                 "open in ui.perfetto.dev or summarize with tools/trace_view.py",
                 len(tracer.events), tracer.dropped, args.trace)
    if metrics is not None:
        metrics.dump(args.metrics)
        log.info("metrics snapshot (%d series) written to %s", len(metrics), args.metrics)
    return {"engine": engine, "prompts": prompts, "ids": ids, "results": results}


if __name__ == "__main__":
    main()
