"""Serving demo: static batch vs the paged continuous-batching engine with
a stagewise admission ramp — through the same model code the 524k-context
dry-run lowers.

    PYTHONPATH=src python examples/serve_demo.py [--arch rwkv6-1.6b]

The paged engine starts with a single decode slot, and as the queue keeps
the ring under sustained pressure it enlarges the slot budget
geometrically (b₁ρˢ — SEBS's stagewise batch enlargement applied to
serving), recycling freed slots and their KV pages for queued requests
mid-decode-loop.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serve import PagedContinuousBatchingEngine, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config(args.arch, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))

    prompts = np.asarray(
        jax.random.randint(jax.random.key(1), (args.requests, 8), 0, cfg.vocab_size)
    )
    print(f"arch={cfg.name} (smoke variant) family={cfg.family}")

    # static batch: everyone prefilled and decoded in lockstep
    static = ServeEngine(model, params, cache_len=64)
    ref = static.generate(prompts[: args.slots], max_new_tokens=args.new_tokens)
    print(f"\n[static] one batch of {args.slots}:")
    for i, row in enumerate(ref):
        print(f"  request {i}: prompt={row[:8].tolist()} -> generated={row[8:].tolist()}")

    # continuous batching: FIFO queue, slot + page recycling, stagewise admission
    engine = PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=args.slots, b1=1, rho=2.0, patience=1,
        page_size=8, prefill_chunks=(8,),
    )
    ids = [engine.submit(p, max_new_tokens=args.new_tokens) for p in prompts]
    results = engine.run()
    print(f"\n[paged] {args.requests} requests through <= {args.slots} slots:")
    for rid in ids:
        row = results[rid]
        print(f"  request {rid}: prompt={row[:8].tolist()} -> generated={row[8:].tolist()}")
    print(
        f"\nadmission ladder {engine.admission.ladder} "
        f"(at most one compiled decode variant per stage: {engine.decode_compiles} compiles), "
        f"peak ring width {engine.stats['peak_width']}, "
        f"{engine.stats['ticks']} decode ticks for {engine.stats['decoded_tokens']} tokens"
    )


if __name__ == "__main__":
    main()
