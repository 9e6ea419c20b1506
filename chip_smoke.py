"""Bring-up check: the main paths of this repo on a TPU, at qwen2.5-3b widths.

    python chip_smoke.py             # one chip: kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: elastic data parallelism only

One process does everything (a chip belongs to one process at a time). It
exits non-zero on any failure, and refuses to run where JAX finds no TPU or
where ``src/repro`` is not next to this file. On success the last stdout
line is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases on one chip:

- kernels: the three ``kernels/paged_decode`` ops compiled for the chip
  (``interpret=False``) at qwen2.5-3b shapes, with ragged positions and a
  copy-on-write shared page table, against ``ref.py``;
- serve: ``launch/serve.py``'s paged engine at all 36 layers, once with
  ``--kernel xla`` and once with ``--kernel pallas``, weights in bf16 (the
  f32 decode step needs 16.75 GB of the chip's 15.75 GB);
- train: ``launch/train.py``'s SEBSTrainer (psgd, accumulate mode) over a
  two-stage SEBS ladder, depth cut to 4 layers so params, psgd anchor,
  gradients and activations fit one chip.

With ``--chips 4``: ``ElasticTrainer`` in exact-sync mode over a ladder that
widens 1 -> 2 -> 4 replicas, against the same run at device budget 1. The
run is at f32 compute, where the CPU host gives bit-identical losses and
final params; it reports whether the chip does, and the largest
differences, and fails only if the losses part by more than 1e-3 relative.

The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache``; a second run reports lower compile seconds.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import logging
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"

SERVE = dict(arch="qwen2.5-3b", variant="full", param_dtype="bfloat16",
             requests=8, prompt_len=256, new_tokens=32, slots=8, page_size=16,
             chunk=128)
# depth and microbatch x sequence settled from compiled.memory_analysis() of
# the accumulate-2 psgd step for a v5e chip: 4 layers, 2 x 512 needs
# 4.96 GB of arguments + 7.14 GB of temporaries; 6 layers at 2 x 512 needs
# 15.5 GB, too close to the 15.75 GB the chip holds
TRAIN = dict(arch="qwen2.5-3b", variant="full", layers=4, b1=2, seq=512,
             c1=6, rho=2, stages=2)
# accum 1, 2, 4 -> widths 1, 2, 4. Microbatch 1: at f32 compute the width-1
# program unrolls 4 microbatches of 2 x 512 into 17.3 GB of temporaries; at
# 1 x 512 the widest program (width 4) needs 4.96 GB + 9.10 GB
ELASTIC = dict(TRAIN, b1=1, c1=2, stages=3)

_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


class PhaseError(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


# -- device -------------------------------------------------------------------

def device_phase(chips: int):
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu", f"no TPU: JAX found {devs[0].platform}")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, found {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"device: {devs[0].device_kind} x{len(devs)} | jax {jax.__version__} | libtpu {libtpu}")
    return devs


# -- kernels ------------------------------------------------------------------

def kernel_phase() -> None:
    """The paged-decode kernels at qwen2.5-3b shapes against ref.py, with
    the bf16 tolerance band of tests/test_paged_decode_kernel.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_decode import ops, ref
    from repro.serve.step import sample_tokens

    cfg = get_config("qwen2.5-3b", "full")
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    slots, ps, mp, chunk, vocab = 8, 16, 18, 128, cfg.vocab_size
    k, v, table, pos = ref.random_paged_pool(0, slots=slots, ps=ps, mp=mp, hkv=hkv, d=d,
                                             share=True, dtype=jnp.bfloat16)
    rng = np.random.default_rng(1)
    tol = 2e-2

    def close(name, out, expect):
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32))))
        ok = bool(jnp.allclose(out.astype(jnp.float32), expect.astype(jnp.float32),
                               atol=tol, rtol=tol))
        say(f"kernels: {name} max |kernel - ref| {err:.3e} (atol=rtol={tol})")
        check(ok, f"{name} kernel disagrees with ref.py")

    q = jnp.asarray(rng.normal(size=(slots, hq, d)), jnp.bfloat16)
    lowered = ops.paged_flash_decode.lower(q, k, v, table, pos, interpret=False)
    check("tpu_custom_call" in lowered.as_text(),
          "paged_flash_decode did not lower to a TPU kernel")
    with jax.default_matmul_precision("highest"):
        expect = ref.paged_attention_ref(q, k, v, table, pos)
    close("paged_flash_decode", ops.paged_flash_decode(q, k, v, table, pos, interpret=False),
          expect)

    qc = jnp.asarray(rng.normal(size=(slots, chunk, hq, d)), jnp.bfloat16)
    start = jnp.maximum(pos - (chunk - 1), 0)
    with jax.default_matmul_precision("highest"):
        expect = ref.paged_prefill_ref(qc, k, v, table, start)
    close("paged_chunk_prefill",
          ops.paged_chunk_prefill(qc, k, v, table, start, interpret=False), expect)

    logits = jnp.asarray(rng.normal(size=(slots, vocab)) * 4, jnp.float32)
    temp = jnp.asarray([0.0, 0.7, 0.0, 1.0, 0.0, 0.3, 0.0, 1.5], jnp.float32)
    top_k = jnp.asarray([0, 0, 5, 50, 0, 1, 7, 0], jnp.int32)
    key = jax.random.key(0)
    got = np.asarray(ops.fused_sample(logits, key, temp, top_k, interpret=False))
    want = np.asarray(sample_tokens(logits, key, temp, top_k))
    greedy = np.asarray(temp) == 0
    say(f"kernels: fused_sample greedy rows {int((got == want)[greedy].sum())}/{int(greedy.sum())} "
        f"equal to argmax, sampled rows {int((got == want)[~greedy].sum())}/{int((~greedy).sum())} "
        "equal to sample_tokens (reported, not checked)")
    check(bool((got == want)[greedy].all()), "fused_sample greedy rows differ from argmax")


# -- serve --------------------------------------------------------------------

def _serve_argv(kernel: str, s: dict) -> list:
    return [
        "--arch", s["arch"], "--variant", s["variant"], "--param-dtype", s["param_dtype"],
        "--engine", "paged", "--kernel", kernel, "--requests", str(s["requests"]),
        "--prompt-len", str(s["prompt_len"]), "--new-tokens", str(s["new_tokens"]),
        "--cache-len", str(s["prompt_len"] + s["new_tokens"]),
        "--slots", str(s["slots"]), "--page-size", str(s["page_size"]),
        "--chunk", str(s["chunk"]),
    ]


def serve_phase() -> None:
    import jax
    import numpy as np

    from repro.launch import serve

    s = SERVE

    logging.getLogger("repro").setLevel(logging.WARNING)  # no per-request lines
    outs = {}
    for kernel in ("xla", "pallas"):
        c0, t0 = _compile_s[0], time.perf_counter()
        run = serve.main(_serve_argv(kernel, s))
        wall = time.perf_counter() - t0
        compiled = _compile_s[0] - c0
        engine, prompts = run["engine"], run["prompts"]
        rows = [np.asarray(run["results"][rid]) for rid in run["ids"]]
        for i, row in enumerate(rows):
            check(row.shape == (s["prompt_len"] + s["new_tokens"],),
                  f"{kernel}: request {i} has {row.shape[0]} tokens")
            check(bool((row[: s["prompt_len"]] == prompts[i]).all()),
                  f"{kernel}: request {i} lost its prompt")
        # warm rerun of the same requests on the same engine: no compiles
        engine.reset_stats()
        ids = [engine.submit(p, max_new_tokens=s["new_tokens"]) for p in prompts]
        t1 = time.perf_counter()
        engine.run()
        warm = time.perf_counter() - t1
        ticks = sorted(engine.stats["decode_tick_s"])
        new = len(ids) * s["new_tokens"]
        layers = sum(seg.num_layers for seg in engine.model.cfg.segments)
        say(f"serve[{kernel}]: {len(rows)} requests x {s['new_tokens']} new tokens at "
            f"{layers} layers, param_dtype {s['param_dtype']} | cold run {wall:.2f} s "
            f"({compiled:.2f} s compiling) | warm rerun {new / warm:.1f} tok/s "
            f"(wall, prefix-cache hits), median decode tick {ticks[len(ticks) // 2] * 1e3:.2f} ms")
        outs[kernel] = np.stack([row[s["prompt_len"]:] for row in rows])
        del run, engine
        gc.collect()
    agree = float((outs["xla"] == outs["pallas"]).mean())
    stats = jax.devices()[0].memory_stats() or {}
    say(f"serve: xla vs pallas greedy token agreement {agree:.4f} (reported, not checked) | "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")


# -- train --------------------------------------------------------------------

def _train_argv(t: dict) -> list:
    return [
        "--arch", t["arch"], "--variant", t["variant"], "--layers", str(t["layers"]),
        "--optimizer", "psgd", "--schedule", "sebs", "--mode", "accumulate",
        "--b1", str(t["b1"]), "--c1", str(t["c1"]), "--rho", str(t["rho"]),
        "--stages", str(t["stages"]), "--seq", str(t["seq"]), "--steps-log", "1",
    ]


def _expected_ladder(t: dict):
    from repro.core import SEBS

    sched = SEBS(b1=t["b1"], C1=t["c1"], rho=t["rho"], num_stages=t["stages"], eta=0.3)
    stages, batches = [], []
    for s, n in enumerate(sched.updates_per_stage()):
        stages += [s] * n
        batches += [int(round(t["b1"] * t["rho"] ** s))] * n
    return stages, batches


def _per_stage_times(trainer) -> str:
    by_stage = {}
    for ev in trainer.tracer.events:
        if ev.get("name") == "train.update":
            by_stage.setdefault(ev["args"]["stage"], []).append(ev["dur"])
    return "; ".join(
        f"stage {s}: first {d[0]:.2f} s (compile + run), then "
        + (", ".join(f"{x:.3f}" for x in d[1:]) or "-") + " s"
        for s, d in sorted(by_stage.items())
    )


def train_phase() -> None:
    import jax
    import numpy as np

    from repro.launch import train

    t = TRAIN

    say(f"train: qwen2.5-3b widths cut to {t['layers']} of 36 layers (memory), "
        f"microbatch {t['b1']} x seq {t['seq']}, psgd in f32, SEBS rho {t['rho']} "
        f"over {t['stages']} stages")
    OUT.mkdir(exist_ok=True)
    c0 = _compile_s[0]
    run = train.main(_train_argv(t) + ["--trace", str(OUT / "chip_smoke_train_trace.json")])
    log = run["log"]
    stages, batches = _expected_ladder(t)
    check(log.stages == stages, f"stage sequence {log.stages} != {stages}")
    check(log.batch_sizes == batches, f"batch sequence {log.batch_sizes} != {batches}")
    check(bool(np.isfinite(log.losses).all()), f"non-finite loss: {log.losses}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"train: stages {log.stages} batches {log.batch_sizes} losses "
        f"{[round(x, 4) for x in log.losses]} | {_compile_s[0] - c0:.2f} s compiling | "
        f"bytes_in_use {stats.get('bytes_in_use', 'not reported')}, process "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    say(f"train: update time per stage: {_per_stage_times(run['trainer'])}")


# -- four chips ---------------------------------------------------------------

def elastic_phase(chips: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train

    t = ELASTIC
    say(f"elastic: exact sync, budget {chips} vs 1, {t['layers']} of 36 layers, f32 compute, "
        f"microbatch {t['b1']} x seq {t['seq']}, ladder rho {t['rho']} over {t['stages']} stages")
    runs = {}
    for budget in (chips, 1):
        c0, t0 = _compile_s[0], time.perf_counter()
        run = train.main(_train_argv(t) + ["--compute-dtype", "float32", "--dp-elastic",
                                          "--sync-mode", "exact", "--device-budget", str(budget)])
        log, trainer = run["log"], run["trainer"]
        check(bool(np.isfinite(log.losses).all()), f"budget {budget}: non-finite loss")
        widths = sorted({trainer.planner.width_for(b // t["b1"]) for b in log.batch_sizes})
        check(max(widths) == budget, f"budget {budget}: ladder reached widths {widths}")
        say(f"elastic[budget {budget}]: widths {widths} | losses {log.losses} | "
            f"{time.perf_counter() - t0:.2f} s, {_compile_s[0] - c0:.2f} s compiling")
        runs[budget] = (list(log.losses),
                        [np.asarray(x) for x in jax.tree.leaves(run["state"].params)])
        del run, trainer
        gc.collect()
    (wide_l, wide_p), (one_l, one_p) = runs[chips], runs[1]
    loss_diff = max(abs(a - b) for a, b in zip(wide_l, one_l))
    param_diff = max(float(np.max(np.abs(a.astype(np.float64) - b))) for a, b in zip(wide_p, one_p))
    same = wide_l == one_l and all(np.array_equal(a, b) for a, b in zip(wide_p, one_p))
    # bit-identity is reported, not checked: it holds on the CPU host (the
    # tests pin it there), while on a TPU the width-1 and width-W programs
    # round each microbatch differently. A gross error still fails here.
    say(f"elastic: width {chips} vs width 1 bit-identical: {same} "
        f"(max loss diff {loss_diff!r}, max param diff {param_diff!r})")
    check(bool(np.allclose(wide_l, one_l, rtol=1e-3, atol=0)),
          f"width {chips} and width 1 losses differ beyond rtol 1e-3")


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the elastic data-parallel phase on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devs = device_phase(args.chips)
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        say(f"compile cache: {enable_compile_cache()}")
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        if args.chips == 1:
            for name, phase in (("kernels", kernel_phase), ("serve", serve_phase),
                                ("train", train_phase)):
                t0 = time.perf_counter()
                phase()
                say(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s")
        else:
            t0 = time.perf_counter()
            elastic_phase(args.chips)
            say(f"phase elastic: ok in {time.perf_counter() - t0:.1f} s")
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"compile seconds (backend compile, this process): {_compile_s[0]:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
