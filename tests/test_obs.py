"""Observability subsystem: tracer/metrics unit behaviour, percentile
consistency with the nearest-rank formula, scheduler
lifecycle timestamps, and the determinism guarantees — tracing must not
change a single token, loss, or compiled executable."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import SanitizerError, audit_tracer
from repro.configs import get_config
from repro.core import SEBS, SEBSTrainer
from repro.data import DataPipeline, TokenDataset
from repro.models import build_model
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    NULL_METRICS,
    nearest_rank,
    time_buckets,
)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.optim import make_optimizer
from repro.serve import DisaggregatedEngine, PagedContinuousBatchingEngine
from repro.serve.scheduler import DONE, RequestScheduler
from repro.train.state import TrainState

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic monotonic counter for the injected-clock seam."""

    def __init__(self, start: float = 100.0, step: float = 0.5):
        self.t = start
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def _setup(arch="qwen2.5-3b", key=0):
    cfg = get_config(arch, "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(key))
    return cfg, model, params


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


def test_ring_buffer_drops_oldest_and_counts_honestly():
    tr = Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert tr.events_total == 10
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr.events) == 0 and tr.events_total == 0 and tr.dropped == 0


def test_disabled_tracer_is_a_true_noop():
    tr = Tracer(enabled=False, clock=FakeClock())
    with tr.span("x", a=1):
        tr.instant("i")
        tr.counter("c", v=1.0)
    tr.complete("y", 0.0, 1.0)
    tr.begin_request(0)
    tr.mark_request(0, "admit")
    tr.end_request(0)
    assert tr.events_total == 0 and len(tr.events) == 0
    assert tr.depth == 0 and tr.open_requests == 0
    # the disabled span is one shared instance — zero per-call allocation
    assert tr.span("a") is tr.span("b") is NULL_TRACER.span("c")
    audit_tracer(tr)  # the sanitizer contract the engines enforce at run end


def test_span_stack_depth_and_balance():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer"):
        assert tr.depth == 1
        with tr.span("inner"):
            assert tr.depth == 2
    assert tr.depth == 0
    tr.assert_balanced()
    audit_tracer(tr)
    # an unclosed span is exactly what the audit exists to catch
    leaked = tr.span("leak").__enter__()
    assert tr.depth == 1
    with pytest.raises(AssertionError):
        tr.assert_balanced()
    with pytest.raises(SanitizerError):
        audit_tracer(tr)
    leaked.__exit__(None, None, None)
    # spans record innermost-first (closed first), durations are clock floats
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "outer", "leak"]
    assert all(e["dur"] > 0 for e in tr.events)


def test_audit_tracer_flags_disabled_tracer_that_recorded():
    tr = Tracer(enabled=False)
    tr._emit({"ph": "i", "name": "smuggled", "ts": 0.0})  # bypass the gate
    with pytest.raises(SanitizerError):
        audit_tracer(tr, where="(test)")


def test_chrome_export_structure():
    clock = FakeClock(start=0.0, step=0.25)
    tr = Tracer(clock=clock)
    with tr.span("tick", width=2):
        pass
    tr.instant("sync")
    tr.counter("pool", used=3.0, capacity=8.0)
    tr.begin_request(7, prompt_len=4, tag="t")
    tr.mark_request(7, "admit")
    tr.end_request(7, tokens=5)
    out = tr.to_chrome()
    assert set(out) == {"traceEvents", "displayTimeUnit"}
    evs = out["traceEvents"]
    assert [e["ph"] for e in evs] == ["X", "i", "C", "b", "n", "e"]
    x, i, c, b, n, e = evs
    # seconds -> microseconds; the span covered one 0.25 s clock step
    assert x["ts"] == pytest.approx(0.25 * 1e6)
    assert x["dur"] == pytest.approx(0.25 * 1e6)
    assert x["args"] == {"width": 2}
    assert i["s"] == "t"
    assert c["args"] == {"used": 3.0, "capacity": 8.0}
    for ev in (b, n, e):
        assert ev["cat"] == "request" and ev["id"] == 7
    assert all("pid" in ev and "tid" in ev for ev in evs)
    json.dumps(out)  # serializable as-is


def test_export_roundtrips_through_trace_view(tmp_path):
    tr = Tracer(clock=FakeClock(start=0.0, step=0.001))
    for i in range(5):
        with tr.span("tick", i=i):
            pass
    tr.begin_request(0)
    tr.mark_request(0, "admit")
    tr.mark_request(0, "prefill_done")
    tr.mark_request(0, "first_token")
    tr.end_request(0)
    chrome = tmp_path / "t.json"
    tr.dump_chrome(str(chrome))
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import trace_view
    finally:
        sys.path.pop(0)
    ev_c = trace_view.load_events(str(chrome))
    assert len(ev_c) == tr.events_total
    # normalized back to seconds, the tracer's own events (chrome rounds to ns)
    for a, b in zip(ev_c, tr.events):
        assert a["ph"] == b["ph"] and a["name"] == b["name"]
        assert a["ts"] == pytest.approx(b["ts"], abs=1e-9)
    summary = trace_view.summarize(ev_c)
    assert summary["spans"]["tick"]["count"] == 5
    phases = summary["request_classes"][""]
    assert phases["total_s"]["count"] == 1
    for name in ("queue_s", "prefill_s", "ttft_s", "decode_s"):
        assert phases[name]["count"] == 1


def test_fake_clock_makes_traces_bit_reproducible():
    def run():
        tr = Tracer(clock=FakeClock(start=10.0, step=0.125))
        for i in range(3):
            with tr.span("u", i=i):
                tr.counter("q", depth=float(i))
        tr.begin_request(0, tag="r")
        tr.end_request(0)
        return json.dumps(tr.to_chrome(), sort_keys=True)

    assert run() == run()


# ---------------------------------------------------------------------------
# metrics unit behaviour + percentile consistency
# ---------------------------------------------------------------------------


def test_nearest_rank_matches_benchmark_formula():
    """nearest_rank is bit-identical to the nearest-rank formula
    sorted(x)[ceil(q/100 * n) - 1], so tracer-derived percentiles need no
    hand-rolled math."""
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        xs = rng.uniform(1e-4, 2.0, n).tolist()
        for q in (0.0, 50.0, 90.0, 99.0, 100.0):
            arr = np.sort(np.asarray(xs, dtype=np.float64))
            rank = int(np.ceil(q / 100.0 * arr.size))
            assert nearest_rank(xs, q) == float(arr[max(rank, 1) - 1])
    assert np.isnan(nearest_rank([], 50))


def test_histogram_bucket_semantics():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for x in (0.5, 1.0, 3.0, 3.5):
        h.observe(x)
    assert h.counts == [2, 0, 2] and h.overflow == 0
    assert h.percentile(50) == 1.0  # rank 2 lands in the first bucket
    assert h.percentile(99) == 4.0
    h.observe(100.0)  # overflow: percentile falls back to the exact max
    assert h.overflow == 1
    assert h.percentile(100) == 100.0
    assert h.count == 5 and h.min == 0.5 and h.max == 100.0
    assert h.mean == pytest.approx((0.5 + 1.0 + 3.0 + 3.5 + 100.0) / 5)
    assert np.isnan(Histogram().percentile(50))
    # default layout resolves decode ticks (ms) and updates (s) alike
    bounds = time_buckets()
    assert bounds[0] < 2e-6 and bounds[-1] == 64.0


def test_histogram_percentile_consistent_with_nearest_rank():
    """Bucketed percentiles answer at bucket resolution: the reported value
    is the upper bound of the bucket holding the exact nearest-rank sample
    (never a smaller bucket, never more than one geometric step above)."""
    rng = np.random.default_rng(1)
    xs = rng.uniform(2e-5, 8.0, 200).tolist()
    h = Histogram()
    for x in xs:
        h.observe(x)
    for q in (50.0, 90.0, 99.0):
        exact = nearest_rank(xs, q)
        bucketed = h.percentile(q)
        assert bucketed >= exact  # upper bound of the containing bucket
        assert bucketed <= exact * 2.0  # geometric (power-of-two) resolution


def test_registry_labels_and_snapshot_determinism():
    reg = MetricsRegistry()
    a = reg.counter("serve.tokens", labels={"engine": "paged", "load": 4})
    b = reg.counter("serve.tokens", labels={"load": 4, "engine": "paged"})
    assert a is b  # label order never splits a series
    a.inc(16)
    reg.gauge("pool.used").set(3)
    reg.histogram("tick", labels={"stage": 0}).observe(0.01)
    assert len(reg) == 3
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["serve.tokens{engine=paged,load=4}"]["value"] == 16.0
    with pytest.raises(AssertionError):
        reg.gauge("serve.tokens", labels={"engine": "paged", "load": 4})
    with pytest.raises(AssertionError):
        a.inc(-1)


def test_disabled_registry_hands_out_shared_noops():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x")
    c.inc(5)
    reg.gauge("y").set(1.0)
    reg.histogram("z").observe(0.5)
    assert c is NULL_METRICS.counter("anything")
    assert len(reg) == 0 and reg.snapshot() == {}


def test_tracer_durations_feed_nearest_rank():
    """The benchmark path: percentiles over tracer span durations equal the
    hand-rolled formula on the same floats — on a fake clock the whole
    chain is deterministic end to end."""
    clock = FakeClock(start=0.0, step=0.01)
    tr = Tracer(clock=clock)
    for _ in range(9):
        t0 = tr.clock()
        t1 = tr.clock()
        tr.complete("serve.decode_tick", t0, t1)
    durs = tr.durations("serve.decode_tick")
    assert len(durs) == 9
    assert all(d == pytest.approx(0.01) for d in durs)
    assert nearest_rank(durs, 50) == sorted(durs)[int(np.ceil(0.5 * 9)) - 1]


# ---------------------------------------------------------------------------
# scheduler lifecycle timestamps
# ---------------------------------------------------------------------------


def test_scheduler_lifecycle_stamps_and_phases():
    clock = FakeClock(start=0.0, step=1.0)
    tr = Tracer(clock=clock)
    sched = RequestScheduler(clock=clock, tracer=tr)
    rid = sched.submit(np.array([1, 2, 3]), max_new_tokens=2, tag="interactive")
    req = sched.requests[rid]
    assert req.t_submit > 0.0
    # nothing else stamped yet: every phase is NaN, never a bogus number
    for value in (req.queue_s, req.prefill_s, req.ttft_s, req.decode_s, req.latency):
        assert np.isnan(value)
    popped = sched.pop_waiting()
    assert popped is req and req.t_admit > req.t_submit
    assert req.queue_s == req.t_admit - req.t_submit
    sched.prefill_done(req)
    sched.prefill_done(req)  # idempotent: first stamp wins
    t_pf = req.t_prefill_done
    assert t_pf > req.t_admit and req.prefill_s == t_pf - req.t_admit
    sched.first_token(req)
    sched.first_token(req)
    assert req.t_first_token > t_pf
    assert req.ttft_s == req.t_first_token - req.t_submit
    assert np.isnan(req.decode_s) and np.isnan(req.latency)  # still RUNNING
    sched.finish(req)
    assert req.state == DONE and req.t_finish > req.t_first_token
    assert req.latency == req.t_finish - req.t_submit
    assert req.decode_s == req.t_finish - req.t_first_token
    # the tracer saw the same lifecycle at the same timestamps
    kinds = [(e["ph"], e["name"]) for e in tr.events]
    assert kinds == [
        ("b", "request"), ("n", "admit"), ("n", "prefill_done"),
        ("n", "first_token"), ("e", "request"),
    ]
    assert [e["ts"] for e in tr.events] == [
        req.t_submit, req.t_admit, req.t_prefill_done, req.t_first_token,
        req.t_finish,
    ]
    assert tr.open_requests == 0


def test_requeue_resets_admit_stamp():
    clock = FakeClock()
    sched = RequestScheduler(clock=clock)
    rid = sched.submit(np.array([1]), max_new_tokens=1)
    req = sched.pop_waiting()
    assert req.t_admit > 0.0
    sched.requeue(req)
    assert req.t_admit == 0.0 and np.isnan(req.queue_s)
    again = sched.pop_waiting()
    assert again is req and sched.requests[rid].t_admit > 0.0
    # queue_s now covers the WHOLE wait including the failed admission
    assert req.queue_s == req.t_admit - req.t_submit


# ---------------------------------------------------------------------------
# determinism: tracing changes no tokens, no losses, no executables
# ---------------------------------------------------------------------------


def _paged(model, params, **obs):
    return PagedContinuousBatchingEngine(
        model, params, cache_len=64, max_slots=2, page_size=4,
        prefill_chunks=(4,), **obs,
    )


def test_paged_tokens_identical_with_tracing_on():
    cfg, model, params = _setup()
    prompts = [
        np.asarray(p, np.int32)
        for p in np.random.default_rng(3).integers(0, cfg.vocab_size, (4, 7))
    ]

    def run(**obs):
        eng = _paged(model, params, **obs)
        ids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        out = eng.run()
        return [out[r] for r in ids], eng

    ref, eng_off = run()
    tracer, metrics = Tracer(), MetricsRegistry()
    traced, eng_on = run(tracer=tracer, metrics=metrics)
    for a, b in zip(ref, traced):
        np.testing.assert_array_equal(a, b)
    # compile-bucket neutrality: tracing added zero executables
    assert eng_on.decode_compiles == eng_off.decode_compiles
    assert eng_on.prefill_compiles == eng_off.prefill_compiles
    # the trace is real: ticks, balanced spans, every request closed
    assert len(tracer.durations("serve.decode_tick")) > 0
    assert tracer.depth == 0 and tracer.open_requests == 0
    # tick durations in the trace ARE the stats floats (shared clock read)
    assert tracer.durations("serve.decode_tick") == list(
        eng_on.stats["decode_tick_s"]
    )
    assert metrics.counter("serve.decoded_tokens").value > 0
    # the untraced engine ran on the shared no-op tracer
    assert eng_off.tracer is NULL_TRACER and eng_off.tracer.events_total == 0


def test_disagg_tokens_identical_with_tracing_on():
    """Degraded 1-device disaggregation: tracing must not perturb the
    cross-pool seam either, and the streamed-byte accounting agrees
    between stats and the metrics registry."""
    cfg, model, params = _setup()
    prompts = [
        np.asarray(p, np.int32)
        for p in np.random.default_rng(4).integers(0, cfg.vocab_size, (3, 9))
    ]

    def run(**obs):
        eng = DisaggregatedEngine(
            model, params, cache_len=64, max_slots=2, page_size=4,
            prefill_chunks=(4,), prefill_slots=2, **obs,
        )
        ids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        out = eng.run()
        return [out[r] for r in ids], eng

    ref, _ = run()
    tracer, metrics = Tracer(), MetricsRegistry()
    traced, eng = run(tracer=tracer, metrics=metrics)
    for a, b in zip(ref, traced):
        np.testing.assert_array_equal(a, b)
    assert eng.stats["seam_bytes"] > 0
    assert metrics.counter("serve.seam_bytes").value == eng.stats["seam_bytes"]
    assert len(tracer.durations("serve.stream")) == eng.stats["transfers"]
    assert tracer.depth == 0 and tracer.open_requests == 0


class _StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs entries/exits."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.log.append(("enter", self.name, self.kwargs))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, None))


def test_annotated_spans_enter_and_exit_in_nesting_order(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _StubAnnotation)
    monkeypatch.setattr(_StubAnnotation, "log", [])
    tr = Tracer(clock=FakeClock(), jax_profiler=True)
    with tr.span("outer", update=3, stage=1) as sp:
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            tr.instant("not-annotated")
        sp.set_arg("loss", 2.5)
    tr.complete("host-only", 0.0, 1.0)  # timed after the fact: no annotation
    assert _StubAnnotation.log == [
        ("enter", "outer", {"update": 3, "stage": 1}),
        ("enter", "inner", {}), ("exit", "inner", None),
        ("enter", "inner2", {}), ("exit", "inner2", None),
        ("exit", "outer", None),
    ]
    outer = [e for e in tr.events if e["name"] == "outer"][0]
    assert outer["args"] == {"update": 3, "stage": 1, "loss": 2.5}
    assert tr.depth == 0
    # an arg set after the span closed reaches its recorded event, also one
    # recorded without args
    sp.set_arg("grad", 0.5)
    assert outer["args"] == {"update": 3, "stage": 1, "loss": 2.5, "grad": 0.5}
    with tr.span("bare") as bare:
        pass
    bare.set_arg("loss", 1.5)
    assert [e for e in tr.events if e["name"] == "bare"][0]["args"] == {"loss": 1.5}
    # the disabled span takes set_arg too, and still records nothing
    NULL_TRACER.span("x").set_arg("loss", 1.0)
    assert NULL_TRACER.events_total == 0


def _tiny_trainer(**obs):
    """The smoke qwen2.5-3b under SEBS in accumulate mode (microbatch 2,
    so stage 1 accumulates and reads the noise-scale norms)."""
    cfg, model, params = _setup()
    optimizer = make_optimizer("psgd", gamma=1e4)
    sched = SEBS(b1=2, C1=6, rho=2.0, num_stages=2, eta=0.05)
    ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=16, seed=0)
    trainer = SEBSTrainer(model, optimizer, sched, DataPipeline(ds),
                          microbatch=2, mode="accumulate", **obs)
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    return trainer, state


def test_trainer_phase_spans_nest_per_update(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _StubAnnotation)
    monkeypatch.setattr(_StubAnnotation, "log", [])
    tracer, metrics = Tracer(clock=FakeClock(), jax_profiler=True), MetricsRegistry()
    trainer, state = _tiny_trainer(tracer=tracer, metrics=metrics)
    _, log = trainer.run(state, log_every=1)
    n = len(log.steps)
    assert n == 6 and log.stages == [0, 0, 0, 1, 1, 1]
    assert tracer.depth == 0
    spans = [e for e in tracer.events if e["ph"] == "X"]
    # one update in flight: update k's train.update holds its data and
    # dispatch and the wait on update k-1 (nothing to read at k = 1; the
    # last update is drained, so its wait reads k-1 and k), and one
    # train.after per update read follows it; recorded as each closes
    reads = [[]] + [[k] for k in range(1, n - 1)] + [[n - 1, n]]
    expected = []
    for r in reads:
        expected += (["train.data", "train.dispatch"] + ["train.wait"] * bool(r)
                     + ["train.update"] + ["train.after"] * len(r))
    assert [e["name"] for e in spans] == expected
    at = 0
    for i, r in enumerate(reads):
        inner = 2 + bool(r)
        group = spans[at: at + inner + 1 + len(r)]
        at += len(group)
        phases, update, afters = group[:inner], group[inner], group[inner + 1:]
        end = update["ts"] + update["dur"]
        for child in phases:
            assert update["ts"] <= child["ts"] and child["ts"] + child["dur"] <= end
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        if r:
            assert phases[-1]["args"] == {"update": r[-1]}
        assert [a["args"] for a in afters] == [{"update": k} for k in r]
        assert all(a["ts"] >= end for a in afters)
        # the loss lands on the span of the update that produced it
        assert update["args"] == {"update": i + 1, "stage": log.stages[i],
                                  "batch": log.batch_sizes[i], "loss": log.losses[i]}
    assert metrics.counter("train.updates_overlapped").value == n - 1
    # every annotation closed in nesting order, update and stage on the update's
    stack = []
    for kind, name, kwargs in _StubAnnotation.log:
        if kind == "enter":
            stack.append(name)
            if name == "train.update":
                assert {"update", "stage"} <= set(kwargs)
        else:
            assert stack.pop() == name
    assert not stack
    assert sum(1 for k, name, _ in _StubAnnotation.log
               if k == "enter" and name == "train.update") == n
    # a trainer that does not communicate emits no comm gauges or track
    assert not any(e["name"] == "train.comm" for e in tracer.events)
    assert not any(k.startswith(("train.comm_bytes", "train.sync_events"))
                   for k in metrics.snapshot())


def test_trainer_disabled_tracer_records_nothing_and_losses_match():
    trainer, state = _tiny_trainer(tracer=Tracer(enabled=False))
    _, off = trainer.run(state, log_every=1)
    assert trainer.tracer.events_total == 0 and trainer.tracer.depth == 0
    trainer, state = _tiny_trainer(tracer=Tracer(clock=FakeClock()))
    _, on = trainer.run(state, log_every=1)
    assert trainer.tracer.events_total > 0
    assert on.losses == off.losses  # bit-identical, not approx
    assert on.noise_scales[-1] == off.noise_scales[-1]


def test_train_step_ops_carry_layer_scopes():
    """The step's parts are named scopes: each appears in the compiled
    program's op_name metadata, which is what a device profile shows."""
    import re

    from repro.train.step import build_train_step

    cfg, model, params = _setup()
    optimizer = make_optimizer("psgd", gamma=1e4)
    state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
    step = build_train_step(model, optimizer, None, accum_steps=2)
    batch = {"tokens": jnp.zeros((2, 2, 16), jnp.int32)}
    text = step.lower(state, batch, jnp.float32(0.1), jnp.int32(0)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed", "attention", "mlp", "final_norm", "head",
                  "grad_accumulate", "optimizer"):
        pattern = re.compile(r"(^|[/(])" + scope + r"([/)]|$)")
        assert any(pattern.search(n) for n in op_names), scope


def test_trainer_losses_bit_identical_with_metrics_on():
    sched = SEBS(b1=4, C1=24, rho=2.0, num_stages=2, eta=0.05)

    def run(**obs):
        cfg, model, params = _setup()
        optimizer = make_optimizer("psgd", gamma=1e4)
        ds = TokenDataset(vocab_size=cfg.vocab_size, seq_len=16, seed=0)
        trainer = SEBSTrainer(
            model, optimizer, sched, DataPipeline(ds),
            mesh=None, microbatch=None, mode="reshape", **obs,
        )
        state = TrainState(params, optimizer.init(params), jnp.zeros((), jnp.int32))
        _, log = trainer.run(state, log_every=1)
        return log

    ref = run()
    tracer, metrics = Tracer(), MetricsRegistry()
    obs_log = run(tracer=tracer, metrics=metrics)
    assert obs_log.losses == ref.losses  # bit-identical, not approx
    assert obs_log.batch_sizes == ref.batch_sizes
    # one train.update span per optimizer update, args carry the schedule
    updates = [e for e in tracer.events
               if e["ph"] == "X" and e["name"] == "train.update"]
    assert len(updates) == len(obs_log.steps)
    assert [e["args"]["batch"] for e in updates] == obs_log.batch_sizes
    assert [e["args"]["loss"] for e in updates] == obs_log.losses
    assert metrics.counter("train.updates").value == len(obs_log.steps)
    assert metrics.counter("train.samples").value == obs_log.samples[-1]
    # per-stage update-time histograms saw every update exactly once
    per_stage = [
        metrics.histogram("train.update_s", labels={"stage": s}).count
        for s in sorted(set(obs_log.stages))
    ]
    assert sum(per_stage) == len(obs_log.steps)
    assert tracer.depth == 0


# ---------------------------------------------------------------------------
# trace_view CLI (the artifact gate CI runs)
# ---------------------------------------------------------------------------


def _trace_view(*argv):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_view.py"), *argv],
        capture_output=True, text=True, cwd=str(REPO),
    )


def test_trace_view_cli_accepts_valid_and_rejects_malformed(tmp_path):
    tr = Tracer(clock=FakeClock(start=0.0, step=0.002))
    for i in range(4):
        with tr.span("serve.decode_tick", width=1):
            pass
    tr.begin_request(0, tag="batch")
    tr.mark_request(0, "admit")
    tr.mark_request(0, "first_token")
    tr.end_request(0)
    good = tmp_path / "good.json"
    tr.dump_chrome(str(good))
    proc = _trace_view(str(good))
    assert proc.returncode == 0, proc.stderr
    assert "serve.decode_tick" in proc.stdout and "batch" in proc.stdout
    proc = _trace_view("--json", str(good))
    assert proc.returncode == 0
    summary = json.loads(proc.stdout)
    assert summary["spans"]["serve.decode_tick"]["count"] == 4

    cases = {
        "not_json.json": "this is not json {",
        "no_events.json": json.dumps({"foo": 1}),
        "span_no_dur.json": json.dumps(
            {"traceEvents": [{"ph": "X", "name": "t", "ts": 1.0}]}
        ),
        "async_no_id.json": json.dumps(
            {"traceEvents": [{"ph": "b", "name": "request", "ts": 1.0}]}
        ),
        "unknown_phase.json": json.dumps(
            {"traceEvents": [{"ph": "Z", "name": "t", "ts": 1.0}]}
        ),
    }
    for fname, text in cases.items():
        bad = tmp_path / fname
        bad.write_text(text)
        proc = _trace_view(str(bad))
        assert proc.returncode == 2, fname
        assert "MALFORMED" in proc.stderr, fname
