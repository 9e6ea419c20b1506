"""Perf-trajectory harness tests: BENCH schema round-trip, the compare.py
regression gate, benchmarks.run failure propagation, the table_comm
per-epoch accounting fix, and the public serve-engine reset seams."""
import copy
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks/

from benchmarks import _schema, compare
from benchmarks._schema import Record


def _records():
    return [
        Record("serve_tok_per_s", 100.0, "tok/s", direction="higher",
               derived="100.0 tok/s", context={"load": 16}),
        Record("serve_latency_p99", 0.5, "s", direction="lower"),
        Record("comm_sync_events", 60, "count", direction="exact"),
        Record("note_metric", 1.0, "ratio", direction="info"),
    ]


# -- schema ------------------------------------------------------------------


def test_bench_roundtrip(tmp_path):
    # out_root that does not exist yet: write_bench must create it
    path = _schema.write_bench("demo", _records(), str(tmp_path / "nested"),
                               env={"jax_version": "test"})
    assert os.path.basename(path) == "BENCH_demo.json"
    payload = _schema.load_bench(path)
    assert payload["schema_version"] == _schema.SCHEMA_VERSION
    assert payload["module"] == "demo"
    assert payload["env"] == {"jax_version": "test"}
    by_name = {m["name"]: m for m in payload["metrics"]}
    assert by_name["serve_tok_per_s"]["value"] == 100.0
    assert by_name["serve_tok_per_s"]["unit"] == "tok/s"
    assert by_name["serve_tok_per_s"]["direction"] == "higher"
    assert by_name["serve_tok_per_s"]["context"] == {"load": 16}


@pytest.mark.parametrize("mutate", [
    lambda p: p.update(schema_version=99),
    lambda p: p.pop("module"),
    lambda p: p["metrics"][0].pop("unit"),
    lambda p: p["metrics"][0].update(direction="sideways"),
    lambda p: p["metrics"][0].update(value=float("nan")),
    lambda p: p["metrics"].append(dict(p["metrics"][0])),  # duplicate name
])
def test_validate_rejects_malformed(mutate):
    payload = _schema.bench_payload("demo", _records(), env={})
    bad = copy.deepcopy(payload)
    mutate(bad)
    with pytest.raises(ValueError):
        _schema.validate(bad)


def test_record_rejects_bad_direction_and_nonfinite():
    with pytest.raises(ValueError):
        Record("x", 1.0, "s", direction="best")
    with pytest.raises(ValueError):
        Record("x", float("inf"), "s")


# -- compare.py gate ---------------------------------------------------------


def _write_pair(tmp_path, mutate=None):
    base_dir = tmp_path / "base"
    cur_dir = tmp_path / "cur"
    base_dir.mkdir()
    cur_dir.mkdir()
    _schema.write_bench("demo", _records(), str(base_dir), env={})
    payload = _schema.bench_payload("demo", _records(), env={})
    if mutate:
        mutate(payload)
    with open(cur_dir / "BENCH_demo.json", "w") as f:
        json.dump(payload, f)
    return str(base_dir), str(cur_dir)


def _compare(base_dir, cur_dir, *extra):
    return compare.main(
        ["--baseline", base_dir, "--current", cur_dir, *extra]
    )


def test_compare_identical_passes(tmp_path):
    base, cur = _write_pair(tmp_path)
    assert _compare(base, cur) == 0


def test_compare_within_band_passes(tmp_path):
    def wobble(p):  # -10% tok/s: inside the 25% band
        p["metrics"][0]["value"] = 90.0
    base, cur = _write_pair(tmp_path, wobble)
    assert _compare(base, cur) == 0


def test_compare_flags_30pct_throughput_regression(tmp_path):
    def regress(p):
        p["metrics"][0]["value"] = 70.0  # tok/s down 30%
    base, cur = _write_pair(tmp_path, regress)
    assert _compare(base, cur) == 1


def test_compare_improvement_never_gates(tmp_path):
    def improve(p):
        p["metrics"][0]["value"] = 200.0   # higher-is-better doubled
        p["metrics"][1]["value"] = 0.01    # lower-is-better collapsed
    base, cur = _write_pair(tmp_path, improve)
    assert _compare(base, cur) == 0


def test_compare_exact_metric_drift_fails(tmp_path):
    def drift(p):
        p["metrics"][2]["value"] = 61  # sync count is exact accounting
    base, cur = _write_pair(tmp_path, drift)
    assert _compare(base, cur) == 1


def test_compare_info_metric_never_gates(tmp_path):
    def drift(p):
        p["metrics"][3]["value"] = 999.0
    base, cur = _write_pair(tmp_path, drift)
    assert _compare(base, cur) == 0


def test_compare_missing_metric_is_regression(tmp_path):
    def drop(p):
        p["metrics"] = p["metrics"][1:]
    base, cur = _write_pair(tmp_path, drop)
    assert _compare(base, cur) == 1


def test_compare_tolerance_override(tmp_path):
    def regress(p):
        p["metrics"][0]["value"] = 70.0
    base, cur = _write_pair(tmp_path, regress)
    assert _compare(base, cur, "--tolerance", "serve_tok_per_s=0.5") == 0


def test_compare_missing_baseline_module(tmp_path):
    base, cur = _write_pair(tmp_path)
    os.remove(os.path.join(base, "BENCH_demo.json"))
    assert _compare(base, cur) == 1
    assert _compare(base, cur, "--allow-missing-baseline") == 0


# -- benchmarks.run failure propagation + artifact writing -------------------


class _OkModule:
    @staticmethod
    def run():
        return [Record("ok_metric", 1.0, "count", direction="exact")]


class _BadModule:
    @staticmethod
    def run():
        raise RuntimeError("boom")


def test_run_writes_artifacts_and_fails_on_module_error(tmp_path, monkeypatch, capsys):
    from benchmarks import run as bench_run

    monkeypatch.setitem(bench_run.MODULES, "okmod", _OkModule)
    monkeypatch.setitem(bench_run.MODULES, "badmod", _BadModule)
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", "okmod,badmod", "--out-root", str(tmp_path)])
    assert "badmod" in str(exc.value)
    out = capsys.readouterr().out
    assert out.splitlines()[0] == _schema.CSV_HEADER
    assert "FAILED" in out
    # the healthy module's artifact was still written and validates
    payload = _schema.load_bench(str(tmp_path / "BENCH_okmod.json"))
    assert payload["metrics"][0]["name"] == "ok_metric"
    assert not (tmp_path / "BENCH_badmod.json").exists()


def test_run_rejects_unknown_module(tmp_path):
    from benchmarks import run as bench_run

    with pytest.raises(SystemExit):
        bench_run.main(["--only", "nope", "--out-root", str(tmp_path)])


# -- table_comm per-epoch accounting (satellite fix) -------------------------


def test_table_comm_per_epoch_times_epochs_equals_totals():
    from benchmarks.table_comm import EPOCHS, _schedules, account

    for name, schedule in _schedules().items():
        for mode in ("exact", "local"):
            one = account(schedule, mode, grad_bytes=1000, state_bytes=2000)
            many = account(schedule, mode, grad_bytes=1000, state_bytes=2000,
                           epochs=EPOCHS)
            for field in ("updates", "sync_events", "bytes"):
                assert many.total(field) == EPOCHS * one.total(field), (
                    name, mode, field
                )


def test_table_comm_epochs_share_stage_breakdown():
    """Each epoch replays the schedule from stage 0 — the per-stage summary
    scales uniformly, it does not pick up phantom stages."""
    from benchmarks.table_comm import _schedules, account

    sched = _schedules()["sebs"]
    one = account(sched, "exact", grad_bytes=10, state_bytes=20)
    five = account(sched, "exact", grad_bytes=10, state_bytes=20, epochs=5)
    assert set(one.summary()) == set(five.summary())
    for stage, row in one.summary().items():
        for field, val in row.items():
            assert five.summary()[stage][field] == 5 * val


# -- roofline silent-zero fix ------------------------------------------------


def test_roofline_report_fails_loudly_when_empty(tmp_path, monkeypatch):
    from benchmarks import roofline_report

    monkeypatch.setattr(roofline_report, "ROOFLINE_DIR", str(tmp_path / "rf"))
    monkeypatch.setattr(roofline_report, "DRYRUN_DIR", str(tmp_path / "dr"))
    monkeypatch.setattr(roofline_report, "ALLOW_MISSING", False)
    with pytest.raises(FileNotFoundError, match="no roofline artifacts"):
        roofline_report.run(out_dir=str(tmp_path / "out"))


def test_roofline_report_allow_missing_reports_skips(tmp_path, monkeypatch):
    from benchmarks import roofline_report

    monkeypatch.setattr(roofline_report, "ROOFLINE_DIR", str(tmp_path / "rf"))
    monkeypatch.setattr(roofline_report, "DRYRUN_DIR", str(tmp_path / "dr"))
    monkeypatch.setattr(roofline_report, "ALLOW_MISSING", True)
    records = roofline_report.run(out_dir=str(tmp_path / "out"))
    by_name = {r.name: r for r in records}
    assert by_name["roofline_combos_analyzed"].value == 0
    skipped = by_name["roofline_combos_skipped"]
    assert skipped.value > 0
    assert skipped.context["skipped"]  # every missing combo enumerated


# -- serve reset seams (satellite fix) ---------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("qwen2.5-3b", "smoke")
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _exercise(engine, cfg, n=3):
    rng = np.random.default_rng(0)
    for _ in range(n):
        engine.submit(rng.integers(0, cfg.vocab_size, 6), max_new_tokens=4)
    return engine.run()


@pytest.mark.parametrize("kind", ["disagg", "paged"])
def test_reset_restores_every_stats_key(smoke_model, kind):
    from repro.serve import DisaggregatedEngine, PagedContinuousBatchingEngine

    cfg, model, params = smoke_model
    cls = DisaggregatedEngine if kind == "disagg" else PagedContinuousBatchingEngine
    make = lambda: cls(  # noqa: E731
        model, params, cache_len=32, max_slots=2, b1=1, rho=2.0, patience=1,
        page_size=4, prefill_chunks=(4,),
    )
    engine = make()
    _exercise(engine, cfg)
    assert engine.stats["ticks"] > 0 and engine.stats["decoded_tokens"] > 0
    stats_ref = engine.stats  # callers may hold the dict; reset is in place
    engine.admission.reset()
    engine.reset_stats()
    assert engine.stats is stats_ref
    fresh = make()
    # every key restored, none dropped (the old dict-surgery reset in the
    # benchmark missed the paged engine's extra counters)
    assert set(engine.stats) == set(fresh.stats)
    for key, val in fresh.stats.items():
        assert list(engine.stats[key]) == list(val) if key == "stage_history" \
            else engine.stats[key] == val, key
    assert engine.admission.stage == 0 and engine.admission._pressure == 0
    # every pool's monotonic peak rebased to live usage for the next window
    for worker in engine.workers:
        assert worker.pool.peak_used == worker.pool.used


def test_reset_engine_still_serves_identically(smoke_model):
    """After reset the engine must produce the same tokens as a fresh one
    (reset touches bookkeeping only, never device state semantics)."""
    from repro.serve import PagedContinuousBatchingEngine

    cfg, model, params = smoke_model
    make = lambda: PagedContinuousBatchingEngine(  # noqa: E731
        model, params, cache_len=32, max_slots=2, b1=1, rho=2.0, patience=1, seed=7,
        page_size=4, prefill_chunks=(4,),
    )
    warm = make()
    _exercise(warm, cfg)
    warm.admission.reset()
    warm.reset_stats()
    warm._rng = __import__("jax").random.key(7)  # align sampling streams
    out_warm = _exercise(warm, cfg)
    out_fresh = _exercise(make(), cfg)
    assert sorted(np.asarray(v).tolist() for v in out_warm.values()) == \
        sorted(np.asarray(v).tolist() for v in out_fresh.values())
