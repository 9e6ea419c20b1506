"""``bench/trace_reduce.py``: interval arithmetic, and the reduction of a
small trace recorded on a TPU v5e chip (``data/small_tpu_trace.xplane.pb``:
inside a ``bench.window`` annotation, three rounds of a 1024 x 1024 bf16
matmul program, a 20 ms host sleep named ``host.sleep``, and a reduction
program; skipped until one is recorded), and of a synthetic trace laid out
as the profiler lays out a TPU's, with known busy, collective and idle
times."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

SMALL = Path(__file__).resolve().parent / "data" / "small_tpu_trace.xplane.pb"


def test_union_and_subtract():
    u = tr._union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert tr._length(u) == 4
    assert tr._subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert tr._subtract([(0, 1), (4, 6)], [(0.5, 5)]) == pytest.approx(1.5)
    assert tr._clip([(0, 4), (6, 8)], 1, 7) == [(1, 4), (6, 7)]


@pytest.mark.skipif(not SMALL.exists(), reason="recorded trace not present")
def test_recorded_tpu_trace():
    r = tr.reduce(str(SMALL))
    assert r["devices"] == 1
    # three sleeps of 20 ms lie inside the window, and the chip idles through them
    assert r["window_s"] > 0.06
    assert 0 < r["busy_s"] < r["window_s"] - 0.055
    names = [g[0] for g in r["breakdown"]["idle_gaps"][:3]]
    assert names == ["host.sleep"] * 3
    assert all(g[1] >= 0.019 for g in r["breakdown"]["idle_gaps"][:3])
    counts = sorted(p["count"] for p in r["programs"].values())
    assert counts[-2:] == [3, 3]
    assert r["collective_s"] == 0
    assert r["breakdown"]["device_ops"]


def _synthetic_xspace(path):
    """One TPU plane and one host plane, laid out as the profiler lays out a
    TPU trace; times in microseconds from 0. Inside ``bench.window`` [0,
    100]: program ``jit_a`` (id 1) runs [0, 20], [40, 60]; program ``jit_b``
    (id 2) runs [70, 80]; its all-reduce op [75, 90] overlaps the fusion
    [70, 80] for 5 us and is exposed for 10; the host sleeps in [20, 40]."""
    xp = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    us = 1_000_000  # picoseconds

    def plane(pid, name, lines, names, stat_names=()):
        p = xp.XPlane(id=pid, name=name)
        for i, n in enumerate(names, 1):
            p.event_metadata[i].CopyFrom(xp.XEventMetadata(id=i, name=n))
        for i, n in enumerate(stat_names, 1):
            p.stat_metadata[i].CopyFrom(xp.XStatMetadata(id=i, name=n))
        for lid, (lname, events) in enumerate(lines, 1):
            line = p.lines.add(id=lid, name=lname, timestamp_ns=0)
            for meta, t0, t1, *stats in events:
                ev = line.events.add(metadata_id=meta, offset_ps=t0 * us, duration_ps=(t1 - t0) * us)
                for sid, value in stats:
                    ev.stats.add(metadata_id=sid, int64_value=value)
        return p

    tpu = plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 0, 20, (1, 1)), (1, 40, 60, (1, 1)), (2, 70, 80, (1, 2))]),
        ("XLA Ops", [(3, 0, 20), (3, 40, 60), (4, 70, 80), (5, 75, 90)]),
    ], ["jit_a", "jit_b", "fusion.1", "fusion.2", "all-reduce.3"], ["program_id"])
    host = plane(2, "/host:CPU", [("python", [(1, 0, 100), (2, 20, 40)])],
                 ["bench.window", "host.sleep"])
    Path(path).write_bytes(xp.XSpace(planes=[tpu, host]).SerializeToString())


def test_synthetic_tpu_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    _synthetic_xspace(path)
    r = tr.reduce(str(path))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(60e-6)  # [0,20] + [40,60] + [70,90]
    assert r["collective_s"] == pytest.approx(15e-6)
    assert r["collective_exposed_s"] == pytest.approx(10e-6)
    progs = sorted((p["name"], p["count"], p["seconds"]) for p in r["programs"].values())
    assert [(n, c) for n, c, _ in progs] == [("jit_a", 2), ("jit_b", 1)]
    assert progs[0][2] == pytest.approx(40e-6)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "host.sleep" and gaps[0][1] == pytest.approx(20e-6)
    assert [g[1] for g in gaps] == pytest.approx([20e-6, 10e-6, 10e-6])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(40e-6) and ops["all-reduce.3"] == pytest.approx(15e-6)
