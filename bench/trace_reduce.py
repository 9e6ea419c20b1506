"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads with ``jax.profiler.ProfileData`` alone. Device planes are the
``/device:TPU:<n>`` planes; on each, the ``XLA Ops`` line gives the
operations and the ``XLA Modules`` line one event per program execution.
The window is the host event named ``bench.window`` (the harness's own
``TraceAnnotation`` around the measured period), or the span of all device
events where it is missing.

Results, per device and averaged over devices:

- busy seconds: the union of operation intervals inside the window;
- device time per program: module events grouped by (name, program id);
- collective seconds, and the part of them during which no other
  operation runs on that device (exposed);
- the breakdown: the operations that took most time, and the longest idle
  gaps of device 0, each named by the innermost host event open at the
  gap's middle.
"""
from __future__ import annotations

import glob
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
                        r"allreduce|allgather|reducescatter|send|recv", re.I)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_NOISE = re.compile(r"^ThreadpoolListener|^SlinkyThreadPool|^end: ")

Interval = Tuple[float, float]


def start(out_dir: Path) -> None:
    """Start the JAX profiler into ``out_dir``, with its Python tracer off."""
    import jax

    out_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)


def collect(out_dir: Path) -> Dict[str, object]:
    """Reduce the trace written under ``out_dir``, then delete it."""
    try:
        return reduce(find_xplane(str(out_dir)))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _length(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _subtract(iv: List[Interval], cover: List[Interval]) -> float:
    """Length of ``iv`` (a union) not covered by ``cover`` (a union)."""
    total, j = 0.0, 0
    for a, b in iv:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            ca, cb = cover[k]
            if ca > cur:
                total += ca - cur
            cur = max(cur, cb)
            if cur >= b:
                break
            k += 1
        if cur < b:
            total += b - cur
    return total


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # an event whose stats cannot be read has none
        return {}


def load_events(path: str):
    """(device events, host events) as plain tuples, times in seconds.
    Device: {ordinal: {"ops": [(t0, t1, name, stats)], "modules": [...]}}.
    Host: [(t0, t1, name)]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, list]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind is None:
                    continue
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    st = _stats(ev) if kind == "modules" else None
                    dev[kind].append((t0, t0 + ev.duration_ns * 1e-9, ev.name, st))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_NOISE.match(ev.name):
                        continue
                    t0 = ev.start_ns * 1e-9
                    host.append((t0, t0 + ev.duration_ns * 1e-9, ev.name))
    return devices, host


def _window(devices, host) -> Interval:
    marks = [(a, b) for a, b, n in host if n == WINDOW]
    if marks:
        return min(a for a, _ in marks), max(b for _, b in marks)
    times = [t for d in devices.values() for kind in d.values() for ev in kind for t in ev[:2]]
    return (min(times), max(times)) if times else (0.0, 0.0)


def _host_name(host, t: float) -> str:
    best = None
    for a, b, n in host:
        if a <= t <= b and n != WINDOW and (best is None or b - a < best[1] - best[0]):
            best = (a, b, n)
    return best[2] if best else "no host event"


def reduce(path: str, top: int = 10) -> Dict[str, object]:
    devices, host = load_events(path)
    w0, w1 = _window(devices, host)
    window_s = w1 - w0
    per_device = {}
    op_time: Dict[str, float] = defaultdict(float)
    programs: Dict[str, Dict[str, float]] = {}
    gaps: List[Interval] = []
    for ordinal, dev in sorted(devices.items()):
        ops = dev["ops"] or dev["modules"]
        busy = _union(_clip([(a, b) for a, b, _, _ in ops], w0, w1))
        coll = _union(_clip([(a, b) for a, b, n, _ in ops if COLLECTIVE.search(n)], w0, w1))
        compute = _union(_clip([(a, b) for a, b, n, _ in ops if not COLLECTIVE.search(n)], w0, w1))
        per_device[ordinal] = {
            "busy_s": _length(busy),
            "collective_s": _length(coll),
            "collective_exposed_s": _subtract(coll, compute),
        }
        for a, b, name, _ in ops:
            if a >= w0 and b <= w1:
                op_time[name] += b - a
        for a, b, name, st in dev["modules"]:
            if a >= w0 and b <= w1:
                key = f"{name}#{st.get('program_id', '')}"
                p = programs.setdefault(key, {"name": name, "seconds": 0.0, "count": 0,
                                              "devices": set()})
                p["seconds"] += b - a
                p["count"] += 1
                p["devices"].add(ordinal)
        if ordinal == min(devices):
            edges = [w0] + [t for iv in busy for t in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b))
    n = max(len(per_device), 1)
    for p in programs.values():
        p["devices"] = len(p["devices"])
    return {
        "window_s": window_s,
        "devices": len(per_device),
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per_device.values()) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"] for d in per_device.values()) / n,
        "per_device": per_device,
        "programs": programs,
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in op_time.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": [[_host_name(host, (a + b) / 2), b - a]
                          for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
        },
    }
