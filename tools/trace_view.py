#!/usr/bin/env python
"""trace_view — validate and summarize an obs trace.

    PYTHONPATH=src python tools/trace_view.py /tmp/serve_trace.json
    PYTHONPATH=src python tools/trace_view.py --json /tmp/train_trace.json

Reads the export of :class:`repro.obs.trace.Tracer`: a Chrome
``trace_event`` JSON object (``{"traceEvents": [...]}``, timestamps in µs —
the Perfetto-loadable artifact). The trace is validated structurally first — a
malformed file (bad JSON, events missing required fields, a complete span
without ``dur``, an async event without ``id``) exits nonzero, which is
what the CI obs-smoke job gates on.

Summaries, all percentiles nearest-rank (:func:`repro.obs.metrics.nearest_rank`):

- per request class (the ``tag`` submitted with each request): per-phase
  p50/p99 — queue wait, prefill, time-to-first-token, decode, total;
- per span name: count / total / p50 / p99 (decode ticks, seam streams,
  train updates, reshards);
- per train stage (from ``train.update`` span args): update-time p50/p99 —
  the per-stage iteration-complexity view the SEBS accounting plots need.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "src"))

from repro.obs.metrics import nearest_rank  # noqa: E402
from repro.obs.trace import PHASES  # noqa: E402


class TraceError(ValueError):
    """The file is not a structurally valid obs trace."""


_ASYNC = ("b", "n", "e")
_KNOWN = ("X", "i", "C") + _ASYNC


def load_events(path: str) -> List[Dict[str, Any]]:
    """Parse a chrome trace into events, timestamps in SECONDS."""
    try:
        text = Path(path).read_text()
    except OSError as e:
        raise TraceError(f"cannot read {path}: {e}") from e
    if not text.strip():
        raise TraceError(f"{path} is empty")
    try:
        obj = json.loads(text)
    except json.JSONDecodeError as e:
        raise TraceError(f"{path}: not chrome trace JSON ({e})") from e
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise TraceError(f"{path}: chrome trace must be an object with 'traceEvents'")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise TraceError(f"{path}: 'traceEvents' must be a list")
    return _validated(events, 1e-6)


def _validated(events: List[Any], scale: float) -> List[Dict[str, Any]]:
    out = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise TraceError(f"event {i} is not an object")
        for field in ("ph", "name", "ts"):
            if field not in ev:
                raise TraceError(f"event {i} ({ev}) missing required field {field!r}")
        if ev["ph"] not in _KNOWN:
            raise TraceError(f"event {i}: unknown phase {ev['ph']!r}")
        if not isinstance(ev["ts"], (int, float)):
            raise TraceError(f"event {i}: non-numeric ts {ev['ts']!r}")
        if ev["ph"] == "X":
            if "dur" not in ev or not isinstance(ev["dur"], (int, float)):
                raise TraceError(f"event {i}: complete span without numeric 'dur'")
        if ev["ph"] in _ASYNC and "id" not in ev:
            raise TraceError(f"event {i}: async event without 'id'")
        ev = dict(ev)
        ev["ts"] = ev["ts"] * scale
        if "dur" in ev:
            ev["dur"] = ev["dur"] * scale
        out.append(ev)
    return out


def _pcts(xs: List[float]) -> Dict[str, float]:
    return {
        "count": len(xs),
        "total_s": sum(xs),
        "p50_s": nearest_rank(xs, 50),
        "p99_s": nearest_rank(xs, 99),
    }


def request_phases(events: List[Dict[str, Any]]) -> Dict[str, Dict[str, List[float]]]:
    """Reconstruct per-request lifecycles from the async b/n/e events and
    bucket phase durations by request class (the ``tag`` arg on the begin
    event; untagged requests group under ``""``)."""
    marks: Dict[Any, Dict[str, float]] = defaultdict(dict)
    tags: Dict[Any, str] = {}
    for ev in events:
        if ev["ph"] not in _ASYNC or ev.get("cat", "request") != "request":
            continue
        rid = ev["id"]
        if ev["ph"] == "b":
            marks[rid]["enqueue"] = ev["ts"]
            tags[rid] = str(ev.get("args", {}).get("tag", ""))
        elif ev["ph"] == "e":
            marks[rid]["done"] = ev["ts"]
        elif ev["name"] in PHASES:
            # re-admission overwrites: phases reflect the FINAL attempt
            marks[rid][ev["name"]] = ev["ts"]
    spans = {
        "queue_s": ("enqueue", "admit"),
        "prefill_s": ("admit", "prefill_done"),
        "ttft_s": ("enqueue", "first_token"),
        "decode_s": ("first_token", "done"),
        "total_s": ("enqueue", "done"),
    }
    out: Dict[str, Dict[str, List[float]]] = defaultdict(lambda: defaultdict(list))
    for rid, m in marks.items():
        if "done" not in m:
            continue  # in flight when the trace was cut
        cls = tags.get(rid, "")
        for phase, (a, b) in spans.items():
            if a in m and b in m:
                out[cls][phase].append(m[b] - m[a])
    return out


def summarize(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    by_name: Dict[str, List[float]] = defaultdict(list)
    by_stage: Dict[int, List[float]] = defaultdict(list)
    counts = {ph: 0 for ph in _KNOWN}
    for ev in events:
        counts[ev["ph"]] += 1
        if ev["ph"] == "X":
            by_name[ev["name"]].append(ev["dur"])
            if ev["name"] == "train.update":
                by_stage[int(ev.get("args", {}).get("stage", -1))].append(ev["dur"])
    classes = request_phases(events)
    return {
        "events": len(events),
        "event_counts": counts,
        "spans": {name: _pcts(xs) for name, xs in sorted(by_name.items())},
        "request_classes": {
            cls: {phase: _pcts(xs) for phase, xs in sorted(phases.items())}
            for cls, phases in sorted(classes.items())
        },
        "train_stages": {
            str(stage): _pcts(xs) for stage, xs in sorted(by_stage.items())
        },
    }


def _fmt_s(x: float) -> str:
    if x != x:  # NaN
        return "    nan"
    if x >= 1.0:
        return f"{x:6.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:5.1f}ms"
    return f"{x * 1e6:5.0f}µs"


def render(summary: Dict[str, Any]) -> str:
    lines = [f"{summary['events']} events  ({summary['event_counts']})"]
    if summary["spans"]:
        lines.append("\nspans (p50 / p99, nearest-rank):")
        for name, s in summary["spans"].items():
            lines.append(
                f"  {name:<24} n={s['count']:<6} total={_fmt_s(s['total_s'])}"
                f"  p50={_fmt_s(s['p50_s'])}  p99={_fmt_s(s['p99_s'])}"
            )
    for cls, phases in summary["request_classes"].items():
        label = cls or "(untagged)"
        n = phases.get("total_s", {}).get("count", 0)
        lines.append(f"\nrequest class {label!r}: {n} completed")
        for phase, s in phases.items():
            lines.append(
                f"  {phase:<12} p50={_fmt_s(s['p50_s'])}  p99={_fmt_s(s['p99_s'])}"
            )
    if summary["train_stages"]:
        lines.append("\ntrain updates by stage:")
        for stage, s in summary["train_stages"].items():
            lines.append(
                f"  stage {stage:<3} n={s['count']:<6}"
                f" p50={_fmt_s(s['p50_s'])}  p99={_fmt_s(s['p99_s'])}"
            )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="trace_view", description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="chrome trace JSON from repro.obs")
    ap.add_argument("--json", action="store_true", help="machine-readable summary")
    args = ap.parse_args(argv)
    try:
        events = load_events(args.trace)
    except TraceError as e:
        print(f"trace_view: MALFORMED: {e}", file=sys.stderr)
        return 2
    summary = summarize(events)
    if args.json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        print(f"{args.trace} OK")
        print(render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
