"""Chrome trace-event JSON: a writer and its schema check.

The training program's own spans live in the JAX profiler's trace, on one
clock with the device ops: ``jax.named_scope``s inside the compiled scan
(``fcpo_rollout``, ``fcpo_buffer``, ``fcpo_update``, ``fl_uplink``,
``fl_encode``, ``fl_aggregate``, ``fl_finetune``) and
``jax.profiler.TraceAnnotation``s around the host stages of
``core.fleet.train_fleet_scan`` (``fleet.prep``, ``fleet.call``,
``fleet.fetch``). ``launch/train_fleet.py --trace-out DIR`` records them.

This module writes the timelines that have a clock of their own: the
request lifecycles ``obs.requests.records_to_chrome`` reconstructs on the
twin's virtual time (``launch/simulate.py --trace-out``). The file opens
in Perfetto / ``chrome://tracing``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional


class Tracer:
    """Collects complete slices and exports them as Chrome trace-event
    JSON (the ``traceEvents`` container format)."""

    def __init__(self, pid: int = 1):
        self.pid = pid
        self.events: List[Dict[str, Any]] = []

    def add_complete(self, name: str, ts_us: float, dur_us: float,
                     cat: str = "request", pid: Optional[int] = None,
                     tid: int = 0, args: Optional[Dict] = None):
        """Append a complete slice with caller-given timestamps (the
        request-attribution exporter passes virtual twin-time)."""
        ev = {"name": name, "cat": cat, "ph": "X", "ts": float(ts_us),
              "dur": float(max(dur_us, 0.0)),
              "pid": self.pid if pid is None else pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def chrome_trace(self) -> Dict[str, Any]:
        return {"traceEvents": sorted(self.events, key=lambda e: e["ts"]),
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, default=float)
        return path


REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")
VALID_PH = {"X", "B", "E", "i", "I", "M", "C", "b", "e", "s", "t", "f"}


def validate_chrome_trace(trace: Any) -> List[str]:
    """Structural check of a Chrome trace-event JSON object. Returns a list
    of problems (empty == valid): container shape, per-event required keys,
    known phase codes, numeric non-negative timestamps, ``X`` events carry
    a non-negative ``dur``."""
    problems: List[str] = []
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        return ["not a {'traceEvents': [...]} container"]
    events = trace["traceEvents"]
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        missing = [k for k in REQUIRED_KEYS if k not in ev]
        if missing:
            problems.append(f"event {i}: missing {missing}")
            continue
        if ev["ph"] not in VALID_PH:
            problems.append(f"event {i}: unknown phase {ev['ph']!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            problems.append(f"event {i}: bad ts {ev['ts']!r}")
        if ev["ph"] == "X" and (not isinstance(ev.get("dur"), (int, float))
                                or ev["dur"] < 0):
            problems.append(f"event {i}: X event without valid dur")
    return problems
