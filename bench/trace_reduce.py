"""From a profiler trace to the numbers the per-layer readers report.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``
beside the ``.xplane.pb``: Chrome trace events in which every device is a
process named ``/device:TPU:<n>`` with an ``XLA Ops`` thread (one event per
executed HLO op, nested: a ``while`` op spans the ops of its body) and an
``XLA Modules`` thread (one event per program run), and the host is the
process ``/host:CPU``, whose threads carry the benchmark's own spans
(``bench.*``). Device and host events share one clock (microseconds).

An op's ``args.tf_op`` is its JAX name stack, e.g.
``jit(_scan_driver)/while/body/closed_call/jit(fleet_episode)/vmap()``; a
layer's device time is the self time (duration less its nested ops) of the
ops whose stack holds that layer's ``jit(<frame>)``. A Pallas kernel is a
``custom-call`` op named after its jitted wrapper, ``_<kernel>_impl.<n>``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import itertools
import json
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(kind, required=True):
    """The published peaks of device ``kind``; an unknown device is an
    error, except in a rehearsal without a chip."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind in table:
        return table[kind]
    if required:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS}")
    return next(iter(table.values()))


def load_events(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)["traceEvents"]


def latest_trace(log_dir):
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.trace.json.gz")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return files[-1]


def reduce_dir(log_dir, chips):
    return Trace(load_events(latest_trace(log_dir)), chips)


def merge(intervals):
    """(start, end) intervals as sorted, disjoint [start, end] lists."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals):
    """Total length covered by (start, end) intervals."""
    return sum(e - s for s, e in merge(intervals))


class Coverage:
    """The union of intervals, asked how much of [lo, hi] it covers in
    logarithmic time."""

    def __init__(self, intervals):
        m = merge(intervals)
        self.starts = [s for s, _ in m]
        self.ends = [e for _, e in m]
        self.cum = [0.0] + list(itertools.accumulate(e - s for s, e in m))

    def within(self, lo, hi):
        i = bisect.bisect_right(self.ends, lo)
        j = bisect.bisect_left(self.starts, hi)
        if hi <= lo or i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))


def idle_intervals(intervals, lo, hi):
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(ops):
    """Each op's duration less the ops nested in it (same thread)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["ts"],
                                                   -ops[i]["dur"]))
    self_t = [op["dur"] for op in ops]
    stack = []
    for i in order:
        s, e = ops[i]["ts"], ops[i]["ts"] + ops[i]["dur"]
        while stack and ops[stack[-1]]["ts"] + ops[stack[-1]]["dur"] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= ops[i]["dur"]
        stack.append(i)
    return self_t


def base_name(name):
    return re.sub(r"\.\d+$", "", name)


class Trace:
    """One traced window, reduced per device and averaged over devices."""

    def __init__(self, events, chips):
        procs, threads = {}, {}
        for ev in events:
            if ev.get("ph") != "M":
                continue
            if ev.get("name") == "process_name":
                procs[ev["pid"]] = ev["args"]["name"]
            elif ev.get("name") == "thread_name":
                threads[(ev["pid"], ev["tid"])] = ev["args"]["name"]
        devices = sorted(p for p, n in procs.items()
                         if n.startswith("/device:TPU:"))[:chips]
        self.ops = {p: [] for p in devices}
        self.modules = {p: [] for p in devices}
        self.host = []
        for ev in events:
            if ev.get("ph") != "X":
                continue
            p = ev.get("pid")
            thread = threads.get((p, ev.get("tid")))
            if p in self.ops and thread == "XLA Ops":
                self.ops[p].append(ev)
            elif p in self.modules and thread == "XLA Modules":
                self.modules[p].append(ev)
            elif procs.get(p, "").startswith("/host:"):
                self.host.append(ev)
        spans = [e for e in self.host if e["name"].startswith(SPAN_PREFIX)]
        self.spans = spans
        if spans:
            self.lo = min(e["ts"] for e in spans)
            self.hi = max(e["ts"] + e["dur"] for e in spans)
        else:
            all_ops = [o for v in self.ops.values() for o in v]
            self.lo = min((o["ts"] for o in all_ops), default=0.0)
            self.hi = max((o["ts"] + o["dur"] for o in all_ops), default=0.0)
        self.self_t = {p: self_times(v) for p, v in self.ops.items()}

    # -- per-device reductions (microseconds), then means in seconds ------
    def _mean(self, per_device, scale=1e-6):
        vals = [per_device(p) for p in self.ops]
        return scale * sum(vals) / len(vals) if vals else 0.0

    def _clipped(self, p):
        return [(max(o["ts"], self.lo), min(o["ts"] + o["dur"], self.hi))
                for o in self.ops[p]
                if o["ts"] < self.hi and o["ts"] + o["dur"] > self.lo]

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e6

    @property
    def busy_s(self):
        """Seconds in which some op ran, union over the window."""
        return self._mean(lambda p: union_length(self._clipped(p)))

    def frame_time(self, frame):
        """Self time of the ops under ``jit(<frame>)`` in their name stack."""
        tag = f"jit({frame})"
        return self._mean(lambda p: sum(
            t for o, t in zip(self.ops[p], self.self_t[p])
            if tag in o.get("args", {}).get("tf_op", "")))

    def kernel(self, name):
        """(calls, seconds) of the Pallas kernel ``name`` per device."""
        pat = re.compile(rf"^_{re.escape(name)}_impl(\.\d+)?$")
        calls = self._mean(lambda p: sum(
            1 for o in self.ops[p] if pat.match(o["name"])), scale=1.0)
        secs = self._mean(lambda p: sum(
            o["dur"] for o in self.ops[p] if pat.match(o["name"])))
        return calls, secs

    def _main_runs(self, p):
        """(start, end) of each run of device ``p``'s main program (the
        module with the most device time) that starts in the window."""
        tot = defaultdict(float)
        for m in self.modules[p]:
            tot[m["name"]] += m["dur"]
        if not tot:
            return []
        main = max(tot, key=tot.get)
        return sorted((m["ts"], m["ts"] + m["dur"]) for m in self.modules[p]
                      if m["name"] == main and self.lo <= m["ts"] <= self.hi)

    def main_runs(self):
        """Runs of the main program in the window, averaged over devices:
        the dispatches whose device work the trace holds."""
        return self._mean(lambda p: len(self._main_runs(p)), scale=1.0)

    def dispatch_gaps(self):
        """Device-idle seconds between consecutive runs of the window's main
        program, per gap."""
        gaps = []
        for p in self.modules:
            runs = self._main_runs(p)
            busy = Coverage((o["ts"], o["ts"] + o["dur"])
                            for o in self.ops[p])
            for (_, e0), (s1, _) in zip(runs, runs[1:]):
                gaps.append((s1 - e0 - busy.within(e0, s1)) / 1e6)
        return gaps

    def breakdown(self, top=10):
        """The device ops with the most self time, and the longest device-
        idle time by the host span it fell in (seconds, device 0)."""
        if not self.ops:
            return {"device_ops": [], "idle_gaps": []}
        p = next(iter(self.ops))
        by_op = defaultdict(float)
        for o, t in zip(self.ops[p], self.self_t[p]):
            by_op[base_name(o["name"])] += t / 1e6
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        by_span = defaultdict(float)
        idle = idle_intervals(self._clipped(p), self.lo, self.hi)
        names = self.host_activities([(s + e) / 2 for s, e in idle])
        for (s, e), name in zip(idle, names):
            by_span[name] += (e - s) / 1e6
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}

    def host_activities(self, times):
        """For each time, the innermost host event around it on the
        benchmark's threads (its spans and the runtime's events inside
        them). One sweep: a thread's events nest, so a stack per thread
        holds the events open at the time swept to."""
        threads = {e["tid"] for e in self.spans}
        events = sorted((e for e in self.host if e["tid"] in threads),
                        key=lambda e: (e["ts"], -e["dur"]))
        end = lambda e: e["ts"] + e["dur"]
        stacks = defaultdict(list)
        out = [None] * len(times)
        k = 0
        for q in sorted(range(len(times)), key=times.__getitem__):
            t = times[q]
            while k < len(events) and events[k]["ts"] <= t:
                ev = events[k]
                stack = stacks[ev["tid"]]
                while stack and end(stack[-1]) < ev["ts"]:
                    stack.pop()
                stack.append(ev)
                k += 1
            best = None
            for stack in stacks.values():
                while stack and end(stack[-1]) < t:
                    stack.pop()
                if stack and (best is None or stack[-1]["dur"] < best["dur"]):
                    best = stack[-1]
            out[q] = best["name"] if best else "outside the benchmark's spans"
        return out
