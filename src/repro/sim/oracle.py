"""Host-side discrete-event oracle for the tensorized twin.

Mirrors ``kernels.ref.sim_microtick`` request-for-request using the plain
Python data-plane classes from ``serving/slo.py`` (``BoundedQueue`` /
``Request`` / ``SLOTracker``) — the reference the twin is equivalence-tested
against (tests/test_sim.py) and the baseline the fig_sim_fidelity benchmark
times. All times are in MICROTICKS (the tracker's ``slo_s`` is the deadline
in ticks), and the service credits are float32 with the twin's order of
operations, so the two implementations agree exactly: same completions,
drops, and effective throughput.

``simulate_python_pipeline`` chains agents into feed-forward pipelines
(``sim.state.Pipeline``): one whole stage after the other, each later stage
fed its upstream's per-tick completions times the objects per frame, with
the fractional object carried from interval to interval.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.kernels.ref import (CAP_BATCH, CAP_POST, CAP_PRE, CAP_QCAP,
                               CAP_SLO, CAP_TBATCH)
from repro.serving.slo import BoundedQueue, Request, SLOTracker
from repro.sim.state import SimParams


def simulate_python_agent(arrivals: np.ndarray, caps: np.ndarray,
                          sp: SimParams) -> Dict[str, float]:
    """One agent through the Python data plane. arrivals: (T, K) int
    per-tick arrival counts; caps: (T, SIM_NCAPS) float (one action decode
    per control interval; queue_cap and slo must be constant — they are
    device properties, not actions). Returns the same request totals the
    twin accumulates."""
    return _run_agent(arrivals, caps, sp)[0]


def _run_agent(arrivals, caps, sp: SimParams):
    """``simulate_python_agent``'s totals, and the agent's completions in
    each tick, (T, K)."""
    arrivals = np.asarray(arrivals)
    caps = np.asarray(caps, np.float32)
    qcap = int(caps[0, CAP_QCAP])
    slo_ticks = int(caps[0, CAP_SLO])
    one = np.float32(1.0)

    pre = BoundedQueue(capacity=qcap)
    ready: List[Request] = []       # batch-formation queue
    in_service: List[Request] = []  # the one in-flight inference batch
    post: List[Request] = []
    tracker = SLOTracker(slo_s=slo_ticks)
    busy, done_at = False, 0
    pre_credit = post_credit = np.float32(0.0)
    rid, m = 0, 0
    done = np.zeros(arrivals.shape, np.int64)

    for t in range(arrivals.shape[0]):
        c_pre, c_post = caps[t, CAP_PRE], caps[t, CAP_POST]
        batch_slots = int(caps[t, CAP_BATCH])
        t_batch = int(caps[t, CAP_TBATCH])
        for j in range(arrivals.shape[1]):
            # (1) inference completion -> post queue
            if busy and m >= done_at:
                post.extend(in_service)
                in_service, busy = [], False
            # (2) post-processing completes the n oldest
            post_credit = min(post_credit + c_post, c_post + one)
            n = min(int(post_credit), len(post))
            if n:
                tracker.complete(post[:n], now=m + 1)
                post = post[n:]
            post_credit -= np.float32(n)
            done[t, j] = n
            # (3) batch launch, backpressured by post room
            if not busy:
                room = qcap - (len(post) + len(in_service))
                nl = min(len(ready), batch_slots, room)
                if nl > 0:
                    in_service, ready = ready[:nl], ready[nl:]
                    busy, done_at = True, m + t_batch
            # (4) pre-processing, backpressured by batch-formation room
            pre_credit = min(pre_credit + c_pre, c_pre + one)
            n = min(int(pre_credit), len(pre), max(qcap - len(ready), 0))
            ready.extend(pre.pop_batch(n))
            pre_credit -= np.float32(n)
            # (5) admission; BoundedQueue counts the drops
            for _ in range(int(arrivals[t, j])):
                pre.push(Request(rid, arrival_t=m))
                rid += 1
            m += 1

    eff = sum(1 for _, lat, _ in tracker.completed if lat <= slo_ticks)
    return {
        "arrived": rid,
        "dropped": pre.drops,
        "completed": len(tracker.completed),
        "effective": eff,
        "lat_sum": float(sum(lat for _, lat, _ in tracker.completed)),
        "in_flight": len(pre.q) + len(ready) + len(in_service) + len(post),
        "effective_throughput": eff / max(m * sp.dt, 1e-9),
    }, done


def simulate_python_fleet(arrivals: np.ndarray, caps: np.ndarray,
                          sp: SimParams) -> List[Dict[str, float]]:
    """A agents sequentially through the Python oracle (this IS the
    baseline cost model: host-side per-agent loops). arrivals: (A, T, K);
    caps: (A, T, SIM_NCAPS)."""
    return [simulate_python_agent(arrivals[i], caps[i], sp)
            for i in range(arrivals.shape[0])]


def fanout_python(objects: np.ndarray, done: np.ndarray) -> np.ndarray:
    """A later stage's per-tick arrivals, (T, K), from its upstream's
    per-tick completions ``done`` (T, K) and the objects each completion
    sends on, (T,): tick by tick, the floor of the running objects
    (float32, as the twin sums them) less the floor before it, with the
    fraction carried across intervals."""
    out = np.zeros(done.shape, np.int64)
    phase = np.float32(0.0)
    for t in range(done.shape[0]):
        f = np.float32(objects[t])
        prev = np.floor(phase)
        running = np.int64(0)
        for k in range(done.shape[1]):
            running += done[t, k]
            cum = phase + f * np.float32(running)
            out[t, k] = int(np.floor(cum) - prev)
            prev = np.floor(cum)
        phase = cum - np.floor(cum)
    return out


def simulate_python_pipeline(arrivals: np.ndarray, objects: np.ndarray,
                             caps: np.ndarray, sp: SimParams, stages: int):
    """Pipelines of ``stages`` agents per camera through the Python oracle,
    stage after stage (a feed-forward chain has no back-pressure, so this
    order is exact). arrivals: (C, T, K) the cameras' per-tick frames;
    objects: (C, T) objects per frame, which the second stage receives per
    completed frame (every later stage one request per completion); caps:
    (A, T, SIM_NCAPS) per stage-major agent (agent s * C + c runs stage s
    of camera c). Returns (per-agent totals, (T, A) requests routed in
    from upstream per interval)."""
    n_cam, n_int = arrivals.shape[:2]
    totals: List[Dict[str, float]] = [{}] * (n_cam * stages)
    routed = np.zeros((n_int, n_cam * stages), np.int64)
    for c in range(n_cam):
        arr = np.asarray(arrivals[c])
        for s in range(stages):
            a = s * n_cam + c
            if s > 0:
                fan = objects[c] if s == 1 else np.ones(n_int, np.float32)
                arr = fanout_python(fan, done)
                routed[:, a] = arr.sum(axis=1)
            totals[a], done = _run_agent(arr, caps[a], sp)
    return totals, routed
