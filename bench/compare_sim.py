"""The comparison that decides ``correct`` in the ``sim`` cells: the
program's first two dispatches (the second continues from the first's
carried state) against the plain reference (``bench/reference/pipeline.py``)
run through the same intervals in one go, given the program's actions.

Exact: per agent, the requests that arrived, were dropped, completed and
completed within their deadline (``effective``) after the checked
intervals (the number of agents that differ), and per
interval and agent the requests routed in from upstream (the number of
cells that differ). Within a limit: each agent's summed latency (the worst
relative difference), and the log-probability of the program's action
under each policy at each checked observation (the worst absolute
difference, in nats): the normalized logits at the action taken.
"""
from __future__ import annotations

import numpy as np

from bench.reference.fcpo import ARRIVED, COMPLETED, DROPPED, EFFECTIVE

F64 = np.float64


def sim_numbers(prog, ref):
    """{name: (value, detail)}. ``prog`` and ``ref`` are readings dicts:
    counters (A, 12) and lat_sum (A,) after the checked intervals, routed
    and logp (T, A) per interval."""
    out = {}
    cp = np.asarray(prog["counters"], np.int64)
    cr = np.asarray(ref["counters"], np.int64)
    for name, j in (("arrived", ARRIVED), ("dropped", DROPPED),
                    ("completed", COMPLETED), ("effective", EFFECTIVE)):
        out[name] = (float(np.sum(cp[:, j] != cr[:, j])),
                     f"agents that differ; {int(cp[:, j].sum())} vs "
                     f"{int(cr[:, j].sum())} requests")
    rp = np.asarray(prog["routed"], np.int64)
    rr = np.asarray(ref["routed"], np.int64)
    out["routed"] = (float(np.sum(rp != rr)),
                     f"agent-intervals that differ; {int(rp.sum())} vs "
                     f"{int(rr.sum())} requests")
    lp = np.asarray(prog["lat_sum"], F64)
    lr = np.asarray(ref["lat_sum"], F64)
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), 1.0)
    a = int(np.argmax(rel))
    out["lat_sum"] = (float(rel[a]),
                      f"agent {a}: {float(lp[a])!r} vs {float(lr[a])!r} ticks")
    gp = np.asarray(prog["logp"], F64)
    gr = np.asarray(ref["logp"], F64)
    gap = np.abs(gp - gr)
    t, a = np.unravel_index(int(np.argmax(gap)), gap.shape)
    out["logp"] = (float(gap[t, a]),
                   f"interval {t}, agent {a}: {float(gp[t, a])!r} vs "
                   f"{float(gr[t, a])!r}")
    return out
