"""The program's own spans in a reduced trace: the host stages of
``repro.core.fleet.train_fleet_scan`` (the ``fleet.*`` profiler
annotations, nested in the benchmark's ``bench.dispatch``) and the named
scopes of its compiled scan (``fcpo_*`` in ``core/crl.py``, ``fl_*`` in
``core/fleet.fl_round``).

JAX writes a scope into a device op's name stack as one path segment,
wrapped by the transformations applied inside the scope's caller:
``.../jit(fleet_episode)/vmap(fcpo_update)/transpose(jvp())/dot_general``.
A fusion carries the name stack of its root op, so an op fused across a
scope's edge counts where its root lies. A program without these spans (a
commit older than them) reads None.
"""
from __future__ import annotations

import re


def host_span_ms(trace, name):
    """Mean duration (ms) of the host spans called ``name`` that start in
    the traced window, or None when there are none."""
    durs = [e["dur"] for e in trace.host
            if e["name"] == name and trace.lo <= e["ts"] <= trace.hi]
    return 1e-3 * sum(durs) / len(durs) if durs else None


def scope_seconds(trace, scope):
    """Self time (s) of the device ops whose name stack holds the segment
    ``scope``, averaged over the devices."""
    pat = re.compile(rf"(^|[/(;]){re.escape(scope)}($|[/):;])")
    return trace._mean(lambda p: sum(
        t for o, t in zip(trace.ops[p], trace.self_t[p])
        if pat.search(o.get("args", {}).get("tf_op", ""))))


def scope_ms_per_episode(ctx, scope):
    """``scope``'s device time per episode (ms), or None when no op of the
    trace runs under it."""
    t = scope_seconds(ctx["trace"], scope)
    if not t or not ctx["episodes"]:
        return None
    return 1e3 * t / ctx["episodes"]
