"""The twin evaluation's own spans in a reduced trace: the named scopes
``sim_policy``, ``sim_fanout`` and ``sim_advance`` of
``repro.sim.harness._simulate``'s interval, read per simulated control
interval of the whole fleet (``bench/program_spans.py`` reads them). A
program without these scopes (a commit older than them) reads None."""
from __future__ import annotations

from bench import program_spans


def scope_ms_per_interval(ctx, scope):
    """``scope``'s device time per fleet interval (ms), or None when no op
    of the trace runs under it."""
    t = program_spans.scope_seconds(ctx["trace"], scope)
    if not t or not ctx["intervals"] or not ctx["agents"]:
        return None
    return 1e3 * t / (ctx["intervals"] / ctx["agents"])
