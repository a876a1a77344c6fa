"""Bytes the twin-evaluation cells' kernel calls move, from shapes.

A pipeline fleet advances every stage of every camera through a control
interval in one ``pipeline_advance`` call: each agent's twin state in and
out, its per-tick arrivals and caps in (the arrivals of a later stage are
all zero; the kernel makes its own from the fan-out), and the fan-out's
objects per completion and phase in and the phase out. ``bench/costs``
counts a single-model fleet's ``queue_advance`` call.
"""
from __future__ import annotations

from bench import costs

FANOUT_WORDS = 3     # objects and phase in, phase out (float32)


def pipeline_advance_bytes(n_agents, twin):
    """HBM bytes one ``pipeline_advance`` call moves for ``n_agents``
    (every stage's)."""
    return (costs.queue_advance_bytes(n_agents, twin)
            + n_agents * 4 * FANOUT_WORDS)
