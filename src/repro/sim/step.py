"""Interval advance: the twin's data plane for one K-microtick interval.

``sim_interval_ref`` is the single-agent jnp oracle (a ``lax.scan`` over the
shared ``kernels.ref.sim_microtick``); ``sim_interval`` is the fleet-batched
entry point that either vmaps the oracle or routes the whole agent batch
through the fused Pallas ``queue_advance`` kernel, which advances blocks of
agents together — bit-identical paths (tests/test_sim.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.sim.state import SimState


def sim_interval_ref(state: SimState, arrivals: jnp.ndarray,
                     caps: jnp.ndarray) -> SimState:
    """Advance ONE agent k_ticks microticks. arrivals: (K,) int32; caps:
    (SIM_NCAPS,) float32 (one action decode held for the interval)."""
    return SimState(*kref.queue_advance_ref(*state, arrivals, caps))


def sim_interval_agent(state: SimState, arrivals: jnp.ndarray,
                       caps: jnp.ndarray,
                       use_pallas: bool = False) -> SimState:
    """Advance ONE agent (the training-backend entry point — vmapped over
    the fleet by ``fleet_episode``): the jnp oracle scan, or the fused
    Pallas kernel. ``kops.queue_advance`` accepts one agent's operands and
    its batching rule turns the fleet's ``vmap`` of this call into one
    kernel call on the whole (A, ...) batch."""
    if use_pallas:
        return SimState(*kops.queue_advance(*state, arrivals, caps))
    return sim_interval_ref(state, arrivals, caps)


def sim_interval(state: SimState, arrivals: jnp.ndarray, caps: jnp.ndarray,
                 use_pallas: bool = False) -> SimState:
    """Fleet-batched advance: state leaves (A, ...), arrivals (A, K), caps
    (A, SIM_NCAPS). ``use_pallas`` fuses the whole interval of every agent
    into one kernel call for the batch."""
    if use_pallas:
        return SimState(*kops.queue_advance(*state, arrivals, caps))
    return jax.vmap(sim_interval_ref)(state, arrivals, caps)


def sim_interval_recorded(state: SimState, arrivals: jnp.ndarray,
                          caps: jnp.ndarray):
    """Single-agent jnp advance that ALSO returns the counters vector after
    every microtick — the request-attribution tap (``repro.obs.requests``
    reconstructs per-request stage stamps from these monotone series).

    Same ``lax.scan`` of ``sim_microtick`` as ``sim_interval_ref`` with a
    per-tick ys output added, so the carried state is bit-identical to the
    unrecorded path (int32 counters — no float reassociation to worry
    about). Returns (new_state, (K, SIM_NCOUNTERS) int32)."""
    def tick(carry, n_arr):
        out = kref.sim_microtick(*carry, n_arr, caps)
        return out, out[1]

    carry, ticks = jax.lax.scan(tick, tuple(state), arrivals)
    return SimState(*carry), ticks
