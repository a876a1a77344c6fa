"""Workload (arrival-rate) trace generation — the video-stream analogue.

Traces model the paper's content dynamics (Fig. 2a): a base request rate per
stream (15 FPS × objects-per-frame), slow diurnal drift, scene-dependent
regimes that switch on context changes (road construction, camera pans), and
short bursts. ``switching_traces`` produces the Fig. 13-style concatenation
of 5-minute segments from different sources; ``ood_traces`` produces the
Fig. 10 out-of-distribution switch (AI-City-style different rate statistics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def smooth_noise(key, n, scale=1.0, corr=0.9):
    """AR(1) noise — smooth rate wander."""
    eps = jax.random.normal(key, (n,)) * scale

    def step(carry, e):
        x = corr * carry + (1 - corr) * e
        return x, x

    _, xs = jax.lax.scan(step, 0.0, eps)
    return xs


def make_trace(key, n_steps: int, base_rate: float = 30.0,
               regime_period: int = 120, regime_scale: float = 0.5,
               burst_prob: float = 0.02, burst_scale: float = 3.0):
    """One stream's arrival-rate trace (requests per control interval)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    t = jnp.arange(n_steps)
    # scene regimes: piecewise-constant multipliers
    n_regimes = n_steps // regime_period + 1
    regime_mult = 1.0 + regime_scale * (
        jax.random.uniform(k1, (n_regimes,)) * 2 - 1)
    regimes = regime_mult[t // regime_period]
    # diurnal-ish slow sine
    slow = 1.0 + 0.25 * jnp.sin(2 * jnp.pi * t / max(n_steps, 1) * 2.0)
    # AR noise
    noise = 1.0 + smooth_noise(k2, n_steps, scale=0.4)
    # bursts (event spikes)
    bursts = jnp.where(jax.random.uniform(k3, (n_steps,)) < burst_prob,
                       burst_scale, 1.0)
    rate = base_rate * regimes * slow * noise * bursts
    return jnp.clip(rate, 1.0, 400.0)


def fleet_traces(key, n_agents: int, n_steps: int, base_rate: float = 30.0,
                 heterogeneity: float = 0.5, **trace_kw):
    """(A, n_steps) traces with per-agent base rates (workload heterogeneity).
    Extra kwargs flow to ``make_trace`` (regime/burst dynamics)."""
    kb, kt = jax.random.split(key)
    bases = base_rate * (1.0 + heterogeneity * (
        jax.random.uniform(kb, (n_agents,)) * 2 - 1))
    keys = jax.random.split(kt, n_agents)
    return jax.vmap(lambda k, b: make_trace(k, n_steps, b, **trace_kw))(keys, bases)


# objects per frame move in steps of 2**-8, so that a pipeline stage's
# fan-out spread is exact in float32 (``sim.state.fanout_arrivals``)
OBJECT_STEP = 1.0 / 256


def object_counts(key, n_cameras: int, n_steps: int, base: float = 2.0,
                  heterogeneity: float = 0.5, wander: float = 0.4,
                  burst_prob: float = 0.05, burst_scale: float = 3.0,
                  max_objects: float = 12.0):
    """(C, n_steps) objects per frame of each camera, per control interval
    (what a detector hands each downstream model): a per-camera base spread
    by ``heterogeneity`` around ``base``, an AR(1) wander of scale
    ``wander``, independent ``burst_scale`` bursts in ``burst_prob`` of the
    intervals, clipped to [0, max_objects] and rounded to ``OBJECT_STEP``."""
    kb, kt = jax.random.split(key)
    bases = base * (1.0 + heterogeneity * (
        jax.random.uniform(kb, (n_cameras,)) * 2 - 1))

    def one(k, b):
        k1, k2 = jax.random.split(k)
        noise = 1.0 + smooth_noise(k1, n_steps, scale=wander)
        bursts = jnp.where(jax.random.uniform(k2, (n_steps,)) < burst_prob,
                           burst_scale, 1.0)
        return jnp.clip(b * noise * bursts, 0.0, max_objects)

    f = jax.vmap(one)(jax.random.split(kt, n_cameras), bases)
    return jnp.round(f / OBJECT_STEP) * OBJECT_STEP


# Fig. 2a-grade content dynamics (3-10x swings): used by the fig7/9/10
# benchmarks so runtime conditions genuinely differ from profiling data.
DYNAMIC = dict(regime_scale=0.9, burst_prob=0.05, burst_scale=4.0)
# Narrow profiling distribution (what an offline-trained agent sees).
PROFILING = dict(regime_scale=0.05, burst_prob=0.0)


def switching_traces(key, n_agents: int, n_steps: int, segment: int = 60,
                     base_rates=(15.0, 45.0, 90.0)):
    """Fig. 13: concatenated segments from drastically different sources.
    Every ``segment`` steps the underlying distribution switches."""
    rates = jnp.asarray(base_rates)
    k1, k2 = jax.random.split(key)
    n_seg = n_steps // segment + 1
    seg_src = jax.random.randint(k1, (n_agents, n_seg), 0, len(base_rates))
    t = jnp.arange(n_steps)
    base = rates[seg_src[:, t // segment]]                  # (A, n_steps)
    keys = jax.random.split(k2, n_agents)
    noise = jax.vmap(lambda k: 1.0 + smooth_noise(k, n_steps, 0.3))(keys)
    return jnp.clip(base * noise, 1.0, 400.0)


def ood_traces(key, n_agents: int, n_steps: int):
    """Fig. 10: out-of-distribution workload (different rate stats + burst
    structure, AI-City-style 10 FPS vehicle-tracking)."""
    kb, kt = jax.random.split(key)
    bases = 60.0 * (1.0 + 0.8 * (jax.random.uniform(kb, (n_agents,)) * 2 - 1))
    keys = jax.random.split(kt, n_agents)
    return jax.vmap(lambda k, b: make_trace(
        k, n_steps, b, regime_period=30, regime_scale=1.0,
        burst_prob=0.08, burst_scale=2.0))(keys, bases)


# Spiky event-camera workload: frequent short multi-x spikes on a moderate
# base — stresses admission control and the deadline tail.
BURST = dict(regime_scale=0.3, burst_prob=0.15, burst_scale=5.0)


def diurnal_traces(key, n_agents: int, n_steps: int, base_rate: float = 40.0,
                   amplitude: float = 0.7, cycles: float = 1.0):
    """Day/night load cycle: a deep sinusoid (peak ≈ (1+amplitude)·base,
    trough ≈ (1-amplitude)·base) with a per-agent phase offset (cameras in
    different timezones / street orientations) plus AR(1) wander."""
    kp, kb, kt = jax.random.split(key, 3)
    phases = jax.random.uniform(kp, (n_agents,)) * 2 * jnp.pi
    bases = base_rate * (1.0 + 0.3 * (
        jax.random.uniform(kb, (n_agents,)) * 2 - 1))
    t = jnp.arange(n_steps, dtype=jnp.float32)
    keys = jax.random.split(kt, n_agents)

    def one(k, b, ph):
        cycle = 1.0 + amplitude * jnp.sin(
            2 * jnp.pi * cycles * t / max(n_steps, 1) + ph)
        noise = 1.0 + smooth_noise(k, n_steps, scale=0.2)
        return jnp.clip(b * cycle * noise, 1.0, 400.0)

    return jax.vmap(one)(keys, bases, phases)


def flash_crowd_traces(key, n_agents: int, n_steps: int,
                       base_rate: float = 25.0, surge_mult: float = 6.0,
                       surge_frac: float = 0.25):
    """Flash crowd: steady load, then a sudden *sustained* surge (a viral
    event / accident on camera) of ``surge_frac`` of the horizon at
    ``surge_mult``× the base rate, starting at a per-agent random step —
    the regime an interval-granular scheduler reacts to a whole period
    late."""
    ks, kb, kt = jax.random.split(key, 3)
    surge_len = max(int(n_steps * surge_frac), 1)
    starts = jax.random.randint(ks, (n_agents,), n_steps // 8,
                                max(n_steps - surge_len, n_steps // 8 + 1))
    bases = base_rate * (1.0 + 0.3 * (
        jax.random.uniform(kb, (n_agents,)) * 2 - 1))
    t = jnp.arange(n_steps)
    keys = jax.random.split(kt, n_agents)

    def one(k, b, s0):
        in_surge = (t >= s0) & (t < s0 + surge_len)
        mult = jnp.where(in_surge, surge_mult, 1.0)
        noise = 1.0 + smooth_noise(k, n_steps, scale=0.25)
        return jnp.clip(b * mult * noise, 1.0, 400.0)

    return jax.vmap(one)(keys, bases, starts)


def drift_traces(key, n_agents: int, n_steps: int, start_rate: float = 15.0,
                 end_rate: float = 90.0):
    """Slow non-stationary drift: the base rate ramps monotonically from
    ``start_rate`` to ``end_rate`` over the horizon (seasonal content
    drift) — no single static configuration is right for the whole trace,
    and a frozen policy degrades monotonically."""
    kb, kt = jax.random.split(key)
    jitter = 1.0 + 0.25 * (jax.random.uniform(kb, (n_agents,)) * 2 - 1)
    t = jnp.arange(n_steps, dtype=jnp.float32)
    ramp = start_rate + (end_rate - start_rate) * t / max(n_steps - 1, 1)
    keys = jax.random.split(kt, n_agents)

    def one(k, j):
        noise = 1.0 + smooth_noise(k, n_steps, scale=0.25)
        return jnp.clip(ramp * j * noise, 1.0, 400.0)

    return jax.vmap(one)(keys, jitter)
