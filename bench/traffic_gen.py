"""Arrival-rate traces for the benchmark's traffic mixes.

A copy of the generators in ``src/repro/data/workload.py`` (``smooth_noise``,
``make_trace``, ``fleet_traces``), kept here so that a change to the program
cannot change the yardstick. A mix is a JSON file under ``bench/traffic/``
naming the generator parameters; ``make_traces`` turns it and a key into
``(n_agents, n_intervals)`` requests per control interval. The program
receives only the generated traces.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def smooth_noise(key, n, scale=1.0, corr=0.9):
    """AR(1) noise: smooth rate wander."""
    eps = jax.random.normal(key, (n,)) * scale

    def step(carry, e):
        x = corr * carry + (1 - corr) * e
        return x, x

    _, xs = jax.lax.scan(step, 0.0, eps)
    return xs


def make_trace(key, n_steps, base_rate=30.0, regime_period=120,
               regime_scale=0.5, burst_prob=0.02, burst_scale=3.0,
               min_rate=1.0, max_rate=400.0):
    """One camera's arrival-rate trace (requests per control interval):
    scene regimes, a slow sine, AR(1) wander and bursts."""
    k1, k2, k3, _ = jax.random.split(key, 4)
    t = jnp.arange(n_steps)
    n_regimes = n_steps // regime_period + 1
    regime_mult = 1.0 + regime_scale * (
        jax.random.uniform(k1, (n_regimes,)) * 2 - 1)
    regimes = regime_mult[t // regime_period]
    slow = 1.0 + 0.25 * jnp.sin(2 * jnp.pi * t / max(n_steps, 1) * 2.0)
    noise = 1.0 + smooth_noise(k2, n_steps, scale=0.4)
    bursts = jnp.where(jax.random.uniform(k3, (n_steps,)) < burst_prob,
                       burst_scale, 1.0)
    rate = base_rate * regimes * slow * noise * bursts
    return jnp.clip(rate, min_rate, max_rate)


def fleet_traces(key, n_agents, n_steps, base_rate=30.0, heterogeneity=0.5,
                 **trace_kw):
    """(A, n_steps) traces with per-camera base rates spread by
    ``heterogeneity`` around ``base_rate``."""
    kb, kt = jax.random.split(key)
    bases = base_rate * (1.0 + heterogeneity * (
        jax.random.uniform(kb, (n_agents,)) * 2 - 1))
    keys = jax.random.split(kt, n_agents)
    return jax.vmap(lambda k, b: make_trace(k, n_steps, b, **trace_kw))(
        keys, bases)


GENERATORS = {"fleet_traces": fleet_traces}


def load_mix(name):
    """Traffic mix ``name``, from ``bench/traffic/<name>.json``."""
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


def make_traces(mix, key, n_agents, n_intervals):
    """(n_agents, n_intervals) float32 rates of ``mix`` (a loaded mix dict)."""
    params = dict(mix["params"])
    gen = GENERATORS[mix["generator"]]
    fn = jax.jit(lambda k: gen(k, n_agents, n_intervals, **params))
    return fn(key)
