"""Objects per frame for the traffic mixes that feed pipelines.

A copy of ``object_counts`` in ``src/repro/data/workload.py``, kept here
so that a change to the program cannot change the yardstick (as
``traffic_gen.py`` copies the rate generators). A mix with an ``objects``
group names this generator and its parameters; ``make_objects`` turns it
and a key into ``(n_cameras, n_intervals)`` objects per frame, in steps of
1/256 so that the program's fan-out spread and the reference's floor the
same float32 numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.traffic_gen import smooth_noise

STEP = 1.0 / 256


def object_counts(key, n_cameras, n_steps, base=2.0, heterogeneity=0.5,
                  wander=0.4, burst_prob=0.05, burst_scale=3.0,
                  max_objects=12.0):
    """A per-camera base spread by ``heterogeneity`` around ``base``, an
    AR(1) wander of scale ``wander``, independent ``burst_scale`` bursts in
    ``burst_prob`` of the intervals, clipped to [0, max_objects]."""
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
    return jnp.round(f / STEP) * STEP


GENERATORS = {"object_counts": object_counts}


def make_objects(mix, key, n_cameras, n_intervals):
    """(n_cameras, n_intervals) float32 objects per frame of ``mix``'s
    ``objects`` group."""
    group = mix["objects"]
    gen = GENERATORS[group["generator"]]
    params = dict(group["params"])
    return jax.jit(lambda k: gen(k, n_cameras, n_intervals, **params))(key)
