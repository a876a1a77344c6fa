"""Plain reference of a pipeline fleet's twin evaluation (detector -> re-ID).

A straightforward implementation of what the ``sim`` cells run, for the
comparison that decides ``correct``. It imports nothing of the program: it
builds the policies from the seed as the program's fleet does, and takes
the twin's microtick, the action decode, the arrival spread, the
observation and the iAgent's forward pass from ``bench/reference/fcpo.py``.
Everything runs in plain ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

It is given the program's recorded actions, so that an action drawn
differently on rounding cannot show up as a fault of the twin. Interval by
interval, in stage order, it computes each agent's observation and the
log-probability of the program's action under its policy, then K
microticks per agent: stage 0 admits the spread of the camera's frame rate,
a later stage its upstream agent's per-tick completions fanned out by the
objects per frame (cumulative floor, the fraction carried from interval to
interval; arrivals enter in the tick of the completion that sent them). A
later stage observes, as its rate, its own arrivals of the previous
interval per second.

``dtype`` is the float type everything is computed and stored in: float32
for the reference, bfloat16 for the control that a sound comparison must
refuse. Integer state (actions, counters) stays int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import fcpo
from bench.reference.fcpo import COMPLETED, DROPPED


def make_ref(config, dtype, n_agents=None):
    """The reference's settings for a pipeline configuration, and the
    number of cameras (``n_agents`` is the tests' smaller fleet, whole
    pipelines)."""
    stages = len(config["stages"])
    cameras = n_agents // stages if n_agents else config["cameras"]
    r = fcpo.Ref(n_agents=cameras * stages, n_pods=1,
                 iagent=tuple(sorted(config["iagent"].items())), rl=(),
                 fl=(), env="twin",
                 twin=tuple(sorted(config["twin"].items())), dtype=dtype)
    return r, cameras


def env_params(r, speed, slo_s):
    """An agent's device profile at ``speed`` with deadline ``slo_s``."""
    F = r.dtype
    speed = speed.astype(F)
    return {"t0": 0.012 / speed, "t1": 0.0022 / speed,
            "pre_rate": 220.0 * speed, "post_rate": 260.0 * speed,
            "contention": 0.18 / jnp.maximum(speed, 0.25),
            "queue_cap": jnp.asarray(128.0, F),
            "slo_s": jnp.asarray(slo_s, F), "net_lat": jnp.asarray(0.015, F)}


def fleet(r, config, cameras, key):
    """The policies a seed gives (the program's fleet key split: the first
    of four keys, one per agent) and each agent's device profile: camera
    ``c``'s device speed from the program's device mix, times stage ``s``'s
    factor, for agent ``s * cameras + c``."""
    kp = jax.random.split(key, 4)[0]
    params = jax.vmap(lambda k: fcpo.agent_init(r, k))(
        jax.random.split(kp, r.n_agents))
    params = jax.tree.map(lambda x: x.astype(r.dtype), params)
    base = jnp.asarray(np.random.default_rng(0).choice(
        [0.5, 0.75, 1.0, 2.0], cameras), jnp.float32)
    speed = jnp.concatenate([base * f for f in config["stage_speed"]])
    slo = jnp.repeat(jnp.asarray(config["stage_slo_s"], jnp.float32),
                     cameras)
    ep = jax.vmap(lambda s, d: env_params(r, s, d))(speed, slo)
    return params, ep


def logp_of(r, p, obs, action):
    lres, lbs, lmt, _ = fcpo.forward(r, p, obs)
    return lres[action[0]] + lbs[action[1]] + lmt[action[2]]


def advance(r, s, arrivals, caps):
    """K microticks of one agent; returns the state and its completions in
    each tick."""
    def tick(st, n):
        st2 = fcpo.microtick(r, st, n, caps)
        return st2, st2["counters"][COMPLETED] - st["counters"][COMPLETED]

    return jax.lax.scan(tick, s, arrivals)


def fanout(r, objects, done, phase):
    """A later stage's per-tick arrivals from its upstream's per-tick
    completions: floors of the running objects sent on."""
    cum = phase + objects * jnp.cumsum(done).astype(r.dtype)
    floors = jnp.floor(jnp.concatenate([phase[None], cum]))
    return (floors[1:] - floors[:-1]).astype(jnp.int32), \
        cum[-1] - jnp.floor(cum[-1])


@functools.lru_cache(maxsize=None)
def make_run(r, cameras, stages):
    """A jitted ``(params, ep, rates (C, T), objects (C, T), actions
    (T, A, 3)) -> {"logp" (T, A), "routed" (T, A), "counters" (A, 12),
    "lat_sum" (A,)}`` from empty pipelines."""
    F = r.dtype
    interval_s = r.k_ticks * r.dt
    rows = [slice(s * cameras, (s + 1) * cameras) for s in range(stages)]

    def run(params, ep, rates, objects, actions):
        def step(carry, xs):
            env, routed_prev = carry
            rate, obj, acts = xs
            obs_rate = jnp.concatenate(
                [rate.astype(F)] + [routed_prev[r_].astype(F) / interval_s
                                    for r_ in rows[1:]])
            obs = jax.vmap(lambda e, s, x: fcpo.twin_observe(r, e, s, x))(
                ep, env, obs_rate)
            logp = jax.vmap(lambda p, o, a: logp_of(r, p, o, a))(
                params, obs, acts)
            caps = jax.vmap(lambda e, a: fcpo.twin_caps(r, e, a))(ep, acts)
            new, routed, done = [], [jnp.zeros((cameras,), jnp.int32)], None
            for s, r_ in enumerate(rows):
                part = jax.tree.map(lambda x: x[r_], env)
                if s == 0:
                    arr, phase = jax.vmap(
                        lambda v, ph: fcpo.spread(r, v, ph))(
                        rate.astype(F), part["phase"])
                else:
                    f = obj.astype(F) if s == 1 else jnp.ones((cameras,), F)
                    arr, phase = jax.vmap(
                        lambda o, d, ph: fanout(r, o, d, ph))(
                        f, done, part["phase"])
                    routed.append(arr.sum(axis=1))
                part, done = jax.vmap(lambda st, a, c: advance(r, st, a, c))(
                    part, arr, jax.tree.map(lambda x: x[r_], caps))
                new.append(dict(part, phase=phase))
            env2 = jax.tree.map(lambda *x: jnp.concatenate(x), *new)
            d_drop = (env2["counters"][:, DROPPED]
                      - env["counters"][:, DROPPED])
            env2 = dict(env2, cur_action=acts, drops_prev=d_drop)
            routed = jnp.concatenate(routed)
            return (env2, routed), (logp.astype(jnp.float32), routed)

        env = jax.vmap(lambda _: fcpo.twin_init(r))(jnp.arange(r.n_agents))
        carry = (env, jnp.zeros((r.n_agents,), jnp.int32))
        (env, _), (logp, routed) = jax.lax.scan(
            step, carry, (rates.T, objects.T, actions))
        return {"logp": logp, "routed": routed, "counters": env["counters"],
                "lat_sum": env["lat_sum"].astype(jnp.float32)}

    return jax.jit(run)


def readings(config, key, n_agents, rates, objects, actions, dtype):
    """The reference's readings over the checked intervals, from the
    seed's own policies, the program's traffic and the program's actions
    (host arrays)."""
    with jax.default_matmul_precision("highest"):
        r, cameras = make_ref(config, dtype, n_agents)
        params, ep = fleet(r, config, cameras, key)
        out = make_run(r, cameras, len(config["stages"]))(
            params, ep, jnp.asarray(rates, jnp.float32),
            jnp.asarray(objects, jnp.float32),
            jnp.asarray(actions, jnp.int32))
        return jax.tree.map(np.asarray, out)
