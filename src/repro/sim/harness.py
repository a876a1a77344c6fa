"""Closed-loop twin harness: trained FCPO policies driving the request-level
data plane.

``simulate_fleet`` runs a whole fleet evaluation as ONE jitted program:
a ``lax.scan`` over control intervals where each interval observes the twin
state, samples the iAgent actions (policy applied every k_ticks microticks,
exactly the paper's 1 s control cadence), decodes them to service caps, and
advances K microticks through ``sim_interval`` (vmapped jnp oracle or the
fused Pallas kernel). There is zero host-side Python per microtick — the
host dispatches once and fetches the per-interval history once.

A ``Pipeline`` of several stages runs each interval in stage order: stage
0's agents advance their whole interval, their per-tick completions are
fanned out by the objects per frame into the next stage's per-tick
arrivals (``fanout_arrivals``), and so on. A feed-forward chain has no
back-pressure, so the stage order is exact. A ``SimCarry`` holds all that
an interval hands to the next, so an evaluation can be dispatched in
chunks: two calls of T/2 intervals equal one of T, bit for bit.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.fcpo import FCPOConfig
from repro.core.agent import ActionMask, sample_actions
from repro.core.env import EnvParams, observe_vector
from repro.kernels import ops as kops
from repro.sim import metrics as sim_metrics
from repro.sim.state import (Pipeline, SimParams, SimState, action_caps,
                             effective_queue_cap, fanout_arrivals, sim_init,
                             spread_arrivals, warn_if_ring_clamps)
from repro.sim.step import (sim_interval, sim_interval_recorded,
                            tick_completions)


class SimCarry(NamedTuple):
    """What one control interval hands to the next, per agent (A, ...)."""
    state: SimState        # the twin
    drops: jnp.ndarray     # (A,) int32 — drops of the last interval
    action: jnp.ndarray    # (A, 3) int32 — the action in force
    phase: jnp.ndarray     # (A,) float32 — fractional request carried by
    #                        the agent's arrival spread (trace or fan-out)
    routed: jnp.ndarray    # (A,) int32 — requests the last interval routed
    #                        in from upstream (0 at stage 0)
    rng: jnp.ndarray       # the policy's sampling key


class SimRun(NamedTuple):
    """One ``simulate_fleet`` call: the final twin state, the per-interval
    history (host arrays of (T, A, ...)), the per-agent request-grade
    summary, and the carry to continue from."""
    state: SimState
    history: Dict
    summary: Dict
    carry: SimCarry


def sim_start(sp: SimParams, n_agents: int, key) -> SimCarry:
    """The carry of an evaluation's first interval: empty pipelines, no
    drops, action (0, 0, 0), no phase, sampling key ``key``."""
    zeros = jnp.zeros((n_agents,), jnp.int32)
    return SimCarry(state=jax.vmap(lambda _: sim_init(sp))(
                        jnp.arange(n_agents)),
                    drops=zeros, action=jnp.zeros((n_agents, 3), jnp.int32),
                    phase=jnp.zeros((n_agents,), jnp.float32), routed=zeros,
                    rng=key)


def sim_observe(cfg: FCPOConfig, sp: SimParams, ep: EnvParams,
                state: SimState, drops_prev, cur_action, rate):
    """The 8-dim iAgent state vector (§IV-B) read off the twin instead of
    the fluid MDP. The normalization is ``core.env.observe_vector`` — the
    ONE definition every backend shares — so a policy trained on the fluid
    env transfers without retargeting (parity: tests/test_backends.py)."""
    return observe_vector(cfg, rate=rate, cur_action=cur_action,
                          drops=drops_prev, pre_q=state.pre_q,
                          post_q=state.post_q,
                          queue_cap=effective_queue_cap(sp, ep),
                          slo_s=ep.slo_s)


def _joined(states):
    """Per-stage (C, ...) states as one (A, ...) state, stage-major."""
    if len(states) == 1:
        return states[0]
    return jax.tree.map(lambda *x: jnp.concatenate(x), *states)


def _stage_by_stage(pipe: Pipeline, state: SimState, arrivals, caps, obj,
                    phase, phase0):
    """The jnp route of an interval: every stage's K microticks, one stage
    after the other (``sim_interval_recorded``), each later stage admitting
    the fan-out of the per-tick completions one stage up. Returns the
    state, the phases, the requests routed in, and the per-tick counters
    (A, K, SIM_NCOUNTERS)."""
    rows = [pipe.rows(s) for s in range(pipe.stages)]
    parts, phases, routed, ticks = [], [phase0], [], []
    for s, r in enumerate(rows):
        if s > 0:
            with jax.named_scope("sim_fanout"):
                # objects per frame after the detector; one request per
                # completion between later stages
                fan = obj if s == 1 else jnp.ones_like(obj)
                arrivals, ph = fanout_arrivals(fan, done, phase[r])
            phases.append(ph)
            routed.append(arrivals.sum(axis=-1))
        with jax.named_scope("sim_advance"):
            st = jax.tree.map(lambda x: x[r], state)
            st2, tk = jax.vmap(sim_interval_recorded)(st, arrivals, caps[r])
            done = tick_completions(st, tk)
        parts.append(st2)
        ticks.append(tk)
    zero = jnp.zeros((pipe.cameras,), jnp.int32)
    return (_joined(parts), jnp.concatenate(phases),
            jnp.concatenate([zero] + routed), jnp.concatenate(ticks))


@partial(jax.jit, static_argnums=(0, 1, 2),
         static_argnames=("use_pallas", "record_ticks"))
def _simulate(cfg: FCPOConfig, sp: SimParams, pipe: Pipeline, params,
              masks: ActionMask, env_params: EnvParams, traces, objects,
              carry: SimCarry, use_pallas: bool = False,
              record_ticks: bool = False):
    a, n_stages, cams = pipe.agents, pipe.stages, pipe.cameras

    def interval(carry, xs):
        state, drops_prev, cur_action, phase, routed_prev, rng = carry
        rate, obj = xs
        rng, k = jax.random.split(rng)
        with jax.named_scope("sim_policy"):
            # a later stage observes its own arrivals of the last interval:
            # this interval's are not known before its upstream has run
            obs_rate = rate if n_stages == 1 else jnp.concatenate(
                [rate, routed_prev[cams:].astype(jnp.float32)
                 / sp.interval_s])
            obs = jax.vmap(
                lambda e, s, d, ca, r: sim_observe(cfg, sp, e, s, d, ca, r)
            )(env_params, state, drops_prev, cur_action, obs_rate)
            actions, logp, _ = jax.vmap(
                lambda p, o, m, kk: sample_actions(cfg, p, o, m, kk)
            )(params, obs, masks, jax.random.split(k, a))
            caps = jax.vmap(
                lambda e, ac: action_caps(cfg, sp, e, ac))(env_params,
                                                           actions)
        with jax.named_scope("sim_fanout"):
            arrivals, phase0 = jax.vmap(
                lambda r, ph: spread_arrivals(sp, r, ph))(rate, phase[:cams])
        ticks = None
        if n_stages == 1 and not record_ticks:
            with jax.named_scope("sim_advance"):
                state2 = sim_interval(state, arrivals, caps, use_pallas)
            phase, routed = phase0, jnp.zeros_like(routed_prev)
        elif use_pallas:
            # every stage in one kernel call, the fan-out inside it
            with jax.named_scope("sim_fanout"):
                arrivals = jnp.concatenate(
                    [arrivals, jnp.zeros((a - cams, sp.k_ticks), jnp.int32)])
                fan = jnp.concatenate([jnp.zeros_like(obj), obj]
                                      + [jnp.ones_like(obj)] * (n_stages - 2))
                phase = jnp.concatenate([phase0, phase[cams:]])
            with jax.named_scope("sim_advance"):
                *out, phase = kops.pipeline_advance(
                    *state, arrivals, caps, fan, phase, stages=n_stages)
                state2 = SimState(*out)
            routed = (state2.arrived - state.arrived).at[:cams].set(0)
        else:
            state2, phase, routed, ticks = _stage_by_stage(
                pipe, state, arrivals, caps, obj, phase, phase0)

        d_comp = (state2.completed - state.completed).astype(jnp.float32)
        d_drop = state2.dropped - state.dropped
        ys = {
            "throughput": d_comp / sp.interval_s,
            "effective_throughput":
                (state2.effective - state.effective).astype(jnp.float32)
                / sp.interval_s,
            "drops": d_drop.astype(jnp.float32),
            "routed": routed,
            "latency": (state2.lat_sum - state.lat_sum)
                / jnp.maximum(d_comp, 1.0) * sp.dt,
            "pre_q": state2.pre_q.astype(jnp.float32),
            "post_q": state2.post_q.astype(jnp.float32),
            "actions": actions,
            "logp": logp,
        }
        if record_ticks:
            # (A, K, SIM_NCOUNTERS) int32, and the caps (A, SIM_NCAPS) —
            # slo at the tick
            ys["tick_counters"] = ticks
            ys["caps"] = caps
        return (state2, d_drop, actions, phase, routed, rng), ys

    carry, history = jax.lax.scan(interval, tuple(carry),
                                  (traces.T, objects.T))
    return SimCarry(*carry), history


def simulate_fleet(cfg: FCPOConfig, sp: SimParams, params,
                   masks: ActionMask, env_params: EnvParams, traces, key,
                   use_pallas: bool = False, record_ticks: bool = False, *,
                   pipeline: Optional[Pipeline] = None, objects=None,
                   carry: Optional[SimCarry] = None) -> SimRun:
    """Drive a fleet of trained policies through the request-level twin.

    params/masks/env_params: agent-stacked (A, ...) pytrees (e.g. a trained
    ``Fleet``'s ``astate.params`` / ``masks`` / ``env_params``); traces:
    (C, T) control-interval frame rates (requests/s) of the C cameras.
    ``pipeline`` (default: one stage, C = A) lays the agents out as C
    chains of S stages, stage-major; a stage after the first admits its
    upstream agent's completions fanned out by ``objects`` ((C, T) objects
    per frame; needed when S > 1). ``carry``: continue an evaluation from
    a previous call's ``SimRun.carry`` (``key`` is then not used); without
    it the twin starts empty with sampling key ``key``. Returns a
    ``SimRun``: the final SimState (A, ...), the per-interval history
    (host arrays of (T, A): throughput, drops, routed, latency, queues,
    actions (T, A, 3), the chosen action's log-probability ``logp``), the
    per-agent request-grade summary incl. p50/p99 latency, and the carry.

    ``record_ticks``: additionally emit the per-microtick counter series
    (``history["tick_counters"]``: (T, A, K, SIM_NCOUNTERS) int32) and the
    held interval caps (``history["caps"]``) — the raw material
    ``repro.obs.requests`` turns into per-request stage stamps. jnp oracle
    path only (the fused Pallas kernel advances a whole interval per call,
    so there is no per-tick state to observe); the carried twin state is
    bit-identical to the unrecorded run.

    Under the JAX profiler the call lands as the host spans ``sim.prep``
    (the input checks and the ring-capacity check, which fetches the queue
    caps), ``sim.call`` (the jitted call's dispatch), ``sim.fetch`` (the
    history's fetch, which waits for the device) and ``sim.summary`` (the
    request-grade summary and its censoring check), and the device ops
    under the scopes ``sim_policy``, ``sim_fanout`` and ``sim_advance``."""
    if record_ticks and use_pallas:
        raise ValueError("record_ticks requires the jnp oracle path "
                         "(use_pallas=False): the fused kernel has no "
                         "per-tick state to record")
    with TraceAnnotation("sim.prep"):
        traces = jnp.asarray(traces, jnp.float32)
        pipe = pipeline or Pipeline(traces.shape[0])
        if traces.shape[0] != pipe.cameras:
            raise ValueError(f"traces hold {traces.shape[0]} cameras, the "
                             f"pipeline {pipe.cameras}")
        if objects is None:
            if pipe.stages > 1:
                raise ValueError("a pipeline of several stages needs the "
                                 "objects per frame")
            objects = jnp.ones_like(traces)
        objects = jnp.asarray(objects, jnp.float32)
        if objects.shape != traces.shape:
            raise ValueError(f"objects {objects.shape} and traces "
                             f"{traces.shape} differ in shape")
        warn_if_ring_clamps(sp, jax.device_get(env_params.queue_cap),
                            stacklevel=2)
        if carry is None:
            carry = sim_start(sp, pipe.agents, key)
    with TraceAnnotation("sim.call"):
        carry, history = _simulate(cfg, sp, pipe, params, masks, env_params,
                                   traces, objects, carry,
                                   use_pallas=use_pallas,
                                   record_ticks=record_ticks)
    with TraceAnnotation("sim.fetch"):
        history = jax.device_get(history)
    with TraceAnnotation("sim.summary"):
        summary = sim_metrics.summarize(carry.state, sp)
        sim_metrics.warn_if_censored(summary, sp, stacklevel=3)
    return SimRun(carry.state, history, summary, carry)


def eval_fleet(cfg: FCPOConfig, sp: SimParams, fleet, traces, key,
               use_pallas: bool = False, record_ticks: bool = False, *,
               pipeline: Optional[Pipeline] = None, objects=None,
               carry: Optional[SimCarry] = None) -> SimRun:
    """``simulate_fleet`` for a trained fleet object: reads the stacked
    policy/mask/device-profile leaves off anything Fleet-shaped
    (``.astate.params`` / ``.masks`` / ``.env_params`` — duck-typed, so this
    module never imports ``core.fleet``). The one request-grade evaluation
    entry the leaderboard (``repro.eval``) and the benchmarks share."""
    return simulate_fleet(cfg, sp, fleet.astate.params, fleet.masks,
                          fleet.env_params, traces, key,
                          use_pallas=use_pallas, record_ticks=record_ticks,
                          pipeline=pipeline, objects=objects, carry=carry)
