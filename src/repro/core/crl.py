"""Continual RL driver (§IV-C): episode rollout + gated online update.

``run_episode`` scans ``n_steps`` control intervals: observe -> sample
cascaded actions -> env step, all through a pluggable ``EnvBackend``
(``core.backends``): the fluid MDP (default) or the request-level twin,
whose control-interval step nests K data-plane microticks — same episode
loop, same scanned fleet driver, "train where you serve". The
diversity-buffer maintenance is hoisted
OUT of the scan body: the buffer is write-only during a rollout, so the
whole episode's candidates are ingested after the scan with ONE
``buffer_insert_batch`` call through the streaming-moment engine — the scan
body stays env+policy only and the per-step O(N·D²+D³) covariance rebuild of
the old insert path disappears from the hot loop (benchmarks/
fig_buffer_perf.py measures the A/B). ``crl_episode`` additionally performs
the online update from the episode rollout through the loss gate. Everything
is a pure function of (params, opt, buffer, env_state, rng) so a fleet of
agents is just a ``vmap`` over stacked states.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.fcpo import FCPOConfig
from repro.core import dtypes as dtp
from repro.core import env as env_mod
from repro.core.agent import ActionMask, sample_actions
from repro.core.backends import FLUID, EnvBackend
from repro.core.buffer import (DiversityBuffer, buffer_insert_batch,
                               buffer_insert_reference)
from repro.core.ppo import Rollout, agent_update


class AgentState(NamedTuple):
    params: Any
    opt: Any
    buffer: DiversityBuffer
    env_state: env_mod.EnvState
    rng: jnp.ndarray


def run_episode(cfg: FCPOConfig, ep: env_mod.EnvParams, astate: AgentState,
                rates: jnp.ndarray, mask: ActionMask,
                use_pallas: bool = False, backend: EnvBackend = FLUID,
                health: bool = False
                ) -> Tuple[AgentState, Rollout, Dict[str, jnp.ndarray]]:
    """Collect one episode (rates: (n_steps,) arrivals per interval).

    The buffer never feeds back into the policy or env within an episode, so
    the scan collects the candidate experiences and a single
    ``buffer_insert_batch`` ingests them afterwards — trajectory-identical to
    per-step inserts (tests/test_buffer.py) but with the diversity scoring
    off the step critical path. ``use_pallas`` routes the batch insert
    through the fused Pallas kernel instead of the jnp streaming scan.
    ``backend`` selects the environment (``core.backends``): the fluid MDP
    or the request-level twin; ``astate.env_state`` must be that backend's
    state pytree (``fleet_init(..., env_backend=...)``). ``health`` adds a
    ``"_health"`` entry of raw per-interval telemetry ((T,)/(T, K) arrays:
    reward, SLO-miss rate, action marginals, arrival rate) to the metrics
    for the fleet health observatory — the scalar metrics and every other
    output are unchanged, so health-off stages the identical program."""

    def step(carry, rate):
        est, rng = carry
        rng, krng = jax.random.split(rng)
        # Observations/rewards enter the learner in float32 even when the
        # carried env state is stored bf16 (StatePolicy.env); the stepped
        # state is cast back to the carry's storage dtypes so the scan
        # carry stays dtype-stable. All identities under the f32 default.
        obs = backend.observe(cfg, ep, est, rate).astype(jnp.float32)
        actions, logp, out = sample_actions(cfg, astate.params, obs, mask, krng)
        est2, reward, info = backend.step(cfg, ep, est, actions, rate)
        est2 = dtp.tree_cast_like(est2, est)
        reward = reward.astype(jnp.float32)
        info = dtp.tree_f32(info)
        probs = jnp.concatenate([jnp.exp(out["res"]), jnp.exp(out["bs"]),
                                 jnp.exp(out["mt"])], axis=-1)
        ys = (obs, actions, logp, reward, out["value"], probs, info)
        return (est2, rng), ys

    # profiler scopes: the benchmark's per-layer readers match these names
    # in the device ops' name stacks (bench/layers/{rollout,buffer}_ms.py)
    with jax.named_scope("fcpo_rollout"):
        (env_state, rng), ys = jax.lax.scan(
            step, (astate.env_state, astate.rng), rates)
    obs, actions, logp, rewards, values, probs, infos = ys
    with jax.named_scope("fcpo_buffer"):
        buffer = buffer_insert_batch(cfg, astate.buffer, obs, actions, logp,
                                     rewards, values, probs,
                                     use_pallas=use_pallas)
    rollout = Rollout(states=obs, actions=actions, logp_old=logp,
                      rewards=rewards, values_old=values)
    metrics = {
        "reward": rewards.mean(),
        "throughput": infos["throughput"].mean(),
        "effective_throughput": infos["effective_throughput"].mean(),
        "latency": infos["latency"].mean(),
        "drops": infos["drops"].mean(),
        "accuracy_proxy": infos["accuracy_proxy"].mean(),
    }
    if health:
        thr = infos["throughput"]
        miss = (thr - infos["effective_throughput"]) / jnp.maximum(thr, 1e-9)
        metrics["_health"] = {"reward": rewards, "miss": miss,
                              "probs": probs, "rate": rates}
    new_state = AgentState(astate.params, astate.opt, buffer, env_state, rng)
    return new_state, rollout, metrics


def run_episode_reference(cfg: FCPOConfig, ep: env_mod.EnvParams,
                          astate: AgentState, rates: jnp.ndarray,
                          mask: ActionMask, backend: EnvBackend = FLUID
                          ) -> Tuple[AgentState, Rollout,
                                     Dict[str, jnp.ndarray]]:
    """The seed episode loop: per-step recompute-oracle buffer inserts
    sequentially inside the scan. Kept as the equivalence oracle for the
    restructured ``run_episode`` (tests/test_buffer.py) and the A/B baseline
    for benchmarks/fig_buffer_perf.py — one definition so both measure the
    same loop."""

    def step(carry, rate):
        est, buf, rng = carry
        rng, krng = jax.random.split(rng)
        # Same dtype discipline as run_episode: f32 into the learner, env
        # carry cast back to its storage dtypes (no-ops under f32 default).
        obs = backend.observe(cfg, ep, est, rate).astype(jnp.float32)
        actions, logp, out = sample_actions(cfg, astate.params, obs, mask, krng)
        est2, reward, info = backend.step(cfg, ep, est, actions, rate)
        est2 = dtp.tree_cast_like(est2, est)
        reward = reward.astype(jnp.float32)
        info = dtp.tree_f32(info)
        probs = jnp.concatenate([jnp.exp(out["res"]), jnp.exp(out["bs"]),
                                 jnp.exp(out["mt"])], axis=-1)
        buf = buffer_insert_reference(cfg, buf, obs, actions, logp, reward,
                                      out["value"], probs)
        ys = (obs, actions, logp, reward, out["value"], info)
        return (est2, buf, rng), ys

    (env_state, buffer, rng), ys = jax.lax.scan(
        step, (astate.env_state, astate.buffer, astate.rng), rates)
    obs, actions, logp, rewards, values, infos = ys
    rollout = Rollout(states=obs, actions=actions, logp_old=logp,
                      rewards=rewards, values_old=values)
    metrics = {
        "reward": rewards.mean(),
        "throughput": infos["throughput"].mean(),
        "effective_throughput": infos["effective_throughput"].mean(),
        "latency": infos["latency"].mean(),
        "drops": infos["drops"].mean(),
        "accuracy_proxy": infos["accuracy_proxy"].mean(),
    }
    new_state = AgentState(astate.params, astate.opt, buffer, env_state, rng)
    return new_state, rollout, metrics


def crl_episode(cfg: FCPOConfig, ep: env_mod.EnvParams, astate: AgentState,
                rates: jnp.ndarray, mask: ActionMask, learn: bool = True,
                backend: EnvBackend = FLUID, health: bool = False
                ) -> Tuple[AgentState, Rollout, Dict[str, jnp.ndarray]]:
    """Episode + gated online update (the CRL inner loop)."""
    astate, rollout, metrics = run_episode(cfg, ep, astate, rates, mask,
                                           backend=backend, health=health)
    if learn:
        with jax.named_scope("fcpo_update"):
            params, opt, lm = agent_update(cfg, astate.params, astate.opt,
                                           rollout, mask)
        astate = astate._replace(params=params, opt=opt)
        metrics = {**metrics, **lm}
    else:
        metrics = {**metrics, "loss": jnp.zeros(()), "l_p": jnp.zeros(()),
                   "l_v": jnp.zeros(()), "l_pen": jnp.zeros(()),
                   "gated": jnp.ones(()), "update_rejected": jnp.zeros(())}
    return astate, rollout, metrics
