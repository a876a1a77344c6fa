"""Fleet health observatory: learning-dynamics state inside the jitted scan.

Health is an *optional* field of the ``Fleet`` pytree. ``None`` (the
default) flattens to an empty subtree, so disabled runs stage the exact
health-free program — bit-identical histories, unchanged golden tests,
unchanged donation audit. Enabled, the state is a ``HealthState`` of
agent-leading float32 leaves updated by pure pytree ops (no host callbacks
on the hot path):

* per-episode, inside ``run_episode``'s metrics tail: telemetry sketches
  (``sketch.py``) + drift detectors (``drift.py``) consume the episode's
  per-interval telemetry (batched sketch updates + a vmapped-over-agents
  detector ``lax.scan``);
* per-``fl_round``: contribution attribution (``attribution.py``) scores
  each selected client's wire delta and folds it into a suspicion EMA
  that ``resilience/guards.py`` can gate selection on;
* per-episode, host-side: O(bins) summaries ride the existing metrics
  stream, where ``alerts.py`` evaluates declarative rules into
  ``ALERTS.jsonl`` and ``launch/watch.py`` renders them live.

``HealthConfig`` is a frozen dataclass threaded through the drivers as a
jit-static argument, like ``TransportConfig``/``FaultConfig``/
``GuardConfig``: presence means on, ``None`` means off.

The episode update is engineered for the <=5% overhead budget
(benchmarks/fig_health.py gates it): the order-independent sketches
(histogram counts, action marginals) consume every interval through
batched scatter-adds/reductions OUTSIDE the sequential path, and only the
inherently sequential detectors (the P² marker and the CUSUM/Page-Hinkley
channels) run in a ``lax.scan`` — over ``stride``-mean samples, so the
scan is ``n_steps / stride`` long instead of ``n_steps``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.health.attribution import attribution_scores
from repro.health.drift import (DriftState, drift_init, drift_reset_episode,
                                drift_update)
from repro.health.sketch import (P2State, hist_init, hist_merge,
                                 hist_quantile, hist_update,
                                 hist_update_batch, p2_init, p2_update,
                                 p2_value)

__all__ = [
    "HealthConfig", "HealthState", "DEFAULT_HEALTH", "HEALTH_METRIC_KEYS",
    "health_init", "update_episode", "episode_summaries", "update_round",
    "attribution_scores", "DriftState", "P2State", "hist_merge",
]


@dataclass(frozen=True)
class HealthConfig:
    """Jit-static knob block for the observatory. ``bins``: histogram
    resolution (quantile error <= one bin width); ``cusum_k``/``cusum_h``
    and ``ph_delta``/``ph_lambda``: detector thresholds (defaults sized so
    an i.i.d. stream false-alarms with probability ~exp(-2kh) ~ 5e-5 per
    run); ``stride``: intervals per detector sample — the sequential
    detectors consume ``stride``-mean telemetry, which shortens the
    in-scan sequential chain by that factor (``n_steps`` must be a
    multiple); ``warmup``: detector *samples* (not intervals) before the
    detectors arm; ``susp_beta``: EMA weight on the newest round's
    attribution score."""
    bins: int = 16
    stride: int = 10
    reward_lo: float = -1.0
    reward_hi: float = 1.0
    cusum_k: float = 0.5
    cusum_h: float = 10.0
    ph_delta: float = 0.2
    ph_lambda: float = 25.0
    ema_slow: float = 0.02
    ema_fast: float = 0.3
    warmup: int = 10
    zclip: float = 8.0
    var_floor: float = 1e-3
    susp_beta: float = 0.5

    def __post_init__(self):
        if self.bins < 2:
            raise ValueError("bins must be >= 2")
        if self.reward_hi <= self.reward_lo:
            raise ValueError("reward_hi must exceed reward_lo")
        for name in ("cusum_k", "cusum_h", "ph_delta", "ph_lambda",
                     "zclip", "var_floor"):
            if getattr(self, name) <= 0.0:
                raise ValueError(f"{name} must be > 0")
        for name in ("ema_slow", "ema_fast", "susp_beta"):
            if not (0.0 < getattr(self, name) <= 1.0):
                raise ValueError(f"{name} must be in (0, 1]")
        if self.warmup < 1:
            raise ValueError("warmup must be >= 1")
        if self.stride < 1:
            raise ValueError("stride must be >= 1")


DEFAULT_HEALTH = HealthConfig()

# Per-episode summary keys merged into the metrics stream (all (A,) on
# device, fleet-reduced by the drivers like every other episode metric).
HEALTH_METRIC_KEYS = (
    "health_reward_p50", "health_reward_p10", "health_reward_p90",
    "health_miss_p90", "health_act_entropy", "health_drift_score",
    "health_drift_flag", "health_susp",
)


class HealthState(NamedTuple):
    """All leaves agent-leading float32 — sharded by the same
    ``agent_spec`` rule as every other per-agent fleet leaf, donated with
    the rest of the fleet state."""
    reward_hist: jnp.ndarray   # (A, bins)
    miss_hist: jnp.ndarray     # (A, bins)
    reward_p2: P2State         # leaves (A, 5) / (A,)
    act_sum: jnp.ndarray       # (A, K) running sum of action marginals
    n_obs: jnp.ndarray         # (A,) intervals observed
    drift_reward: DriftState   # leaves (A,)
    drift_rate: DriftState     # leaves (A,)
    susp: jnp.ndarray          # (A,) attribution suspicion EMA
    susp_last: jnp.ndarray     # (A,) raw suspicion from the last FL round
    sel_last: jnp.ndarray      # (A,) selection mask at that round


def health_init(hcfg: HealthConfig, n_agents: int,
                n_actions: int) -> HealthState:
    def bcast(x):
        return jnp.broadcast_to(x, (n_agents,) + jnp.shape(x)).copy()
    zeros = jnp.zeros((n_agents,), jnp.float32)
    return HealthState(
        reward_hist=jnp.zeros((n_agents, hcfg.bins), jnp.float32),
        miss_hist=jnp.zeros((n_agents, hcfg.bins), jnp.float32),
        reward_p2=jax.tree.map(bcast, p2_init(0.5)),
        act_sum=jnp.zeros((n_agents, n_actions), jnp.float32),
        n_obs=zeros,
        drift_reward=jax.tree.map(bcast, drift_init()),
        drift_rate=jax.tree.map(bcast, drift_init()),
        susp=zeros, susp_last=zeros, sel_last=zeros)


def _detector_kwargs(hcfg: HealthConfig) -> dict:
    return dict(k=hcfg.cusum_k, h=hcfg.cusum_h, ph_delta=hcfg.ph_delta,
                ph_lambda=hcfg.ph_lambda, ema_slow=hcfg.ema_slow,
                ema_fast=hcfg.ema_fast, warmup=hcfg.warmup,
                zclip=hcfg.zclip, var_floor=hcfg.var_floor)


def update_episode(hcfg: HealthConfig, state: HealthState, reward, miss,
                   probs, rate) -> HealthState:
    """Advance every agent's sketches and detectors through one episode of
    per-interval telemetry. ``reward``/``miss``/``rate``: (A, T);
    ``probs``: (A, T, K). Engineered for the overhead budget: histogram
    counts and action marginals commute, so the full episode lands in two
    batched scatter-adds and one reduction; only the order-dependent
    detectors scan — over ``stride``-mean samples, with the two drift
    channels stepping as ONE stacked (2,)-leaf update. Everything stays
    inside the compiled program."""
    dk = _detector_kwargs(hcfg)
    t = reward.shape[1]
    s = hcfg.stride
    if t % s != 0:
        raise ValueError(
            f"episode length {t} is not a multiple of HealthConfig.stride="
            f"{s}; pick a stride that divides cfg.n_steps")

    def per_agent(st: HealthState, r, m, p, ra) -> HealthState:
        st = st._replace(
            reward_hist=hist_update_batch(st.reward_hist, r,
                                          hcfg.reward_lo, hcfg.reward_hi),
            miss_hist=hist_update_batch(st.miss_hist, m, 0.0, 1.0),
            act_sum=st.act_sum + jnp.sum(p.astype(jnp.float32), axis=0),
            n_obs=st.n_obs + float(t),
            drift_reward=drift_reset_episode(st.drift_reward),
            drift_rate=drift_reset_episode(st.drift_rate))

        # the P² marker tracks the median of stride-mean reward (the raw-
        # sample quantiles live in the histogram sketch); the detectors
        # standardize per-sample, so the stride only trades detection
        # granularity, not sensitivity to sustained shifts
        rs = jnp.mean(r.reshape(t // s, s), axis=1)
        ras = jnp.mean(ra.reshape(t // s, s), axis=1)
        drift2 = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                              st.drift_reward, st.drift_rate)

        def step(carry, x):
            p2, d2 = carry
            r_t, ra_t = x
            return (p2_update(p2, r_t, 0.5),
                    drift_update(d2, jnp.stack([r_t, ra_t]), **dk)), None

        (p2, d2), _ = lax.scan(step, (st.reward_p2, drift2), (rs, ras))
        return st._replace(
            reward_p2=p2,
            drift_reward=jax.tree.map(lambda x: x[0], d2),
            drift_rate=jax.tree.map(lambda x: x[1], d2))

    return jax.vmap(per_agent)(state, reward, miss, probs, rate)


def episode_summaries(hcfg: HealthConfig, state: HealthState) -> dict:
    """O(bins) per-agent digests of the sketch/detector state — the (A,)
    arrays merged into the episode metrics (keys ``HEALTH_METRIC_KEYS``)."""
    def rq(p):
        return jax.vmap(lambda c: hist_quantile(
            c, p, hcfg.reward_lo, hcfg.reward_hi))(state.reward_hist)

    marg = state.act_sum / jnp.maximum(state.n_obs, 1.0)[:, None]
    pm = marg / jnp.maximum(jnp.sum(marg, axis=1, keepdims=True), 1e-9)
    entropy = -jnp.sum(pm * jnp.log(pm + 1e-9), axis=1)
    return {
        "health_reward_p50": jax.vmap(p2_value)(state.reward_p2),
        "health_reward_p10": rq(0.10),
        "health_reward_p90": rq(0.90),
        "health_miss_p90": jax.vmap(lambda c: hist_quantile(
            c, 0.90, 0.0, 1.0))(state.miss_hist),
        "health_act_entropy": entropy,
        "health_drift_score": jnp.maximum(state.drift_reward.score,
                                          state.drift_rate.score),
        "health_drift_flag": jnp.maximum(state.drift_reward.flag,
                                         state.drift_rate.flag),
        "health_susp": state.susp,
    }


def update_round(hcfg: HealthConfig, state: HealthState, susp_new,
                 sel) -> HealthState:
    """Fold one FL round's attribution scores into the suspicion EMA.
    Unselected clients keep their EMA (no evidence either way);
    ``susp_last``/``sel_last`` snapshot the raw round for benchmarks and
    the stream."""
    sel32 = sel.astype(jnp.float32)
    beta = hcfg.susp_beta
    ema = jnp.where(sel32 > 0,
                    (1.0 - beta) * state.susp + beta * susp_new,
                    state.susp)
    return state._replace(susp=ema, susp_last=susp_new * sel32,
                          sel_last=sel32)
