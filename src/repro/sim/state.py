"""State layout + action decode for the request-level data-plane twin.

A ``SimState`` is ONE agent's discrete-event pipeline (stack the leaves to
(A, ...) for a fleet): a power-of-two ring of arrival microticks plus the
monotone stage counters, token-bucket service credits, and request-grade
accumulators defined in ``repro.kernels.ref`` (the shared microtick math).
Stage membership is positional — each pipeline stage's occupants are a
contiguous ring segment between two counters — so queue lengths are counter
differences and a request's deadline is ``arrive + slo_ticks``. A request
is one unit of its model's work: a frame for a detector, one object crop
for a model downstream of it.

A ``Pipeline`` chains models: ``cameras`` feed-forward chains of ``stages``
agents each, every agent its own ``SimState``. Stage 0 admits the camera's
frames (``spread_arrivals`` of the trace rate); a later stage admits what
its upstream agent completed, fanned out by the objects per frame
(``fanout_arrivals``). See docs/architecture.md, "Pipelines".

``action_caps`` decodes an iAgent action (RES, BS, MT) into the per-tick
service capacities of the twin with the SAME formulas as the fluid
``core/env.py`` MDP (contention, frame packing, the t0 + t1·bs·area batch
curve), which is what makes fluid-vs-twin fidelity checks meaningful.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fcpo import FCPOConfig
from repro.core.env import EnvParams, default_env_params
from repro.kernels import ref as kref


@dataclass(frozen=True)
class Pipeline:
    """Static layout of a fleet of feed-forward model chains (hashable — a
    jit static argument): ``cameras`` chains of ``stages`` models, one
    iAgent per model. Agents are stage-major: agent ``s * cameras + c``
    runs stage ``s`` of camera ``c``, so each stage is one contiguous
    slice of the agent axis. ``Pipeline(a)`` is a fleet of ``a`` single
    models."""
    cameras: int
    stages: int = 1

    def __post_init__(self):
        if self.cameras < 1 or self.stages < 1:
            raise ValueError(f"a pipeline needs >= 1 camera and stage, got "
                             f"{self.cameras} x {self.stages}")

    @property
    def agents(self) -> int:
        return self.cameras * self.stages

    def rows(self, stage: int) -> slice:
        """The agents of ``stage``."""
        return slice(stage * self.cameras, (stage + 1) * self.cameras)


@dataclass(frozen=True)
class SimParams:
    """Static twin geometry (hashable — a jit static argument)."""
    dt: float = 0.05     # microtick length (s); k_ticks*dt = control interval
    k_ticks: int = 20    # microticks per control interval (1 s in the paper)
    ring: int = 512      # ring capacity; power of two, >= 3 * queue_cap
    hist_n: int = 64     # latency histogram buckets (ticks)

    def __post_init__(self):
        assert self.ring > 0 and self.ring & (self.ring - 1) == 0, \
            "ring capacity must be a positive power of two"
        assert self.k_ticks >= 1 and self.hist_n >= 2

    @property
    def interval_s(self) -> float:
        return self.k_ticks * self.dt


class SimState(NamedTuple):
    """Per-agent twin state; all views below work batched (A, ...)."""
    arrive: jnp.ndarray    # (R,) int32 — arrival microtick per ring slot
    counters: jnp.ndarray  # (SIM_NCOUNTERS,) int32 — pointers + accumulators
    credits: jnp.ndarray   # (2,) float32 — pre/post fractional service credit
    lat_sum: jnp.ndarray   # () float32 — summed completed latency (ticks)
    hist: jnp.ndarray      # (H,) int32 — completed-latency histogram (ticks)

    # queue lengths are differences of the monotone stage counters
    @property
    def pre_q(self):
        return (self.counters[..., kref.SIM_TAIL]
                - self.counters[..., kref.SIM_PPRE])

    @property
    def batch_q(self):
        return (self.counters[..., kref.SIM_PPRE]
                - self.counters[..., kref.SIM_LAUNCH])

    @property
    def post_q(self):
        return (self.counters[..., kref.SIM_PINF]
                - self.counters[..., kref.SIM_HEAD])

    @property
    def in_flight(self):
        return (self.counters[..., kref.SIM_TAIL]
                - self.counters[..., kref.SIM_HEAD])

    @property
    def arrived(self):
        return self.counters[..., kref.SIM_ARRIVED]

    @property
    def dropped(self):
        return self.counters[..., kref.SIM_DROPPED]

    @property
    def completed(self):
        return self.counters[..., kref.SIM_COMPLETED]

    @property
    def effective(self):
        return self.counters[..., kref.SIM_EFFECTIVE]

    @property
    def tick(self):
        return self.counters[..., kref.SIM_TICK]


def sim_init(sp: SimParams) -> SimState:
    """One agent's empty pipeline (vmap over a dummy axis for a fleet)."""
    return SimState(
        arrive=jnp.zeros((sp.ring,), jnp.int32),
        counters=jnp.zeros((kref.SIM_NCOUNTERS,), jnp.int32),
        credits=jnp.zeros((2,), jnp.float32),
        lat_sum=jnp.zeros((), jnp.float32),
        hist=jnp.zeros((sp.hist_n,), jnp.int32),
    )


def effective_queue_cap(sp: SimParams, ep: EnvParams) -> jnp.ndarray:
    """Per-stage queue capacity, clamped so the ring can never overflow
    (each of the three stage queues is bounded by it)."""
    return jnp.minimum(ep.queue_cap, float(sp.ring // 3))


def warn_if_ring_clamps(sp: SimParams, queue_cap, stacklevel: int = 2) -> None:
    """THE host-side guard on the ``effective_queue_cap`` clamp (one
    definition for the evaluation harness and the training backend): warn
    when the ring cannot hold 3x the device queue_cap, because the clamp
    then changes twin dynamics, observation normalization, and — during
    twin-backed training — ``fl_round``'s Eq. 7 memory-availability stat
    (which normalizes ``pre_q`` by the *unclamped* cap). Call on concrete
    params, never under ``jit``."""
    qcap = np.asarray(queue_cap)
    if (qcap > sp.ring // 3).any():
        warnings.warn(
            f"SimParams.ring={sp.ring} clamps queue_cap "
            f"{float(qcap.max()):.0f} -> {sp.ring // 3} (ring must be >= "
            f"3*queue_cap); twin dynamics, observation normalization, and "
            f"the Eq. 7 memory-availability stat (twin-backed training) "
            f"will differ from the fluid env — raise `ring` to match the "
            f"device profile", stacklevel=stacklevel)


def action_caps(cfg: FCPOConfig, sp: SimParams, ep: EnvParams,
                action: jnp.ndarray) -> jnp.ndarray:
    """Decode one agent's (RES, BS, MT) action into a (SIM_NCAPS,) float32
    caps vector for the microtick kernel — same latency surface as
    ``core.env.env_step`` (mt contention, 1/area frame packing,
    t_batch = t0 + t1·bs·area), discretized to ticks."""
    res_scale = jnp.asarray(cfg.res_scales)[action[..., 0]]
    bs = jnp.asarray(cfg.bs_values, jnp.float32)[action[..., 1]]
    mt = jnp.asarray(cfg.mt_values, jnp.float32)[action[..., 2]]

    area = res_scale ** 2
    mt_eff = mt * jnp.maximum(1.0 - ep.contention * (mt - 1.0), 0.3)
    rate_pre = ep.pre_rate * mt_eff / jnp.maximum(area, 0.05)
    rate_post = ep.post_rate * mt_eff
    t_batch_s = ep.t0 + ep.t1 * bs * area

    return jnp.stack([
        rate_pre * sp.dt,
        rate_post * sp.dt,
        jnp.maximum(jnp.round(bs / area), 1.0),      # requests per batch
        jnp.maximum(jnp.ceil(t_batch_s / sp.dt), 1.0),
        jnp.round(effective_queue_cap(sp, ep)),
        jnp.maximum(jnp.round(ep.slo_s / sp.dt), 1.0),
    ]).astype(jnp.float32)


def spread_arrivals(sp: SimParams, rate, phase=0.0):
    """Deterministic per-tick arrival counts for one control interval.

    Cumulative-floor spreading of ``rate`` requests/s over k_ticks, with
    ``phase`` carrying the fractional request left over from previous
    intervals — so a steady 30.9 req/s admits 30.9 requests/s on average
    instead of a permanent floor(rate) deficit. Returns ((K,) int32 counts,
    new phase in [0, 1)); the interval total is
    floor(phase + rate * k_ticks * dt)."""
    phase = jnp.asarray(phase, jnp.float32)
    j = jnp.arange(1 + sp.k_ticks, dtype=jnp.float32)
    cum = jnp.floor(phase + rate * sp.dt * j)
    counts = (cum[1:] - cum[:-1]).astype(jnp.int32)
    end = phase + rate * sp.dt * sp.k_ticks
    return counts, end - jnp.floor(end)


def fanout_arrivals(objects, completions, phase):
    """Per-tick arrivals of a pipeline stage from its upstream agent's
    per-tick completions (late ones included: a late frame's objects are
    still processed).

    objects: (...) float32 — requests each upstream completion sends on
    (the camera's objects per frame for the stage after the detector);
    completions: (..., K) int32; phase: (...) float32 in [0, 1), the
    fraction of a request carried from earlier intervals. With
    cum(k) = phase + objects * sum_{j<=k} completions(j) and
    cum(-1) = phase, the arrivals at tick k are
    floor(cum(k)) - floor(cum(k-1)), and the new phase is
    cum(K-1) - floor(cum(K-1)). Returns ((..., K) int32, new phase).

    Arrivals enter the downstream ring in the tick of the completion that
    sent them. Every quantity is exact in float32 when ``objects`` is a
    multiple of 2**-8 and each interval's cum stays below 2**16, so every
    backend then floors the same numbers."""
    phase = jnp.asarray(phase, jnp.float32)
    done = jnp.cumsum(completions, axis=-1).astype(jnp.float32)
    cum = phase[..., None] + jnp.asarray(objects, jnp.float32)[..., None] \
        * done
    floors = jnp.floor(jnp.concatenate([phase[..., None], cum], axis=-1))
    counts = (floors[..., 1:] - floors[..., :-1]).astype(jnp.int32)
    end = cum[..., -1]
    return counts, end - jnp.floor(end)


def pipeline_env_params(pipe: Pipeline, speeds, stage_speed,
                        stage_slo_s) -> EnvParams:
    """Per-agent device profiles of a pipeline fleet, stage-major: agent
    ``s * cameras + c`` runs at ``speeds[c] * stage_speed[s]`` (the
    camera's device, scaled by how much lighter stage ``s``'s model is)
    with the deadline ``stage_slo_s[s]``, counted from its own arrival."""
    if len(stage_speed) != pipe.stages or len(stage_slo_s) != pipe.stages:
        raise ValueError(f"need one speed factor and one SLO per stage "
                         f"({pipe.stages}), got {len(stage_speed)} and "
                         f"{len(stage_slo_s)}")
    speeds = jnp.asarray(speeds, jnp.float32)
    agent_speed = jnp.concatenate([speeds * f for f in stage_speed])
    agent_slo = jnp.repeat(jnp.asarray(stage_slo_s, jnp.float32),
                           pipe.cameras)
    return jax.vmap(default_env_params)(agent_speed, agent_slo)
