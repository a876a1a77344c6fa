"""Fleet driver: the full FCPO loop over a fleet of iAgents.

One fleet = stacked agent pytrees (A on the leading axis) + stacked env
params/states + per-pod base networks. The CRL inner loop is ``vmap``'d;
the FL round is Algorithm 1 over the stacked axis. Under the production
mesh the agent axis is sharded over ``data`` (and ``pod`` maps to the FL
hierarchy) via ``fleet_shardings``, making the entire federated-continual
system one SPMD program.

Two drivers:
  * ``train_fleet_scan`` — the production path: ONE jitted, donated
    ``lax.scan`` over episodes. The FL cadence (``fl_every``, the
    ``hierarchical_period`` pod merge, straggler masking from pre-drawn
    availability bits) lives inside the scanned body as ``lax.cond``s, and
    per-episode metrics accumulate as stacked device arrays — a whole
    training run is O(1) host dispatches instead of O(n_episodes).
  * ``train_fleet_reference`` — the original Python loop (one dispatch per
    episode, per-metric host syncs), kept as the equivalence oracle.
``train_fleet`` is the compatibility entry point and delegates to the scan
driver.
"""
from __future__ import annotations

from contextlib import nullcontext
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.fcpo import FCPOConfig
from repro.core import dtypes as dtp
from repro.core import env as env_mod
from repro.core import federated as fed
from repro.core.agent import ActionMask, agent_init, full_mask
from repro.core.backends import FLUID, EnvBackend, get_backend
from repro.core.buffer import (buffer_cast, buffer_diversity_mean,
                               buffer_init, buffer_resync)
from repro.core.crl import AgentState, crl_episode
from repro.core.ppo import agent_opt_init, finetune_heads
from repro.distributed import sharding as shd
from repro.fl import codec as fl_codec
from repro.fl import staleness as fl_stale
from repro.fl import transport as fl_transport
from repro.fl.transport import DEFAULT_TRANSPORT, TransportConfig
# the health observatory (repro.health) is a leaf layer:
# pure pytree state + jnp ops, imports nothing from core, so the sketch /
# drift / attribution updates stay inside the donated scan; health is a
# jit-static config and the default (None) keeps the Fleet pytree and the
# traced program exactly the pre-health ones
from repro.health import HealthConfig
from repro.health import attribution_scores as health_attribution
from repro.health import episode_summaries as health_summaries
from repro.health import health_init
from repro.health import update_episode as health_update_episode
from repro.health import update_round as health_update_round
from repro.resilience import faults as rfaults
from repro.resilience.faults import FaultConfig
from repro.resilience.guards import DEFAULT_GUARDS, GuardConfig
from repro.resilience.guards import clip_deltas as guard_clip_deltas
from repro.resilience.guards import finite_mask as guard_finite_mask


@jax.tree_util.register_pytree_node_class
class Fleet:
    """Stacked fleet state. ``n_pods`` and the head-group *counts* are static
    (pytree aux data); everything else is traced leaves."""

    FIELDS = ("astate", "base_params", "env_params", "masks", "group_ids",
              "pod_ids", "bandwidth", "speeds", "episode", "residuals",
              "pending", "crash_timer", "partition_timer", "health")

    def __init__(self, astate, base_params, env_params, masks, group_ids,
                 pod_ids, bandwidth, speeds, episode, residuals, pending,
                 crash_timer, partition_timer, health=None, *, n_pods,
                 group_counts):
        self.astate: AgentState = astate
        self.base_params = base_params
        self.env_params: env_mod.EnvParams = env_params
        self.masks: ActionMask = masks
        self.group_ids: Dict[str, jnp.ndarray] = group_ids  # per head key
        self.pod_ids = pod_ids
        self.bandwidth = bandwidth
        self.speeds = speeds
        self.episode = episode
        # FL transport state: per-agent error-feedback residuals of the
        # lossy delta codec, and the staleness buffer of parked uploads —
        # both live in the pytree so the whole transport path stays inside
        # the donated scan (zero host work per round).
        self.residuals = residuals
        self.pending: fl_stale.PendingDeltas = pending
        # Chaos layer state: per-agent crash-recovery countdown (episodes a
        # crashed agent stays down) and per-pod partition countdown (merge
        # events a partitioned pod skips) — in the pytree so fault injection
        # stays inside the donated scan. All-zeros when faults are off.
        self.crash_timer = crash_timer
        self.partition_timer = partition_timer
        # Health observatory state (repro.health.HealthState): per-agent
        # telemetry sketches, drift detectors, and attribution suspicion.
        # None (the default) flattens to an EMPTY subtree — the pytree, the
        # donation audit, and every traced program are bit-identical to
        # pre-health fleets.
        self.health = health
        self.n_pods: int = n_pods
        self.group_counts: Dict[str, int] = group_counts

    @property
    def head_groups(self) -> Dict[str, Any]:
        out: Dict[str, Any] = dict(self.group_ids)
        for k, v in self.group_counts.items():
            out[f"{k}_count"] = v
        return out

    def _replace(self, **kw) -> "Fleet":
        vals = {f: getattr(self, f) for f in self.FIELDS}
        vals.update(kw)
        return Fleet(**vals, n_pods=self.n_pods, group_counts=self.group_counts)

    def tree_flatten(self):
        leaves = tuple(getattr(self, f) for f in self.FIELDS)
        aux = (self.n_pods, tuple(sorted(self.group_counts.items())))
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_pods, gc = aux
        return cls(*leaves, n_pods=n_pods, group_counts=dict(gc))


def fleet_shardings(fleet: Fleet, mesh) -> Fleet:
    """A Fleet of ``NamedSharding``s mirroring ``fleet``: agent-stacked
    leaves over the mesh's (pod, data) / data axes, per-pod base networks
    over the FL hierarchy, the episode counter replicated. Indivisible dims
    fall through to replication (``greedy_spec``), so any fleet size works
    on any mesh."""
    agent = lambda x: NamedSharding(mesh, shd.agent_spec(jnp.shape(x), mesh))
    pod = lambda x: NamedSharding(mesh, shd.pod_spec(jnp.shape(x), mesh))
    vals = {}
    for f in Fleet.FIELDS:
        v = getattr(fleet, f)
        if f in ("base_params", "partition_timer"):
            vals[f] = jax.tree.map(pod, v)
        elif f == "episode":
            vals[f] = NamedSharding(mesh, P())
        else:
            vals[f] = jax.tree.map(agent, v)
    return Fleet(**vals, n_pods=fleet.n_pods, group_counts=fleet.group_counts)


def fleet_cast(fleet: Fleet, state_policy) -> Fleet:
    """Cast the fleet's state families to a ``repro.core.dtypes.StatePolicy``
    (name / instance / None -> float32). Storage-only: every training path
    computes in float32 and writes back at the stored leaf dtype, so the
    policy is fully encoded in the leaves — no static flags, no retrace keys
    beyond the dtype change itself. Casting to ``"float32"`` recovers a
    full-precision fleet from a lean one (int8 buffer slots dequantize)."""
    pol = dtp.get_policy(state_policy)
    astate = fleet.astate
    opt = dict(fleet.astate.opt)
    opt["m"] = dtp.cast_floats(opt["m"], pol.opt)
    opt["v"] = dtp.cast_floats(opt["v"], pol.opt)
    astate = astate._replace(
        params=dtp.cast_floats(astate.params, pol.model),
        opt=opt,
        buffer=buffer_cast(astate.buffer, pol.buffer),
        env_state=dtp.cast_floats(astate.env_state, pol.env),
    )
    return fleet._replace(
        astate=astate,
        base_params=dtp.cast_floats(fleet.base_params, pol.model),
        env_params=dtp.cast_floats(fleet.env_params, pol.env),
        residuals=dtp.cast_floats(fleet.residuals, pol.transport),
        pending=fleet.pending._replace(
            delta=dtp.cast_floats(fleet.pending.delta, pol.transport)),
    )


def fleet_state_bytes(fleet: Fleet) -> Dict[str, float]:
    """Storage bytes of the fleet pytree by state family (plus ``total`` and
    ``per_agent``) — the quantity the lean policies shrink and the scaling
    benchmark curves. Pure host-side accounting from shapes/dtypes."""
    a = int(fleet.pod_ids.shape[0])
    fam = {
        "model": (fleet.astate.params, fleet.base_params),
        "opt": fleet.astate.opt,
        "buffer": fleet.astate.buffer,
        "env": (fleet.astate.env_state, fleet.env_params),
        "transport": (fleet.residuals, fleet.pending),
        "health": fleet.health,
        "misc": (fleet.masks, fleet.group_ids,
                 fleet.pod_ids, fleet.bandwidth, fleet.speeds,
                 fleet.astate.rng, fleet.crash_timer, fleet.partition_timer),
    }
    out = {k: float(dtp.tree_bytes(v)) for k, v in fam.items()}
    out["total"] = float(sum(out.values()))
    out["per_agent"] = out["total"] / max(a, 1)
    return out


def fleet_device_bytes(fleet: Fleet) -> Dict[int, float]:
    """Actual per-device placement of the fleet pytree: ``{device_id:
    bytes}`` summed over every leaf's addressable shards. On a fleet mesh
    the agent-sharded leaves split across the ``data`` axis, so a balanced
    placement shows near-equal rows — the quantity the watcher's scaling
    rows stream."""
    per: Dict[int, float] = {}
    for leaf in jax.tree.leaves(fleet):
        for sh in getattr(leaf, "addressable_shards", ()):
            d = int(sh.device.id)
            per[d] = per.get(d, 0.0) + float(sh.data.nbytes)
    return per


def fleet_init(cfg: FCPOConfig, n_agents: int, key, *, n_pods: int = 1,
               masks: Optional[ActionMask] = None,
               speeds: Optional[jnp.ndarray] = None,
               bandwidth: Optional[jnp.ndarray] = None,
               slo_s: Optional[float] = None, mesh=None,
               env_backend=None, state_policy=None,
               health: Optional[HealthConfig] = None) -> Fleet:
    """``env_backend``: ``"fluid"`` (default) / ``"twin"`` / an
    ``EnvBackend`` — the per-agent ``astate.env_state`` leaves are that
    backend's state pytree, so pass the SAME backend to the training
    drivers. ``state_policy``: a ``repro.core.dtypes`` policy name /
    ``StatePolicy`` — storage dtypes for the fleet state families
    (``fleet_cast``); the default (None) keeps the all-float32 layout,
    bit-identical to pre-policy fleets. ``health``: a
    ``repro.health.HealthConfig`` — attaches the observatory state
    (sketches, drift detectors, suspicion) to the pytree; None (default)
    keeps the pre-health fleet exactly."""
    backend = get_backend(env_backend)
    kp, kb, ke, kr = jax.random.split(key, 4)
    agent_keys = jax.random.split(kp, n_agents)
    params = jax.vmap(lambda k: agent_init(cfg, k))(agent_keys)
    opt = jax.vmap(agent_opt_init)(params)
    buffers = jax.vmap(lambda _: buffer_init(cfg))(jnp.arange(n_agents))
    env_states = jax.vmap(lambda _: backend.init(cfg))(jnp.arange(n_agents))
    rngs = jax.random.split(kr, n_agents)

    if speeds is None:  # heterogeneous device mix (Orin/NX/AGX/server-like)
        speeds = jnp.asarray(
            np.random.default_rng(0).choice([0.5, 0.75, 1.0, 2.0], n_agents))
    if bandwidth is None:
        bandwidth = jnp.asarray(
            np.random.default_rng(1).uniform(2.0, 40.0, n_agents))
    env_params = jax.vmap(lambda s: env_mod.default_env_params(
        s, cfg.slo_s if slo_s is None else slo_s))(speeds)
    backend.check_env_params(env_params)

    if masks is None:
        masks = jax.tree.map(lambda m: jnp.broadcast_to(m, (n_agents,) + m.shape),
                             full_mask(cfg))
    hg = fed.head_group_ids(masks)
    group_ids = {k: v for k, v in hg.items() if not k.endswith("_count")}
    group_counts = {k[:-len("_count")]: v for k, v in hg.items()
                    if k.endswith("_count")}
    pod_ids = jnp.asarray(np.arange(n_agents) % n_pods, jnp.int32)

    base = agent_init(cfg, kb)
    base_params = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_pods,) + x.shape), base)

    astate = AgentState(params=params, opt=opt, buffer=buffers,
                        env_state=env_states, rng=rngs)
    fleet = Fleet(astate, base_params, env_params, masks, group_ids,
                  pod_ids, bandwidth, speeds, jnp.zeros((), jnp.int32),
                  fl_codec.residuals_init(params),
                  fl_stale.pending_init(params),
                  jnp.zeros((n_agents,), jnp.int32),
                  jnp.zeros((n_pods,), jnp.int32),
                  health_init(health, n_agents, cfg.n_res + cfg.n_bs
                              + cfg.n_mt) if health is not None else None,
                  n_pods=n_pods, group_counts=group_counts)
    if state_policy is not None:
        fleet = fleet_cast(fleet, state_policy)
    if mesh is not None:
        fleet = jax.device_put(fleet, fleet_shardings(fleet, mesh))
    return fleet


@partial(jax.jit, static_argnums=0,
         static_argnames=("learn", "backend", "health"))
def fleet_episode(cfg: FCPOConfig, fleet: Fleet, rates: jnp.ndarray,
                  learn: bool = True, backend: EnvBackend = FLUID,
                  health: Optional[HealthConfig] = None):
    """One CRL episode for all agents. rates: (A, n_steps).
    Returns (fleet, rollouts, metrics). ``backend`` (static, hashable)
    selects the environment the episodes run in. ``health`` (static)
    advances every agent's telemetry sketches and drift detectors through
    the episode's raw per-interval telemetry and merges their O(bins)
    summaries into the metrics as (A,) arrays (``repro.health.
    HEALTH_METRIC_KEYS``); the fleet must carry matching health state
    (``fleet_init(..., health=...)``). None (default) stages the exact
    pre-health program."""
    # Under a mesh the agent axis is named, so a Pallas kernel the episode
    # runs per agent (kernels.ops shard_maps it) splits over the devices
    # that hold those agents.
    mesh = shd.ambient_mesh()
    spmd = None if mesh is None else shd.agent_axes(rates.shape[0], mesh)
    astate, rollouts, metrics = jax.vmap(
        lambda ep, st, r, m: crl_episode(cfg, ep, st, r, m, learn, backend,
                                         health=health is not None),
        spmd_axis_name=spmd,
    )(fleet.env_params, fleet.astate, rates, fleet.masks)
    hstate = fleet.health
    if health is not None:
        if hstate is None:
            raise ValueError("fleet_episode(health=...) needs a fleet with "
                             "health state (fleet_init(..., health=...))")
        tele = metrics.pop("_health")
        hstate = health_update_episode(health, hstate, tele["reward"],
                                       tele["miss"], tele["probs"],
                                       tele["rate"])
        metrics.update(health_summaries(health, hstate))
    fleet = fleet._replace(astate=astate, episode=fleet.episode + 1,
                           health=hstate)
    return fleet, rollouts, metrics


@partial(jax.jit, static_argnums=0,
         static_argnames=("transport", "guards", "faults", "health"))
def fl_round(cfg: FCPOConfig, fleet: Fleet, rollouts, available=None,
             transport: Optional[TransportConfig] = None,
             guards: Optional[GuardConfig] = None,
             faults: Optional[FaultConfig] = None,
             byzantine=None, fault_key=None, *,
             health: Optional[HealthConfig] = None):
    """One federated round: transport -> Eq. 7 selection -> Alg. 1
    aggregation -> Alg. 2 head fine-tuning.

    ``available`` masks out Bernoulli stragglers/offline agents (the legacy
    fault-tolerance path). ``transport`` (jit-static) adds the communication
    model on top: clients ship ``params - base`` deltas encoded per-leaf
    with error feedback (``fleet.residuals``); a configured round deadline
    makes stragglers *emergent* — an agent participates iff it is
    Bernoulli-available AND its encoded upload fits the deadline — and with
    ``async_rounds`` a missed upload parks in ``fleet.pending`` to join a
    later round staleness-discounted. The default transport (float32 codec,
    no deadline, sync) compiles to the exact pre-transport round.

    ``guards`` (jit-static, ``repro.resilience.GuardConfig``) selects the
    Algorithm 1 statistic (mean / trimmed / median), an optional per-leaf
    delta norm clip, and the non-finite contribution rejection. ``faults``
    + ``byzantine`` ((A,) bool) + ``fault_key`` inject byzantine corruption
    into the decoded deltas, post-codec. The defaults (no faults, mean
    aggregation, guards on) compile to the exact pre-chaos round.

    The stages run under the profiler scopes ``fl_uplink`` (link model),
    ``fl_encode`` (the server-side view of the clients' parameters: codec
    round trip or the lossless shortcut), ``fl_aggregate`` (Algorithm 1)
    and ``fl_finetune`` (Algorithm 2); selection and the buffer resync sit
    outside them, under ``jit(fl_round)``.

    ``health`` (jit-static, ``repro.health.HealthConfig``) attributes the
    round: every selected client's wire delta is scored against a
    norm-clipped robust reference (per-client norm, cosine, leave-one-out
    cosine -> suspicion in [0, 1], ``repro.health.attribution``), folded
    into the fleet's suspicion EMA. With ``guards.susp_threshold`` > 0 the
    *previous* round's EMA additionally gates Eq. 7 selection (scores for
    this round's deltas cannot exist before aggregation, so the gate is
    one round behind by construction). On the plain-transport path the
    deltas are computed as a pure readout on the side — the aggregation
    shortcut (and its bit-identical numerics) is preserved.

    Returns (fleet, sel, fl_metrics) where ``sel`` is the (A,) aggregation
    mask and ``fl_metrics`` the per-round communication/defense metrics
    (``repro.fl.transport.FL_METRIC_KEYS``)."""
    transport = DEFAULT_TRANSPORT if transport is None else transport
    if health is not None and fleet.health is None:
        raise ValueError("fl_round(health=...) needs a fleet with health "
                         "state (fleet_init(..., health=...))")
    guards = DEFAULT_GUARDS if guards is None else guards
    byz_on = faults is not None and faults.byzantine_active
    a = fleet.pod_ids.shape[0]
    if available is None:
        available = jnp.ones((a,), bool)
    if byz_on and byzantine is None:
        byzantine = jnp.zeros((a,), bool)
    legacy_avail = available
    params = fleet.astate.params
    pending = fleet.pending
    rejected = jnp.zeros((), jnp.float32)
    clipped = jnp.zeros((), jnp.float32)

    # Parked uploads are validated before anything reads them (selection
    # included): a poisoned delta parked in an earlier round must not make
    # its offline owner selectable nor resurface into aggregation.
    if guards.reject_nonfinite and transport.async_rounds:
        pending, n_dropped = fl_stale.validate_pending(pending)
        rejected = rejected + n_dropped

    # --- communication model: payload sizes are static, links are per-agent
    with jax.named_scope("fl_uplink"):
        up_bytes = fl_transport.agent_payload_bytes(params, transport,
                                                   stacked=True)
        full_bytes = fl_transport.full_param_bytes(params, stacked=True)
        down_bytes = fl_transport.downlink_bytes(transport, a, fleet.n_pods,
                                                 up_bytes, full_bytes)
        uplink_s = fl_transport.uplink_seconds(up_bytes, fleet.bandwidth)
        on_time = fl_transport.on_time_mask(uplink_s, transport.deadline_s)
        fresh_ok = legacy_avail & on_time

    # --- Eq. 7 selection. Sync rounds: a slow link emergently drops out of
    # selection. Async rounds: slow-but-alive clients stay selectable (they
    # park for the next round) and parked deltas are selectable even if
    # their owner is offline now (the server already holds them).
    if transport.async_rounds:
        selectable = legacy_avail | pending.has
    else:
        selectable = fresh_ok
    div = buffer_diversity_mean(fleet.astate.buffer)
    stats = fed.ClientStats(
        mem_avail=jnp.clip(1.0 - fleet.astate.env_state.pre_q
                           / fleet.env_params.queue_cap, 0, 1),
        compute_avail=jnp.clip(fleet.speeds / 2.0, 0, 1),
        diversity=div,
        bandwidth=fleet.bandwidth,
        available=selectable,
    )
    if health is not None and guards.susp_threshold > 0.0:
        # the attribution evidence stream closes into action: clients the
        # PREVIOUS round scored suspect lose their selection slot to the
        # next-best honest candidate
        sel = fed.select_clients(cfg, stats, suspicion=fleet.health.susp,
                                 susp_threshold=guards.susp_threshold)
    else:
        sel = fed.select_clients(cfg, stats)
    health_rej = jnp.zeros((a,), bool)  # nonfinite-rejected => suspicion 1

    head_losses = jax.vmap(
        lambda p, r, m: fed.per_head_losses(cfg, p, r, m)
    )(params, rollouts, fleet.masks)

    # --- reconstruct the server-side view of each client's parameters
    with jax.named_scope("fl_encode"):
        if transport.plain and not byz_on and guards.clip_factor <= 0:
            # lossless codec, nothing parked, nothing corrupted or clipped
            # in transit: base + (params - base) == params identically —
            # skip the delta machinery so the default config is bit-for-bit
            # the pre-transport program.
            recon, sel_agg = params, sel
            residuals, new_pending = fleet.residuals, pending
            transmitted = sel
            stale_used = jnp.zeros((), jnp.float32)
            if guards.reject_nonfinite:
                # identity on healthy params; a wedged client (NaN'd by its
                # own training) drops out of aggregation instead of
                # poisoning it
                ok = guard_finite_mask(params)
                rejected = rejected + jnp.sum(sel & ~ok).astype(jnp.float32)
                sel_agg = sel & ok
                health_rej = sel & ~ok
            if health is not None:
                # pure readout on the side: the shortcut above still
                # aggregates the raw params, so the plain-path numerics stay
                # bit-identical to health-off — the deltas vs the downlinked
                # base exist only to be scored
                base_h = jax.tree.map(
                    lambda b: shd.agent_hint(b[fleet.pod_ids]
                                             .astype(jnp.float32)),
                    fleet.base_params)
                delta_h = jax.tree.map(
                    lambda p, b: jnp.subtract(p.astype(jnp.float32), b),
                    params, base_h)
                susp_new = health_attribution(delta_h, sel_agg)["susp"]
        else:
            # The (P,...)->(A,...) gather is the round's downlink broadcast:
            # the agent hint lets a meshed run materialize it shard-local
            # instead of full-replica. Deltas are formed in float32 whatever
            # the storage policy (bf16 params would otherwise difference at
            # bf16). Both are no-ops under the default f32/no-mesh config.
            base_g = jax.tree.map(
                lambda b: shd.agent_hint(b[fleet.pod_ids]
                                         .astype(jnp.float32)),
                fleet.base_params)
            delta = jax.tree.map(
                lambda p, b: jnp.subtract(p.astype(jnp.float32), b),
                params, base_g)
            decoded, res_next = fl_codec.codec_roundtrip(
                delta, fleet.residuals, transport)
            if byz_on:
                # corruption happens in transit, AFTER the honest client
                # encoded its delta and committed error feedback — the server
                # sees garbage, the client's own state stays consistent
                key = (fault_key if fault_key is not None
                       else jax.random.PRNGKey(faults.seed))
                decoded = rfaults.corrupt_deltas(faults, decoded, byzantine,
                                                 key)
            if transport.async_rounds:
                w_stale = fl_stale.stale_weights(pending,
                                                 transport.staleness_decay)
                contrib = fl_stale.merge_contributions(decoded, pending,
                                                       fresh_ok, w_stale)
                sel_agg = sel & (fresh_ok | pending.has)
                parked = sel & legacy_avail & ~on_time
                consumed = sel & pending.has & ~fresh_ok
                fresh_sent = sel & fresh_ok
                transmitted = fresh_sent | parked
                new_pending = fl_stale.update_pending(pending, decoded, parked,
                                                      consumed, fresh_sent)
                stale_used = jnp.sum(consumed).astype(jnp.float32)
            else:
                contrib = decoded
                sel_agg = sel            # selection already required on-time
                transmitted = sel
                new_pending = pending
                stale_used = jnp.zeros((), jnp.float32)
            # --- server-side defenses on the merged wire contributions ---
            if guards.reject_nonfinite:
                ok = guard_finite_mask(contrib)
                rejected = rejected + jnp.sum(sel_agg & ~ok).astype(
                    jnp.float32)
                health_rej = sel_agg & ~ok
                sel_agg = sel_agg & ok
            if health is not None:
                # score the post-corruption wire deltas BEFORE clipping — the
                # clip would erase exactly the magnitude evidence the norm
                # term keys on
                susp_new = health_attribution(contrib, sel_agg)["susp"]
            if guards.clip_factor > 0:
                contrib, clipped = guard_clip_deltas(contrib, sel_agg,
                                                     guards.clip_factor)
            # only selected contributors are seen through the wire; everyone
            # else enters aggregation with their TRUE params, so Alg. 1's
            # no-contributor fallback ("groups with no contributor keep the
            # agent's own head") keeps real heads, not a lossy reconstruction
            # whose error feedback was never committed.
            recon = jax.tree.map(
                lambda rc, p: jnp.where(
                    sel_agg.reshape((-1,) + (1,) * (rc.ndim - 1)), rc,
                    p.astype(rc.dtype)),
                jax.tree.map(jnp.add, base_g, contrib), params)
            # error feedback commits only for deltas that actually went (or
            # will go, parked) over the wire; everyone else re-derives a fresh
            # delta against the moved base next round. The codec returns f32
            # residuals; they are stored back at StatePolicy.transport
            # precision.
            residuals = jax.tree.map(
                lambda nr, r: jnp.where(
                    transmitted.reshape((-1,) + (1,) * (nr.ndim - 1)),
                    nr.astype(r.dtype), r),
                res_next, fleet.residuals)

    # Algorithm 1 computes in float32 (recon may arrive bf16 off the plain
    # path under a lean model policy); the new fleet/base params are stored
    # back at the policy dtype — all astype identities under the default.
    with jax.named_scope("fl_aggregate"):
        new_params, new_base = fed.aggregate(
            cfg, dtp.tree_f32(recon), dtp.tree_f32(fleet.base_params),
            sel_agg, head_losses, fleet.head_groups, fleet.pod_ids,
            fleet.n_pods, method=guards.agg, trim_frac=guards.trim_frac)
        new_params = dtp.tree_cast_like(new_params, params)
        new_base = dtp.tree_cast_like(new_base, fleet.base_params)

    # Algorithm 2: local action-head fine-tuning on local experiences
    with jax.named_scope("fl_finetune"):
        params, opt = jax.vmap(
            lambda p, o, r, m: finetune_heads(cfg, p, o, r, m)
        )(new_params, fleet.astate.opt, rollouts, fleet.masks)

    # FL-round cadence is the off-hot-path slot to resync the buffers'
    # streaming moments from their slots, bounding rank-1 float32 drift.
    buffers = jax.vmap(buffer_resync)(fleet.astate.buffer)
    astate = fleet.astate._replace(params=params, opt=opt, buffer=buffers)

    n_up = jnp.sum(transmitted).astype(jnp.float32)
    fl_metrics = {
        "fl_payload_bytes": n_up * up_bytes + down_bytes,
        "fl_uplink_s": jnp.sum(jnp.where(transmitted, uplink_s, 0.0))
        / jnp.maximum(n_up, 1.0),
        "fl_missed": jnp.sum(legacy_avail & ~on_time).astype(jnp.float32),
        "fl_stale_used": stale_used,
        "fl_rejected": rejected,
        "fl_clipped": clipped,
    }
    new_health = fleet.health
    if health is not None:
        # a rejected contribution is maximal evidence — the client shipped
        # garbage, whatever its direction would have scored
        susp_new = jnp.where(health_rej, 1.0, susp_new)
        new_health = health_update_round(health, fleet.health, susp_new,
                                         sel_agg | health_rej)
    fleet = fleet._replace(astate=astate, base_params=new_base,
                           residuals=residuals, pending=new_pending,
                           health=new_health)
    return fleet, sel_agg, fl_metrics


@partial(jax.jit, static_argnums=0, static_argnames=("faults",))
def pod_merge(cfg: FCPOConfig, fleet: Fleet, partition=None,
              faults: Optional[FaultConfig] = None):
    """Hierarchical cross-pod exchange (cloud tier).

    With partition faults active, ``partition`` ((P,) bool) is this merge
    event's fresh partition draws: a newly partitioned pod drops off the
    cloud tier for ``faults.partition_merges`` merge events (its base
    network drifts alone — only active pods average and redistribute),
    then rejoins. The default (no faults) is the original all-pods merge."""
    if faults is None or not faults.partition_active or partition is None:
        return fleet._replace(base_params=fed.merge_pods(fleet.base_params))
    timer = jnp.maximum(fleet.partition_timer - 1, 0)
    timer = jnp.where(partition, faults.partition_merges, timer)
    active = timer == 0
    return fleet._replace(base_params=fed.merge_pods(fleet.base_params,
                                                     active),
                          partition_timer=timer)


def _normalize_chaos(faults, guards):
    """Map inactive fault configs to None and a None guard config to the
    default — maximizes jit-cache identity with pre-chaos call sites."""
    if faults is not None and not faults.active:
        faults = None
    guards = DEFAULT_GUARDS if guards is None else guards
    return faults, guards


def _ensure_health(cfg: FCPOConfig, fleet: Fleet,
                   health: Optional[HealthConfig]) -> Fleet:
    """Attach fresh observatory state when a health config is given but the
    fleet predates it (e.g. a pre-health checkpoint) — a fleet that already
    carries state keeps it (chunked runs accumulate across restores)."""
    if health is not None and fleet.health is None:
        a = int(fleet.pod_ids.shape[0])
        fleet = fleet._replace(health=health_init(
            health, a, cfg.n_res + cfg.n_bs + cfg.n_mt))
    return fleet


def train_fleet_reference(cfg: FCPOConfig, fleet: Fleet, traces: jnp.ndarray,
                          learn: bool = True, federated: bool = True,
                          straggler_prob: float = 0.0, seed: int = 0,
                          env_backend=None,
                          transport: Optional[TransportConfig] = None,
                          metrics_sink=None,
                          faults: Optional[FaultConfig] = None,
                          guards: Optional[GuardConfig] = None,
                          episode_offset: int = 0,
                          total_episodes: Optional[int] = None,
                          health: Optional[HealthConfig] = None):
    """The original Python-loop driver: one host dispatch per episode plus a
    per-metric host sync — O(n_episodes) dispatches. Kept as the equivalence
    oracle for ``train_fleet_scan`` (same seeds => same straggler draws,
    same fault plan). ``metrics_sink`` gets the same per-episode records as
    the scan driver's streaming tap, appended directly from the loop.
    ``faults``/``guards``/``episode_offset``/``total_episodes``/``health``
    mirror ``train_fleet_scan``."""
    backend = get_backend(env_backend)
    transport = DEFAULT_TRANSPORT if transport is None else transport
    faults, guards = _normalize_chaos(faults, guards)
    fleet = _ensure_health(cfg, fleet, health)
    a, total = traces.shape
    n_eps = total // cfg.n_steps
    total_eps = (episode_offset + n_eps if total_episodes is None
                 else total_episodes)
    if total_eps < episode_offset + n_eps:
        raise ValueError(f"total_episodes={total_eps} < episode_offset="
                         f"{episode_offset} + {n_eps} trace episodes")
    schedule = fed.fl_schedule(cfg, total_eps, federated=federated,
                               learn=learn)
    plan = rfaults.draw_fault_plan(schedule, a, fleet.n_pods, faults)
    crash_on = faults is not None and faults.crash_active
    byz_on = faults is not None and faults.byzantine_active
    part_on = faults is not None and faults.partition_active
    rng = np.random.default_rng(seed)
    history: Dict[str, list] = {}
    rounds = int(schedule[:episode_offset].sum())

    for e in range(episode_offset):  # burn the pre-offset straggler draws
        if schedule[e]:
            rng.random(a)
    for e in range(episode_offset, episode_offset + n_eps):
        i = e - episode_offset
        rates = traces[:, i * cfg.n_steps:(i + 1) * cfg.n_steps]
        prev_astate = fleet.astate
        fleet, rollouts, metrics = fleet_episode(cfg, fleet, rates,
                                                 learn=learn,
                                                 backend=backend,
                                                 health=health)
        ran = None
        if crash_on:
            fleet, ran, down = rfaults.apply_crashes(
                faults, prev_astate, fleet, jnp.asarray(plan.crash[e]))
        fl_metrics = fl_transport.fl_zero_metrics()
        if schedule[e]:
            avail = jnp.asarray(rng.random(a) >= straggler_prob)
            if crash_on:
                avail = avail & ~down
            fkey = (jax.random.fold_in(jax.random.PRNGKey(faults.seed), e)
                    if byz_on else None)
            pre_round = fleet.astate
            fleet, _, fl_metrics = fl_round(
                cfg, fleet, rollouts, avail, transport=transport,
                guards=guards, faults=faults,
                byzantine=(jnp.asarray(plan.byzantine[e]) if byz_on
                           else None),
                fault_key=fkey, health=health)
            if crash_on:
                # a down agent is offline: it must not receive the round's
                # new model (it rejoins later via the step-① warm start)
                fleet = fleet._replace(astate=rfaults.freeze_astate(
                    down, pre_round, fleet.astate))
            rounds += 1
            if rounds % cfg.hierarchical_period == 0 and fleet.n_pods > 1:
                fleet = pod_merge(
                    cfg, fleet,
                    jnp.asarray(plan.partition[e]) if part_on else None,
                    faults=faults if part_on else None)
        if ran is None:
            ep_metrics = {k: float(np.asarray(v).mean())
                          for k, v in metrics.items()}
        else:  # alive-weighted: a frozen agent's episode did not happen
            w = np.asarray(ran, np.float64)
            d = max(w.sum(), 1.0)
            ep_metrics = {k: float((np.asarray(v) * w).sum() / d)
                          for k, v in metrics.items()}
        ep_metrics.update({k: float(np.asarray(v))
                           for k, v in fl_metrics.items()})
        for k, v in ep_metrics.items():
            history.setdefault(k, []).append(v)
        if metrics_sink is not None:
            metrics_sink.append({"episode": e, **ep_metrics})
    return fleet, {k: np.asarray(v) for k, v in history.items()}


# ---------------------------------------------------------------------------
# Streaming metrics: a host-side sink tap on the per-episode metrics
# ---------------------------------------------------------------------------
# Sinks are registered here and addressed by an integer id passed to the
# compiled scan as a plain (non-static) operand, so attaching a different
# sink object to a same-shaped run NEVER recompiles — only the stream
# on/off bit is part of the jit cache key. The sink itself is duck-typed
# (anything with ``.append(record)``; ``repro.eval.stream.MetricsSink`` is
# the JSONL file implementation), which keeps ``core`` free of any
# dependency on the eval/observability layer.
_METRIC_SINKS: Dict[int, Any] = {}
_NEXT_SINK_ID = [1]


def _register_sink(sink) -> int:
    sid = _NEXT_SINK_ID[0]
    _NEXT_SINK_ID[0] += 1
    _METRIC_SINKS[sid] = sink
    return sid


def _sink_emit(names, sink_id, episode, values):
    """Host callback target (ordered ``jax.debug.callback`` from the scan
    body / plain call from the reference loop): one record per episode."""
    sink = _METRIC_SINKS.get(int(sink_id))
    if sink is not None:
        sink.append({"episode": int(episode),
                     **{k: float(v) for k, v in zip(names, values)}})


# ---------------------------------------------------------------------------
# Scanned driver — the whole episodes -> FL round -> pod merge cadence is one
# compiled program
# ---------------------------------------------------------------------------
def _scan_driver(cfg: FCPOConfig, fleet: Fleet, rates_eps: jnp.ndarray,
                 avail: jnp.ndarray, do_fl: jnp.ndarray, ep_idx: jnp.ndarray,
                 sink_id: jnp.ndarray, crash_eps: jnp.ndarray,
                 byz_eps: jnp.ndarray, part_eps: jnp.ndarray,
                 rounds0: jnp.ndarray, learn: bool,
                 backend: EnvBackend, transport: TransportConfig,
                 faults: Optional[FaultConfig],
                 guards: GuardConfig, stream: bool,
                 health: Optional[HealthConfig]):
    """Scan body host fn. rates_eps: (n_eps, A, n_steps); avail/do_fl/ep_idx:
    pre-drawn availability bits, FL schedule, and (absolute) episode
    indices, consumed as scan xs. crash_eps/byz_eps/part_eps: the pre-drawn
    fault plan (``resilience.draw_fault_plan``), also scan xs — dead code
    when ``faults`` (static) is None. ``rounds0`` seeds the FL-round
    counter so a resumed chunk keeps the hierarchical-merge cadence.
    ``stream`` (static: False / "ordered" / "unordered") taps every
    episode's metrics out to the registered sink ``sink_id`` via a host
    callback — the run is still ONE dispatch, but the sink's JSONL file
    tails live. Meshed runs use the unordered flavor (ordered effects are
    single-device-only); the scan's sequential data dependence still
    fires it once per episode. ``health`` (static) advances the
    observatory state through every episode and FL round (sketches, drift
    detectors, attribution) — all pure pytree ops inside the scan; None
    stages the exact health-free program."""
    crash_on = faults is not None and faults.crash_active
    byz_on = faults is not None and faults.byzantine_active
    part_on = faults is not None and faults.partition_active

    def body(carry, xs):
        flt, rounds = carry
        rates, av, fl, ep_i, crash, byz, px = xs
        prev_astate = flt.astate
        flt, rollouts, metrics = fleet_episode(cfg, flt, rates, learn=learn,
                                               backend=backend,
                                               health=health)
        ran = down = None
        if crash_on:
            flt, ran, down = rfaults.apply_crashes(faults, prev_astate, flt,
                                                   crash)
            av = av & ~down

        def with_fl(op):
            f, rnd = op
            fkey = (jax.random.fold_in(jax.random.PRNGKey(faults.seed), ep_i)
                    if byz_on else None)
            pre_round = f.astate
            f, _, flm = fl_round(cfg, f, rollouts, av, transport=transport,
                                 guards=guards, faults=faults,
                                 byzantine=byz if byz_on else None,
                                 fault_key=fkey, health=health)
            if crash_on:
                # a down agent is offline: it must not receive the round's
                # new model (it rejoins later via the step-① warm start)
                f = f._replace(astate=rfaults.freeze_astate(
                    down, pre_round, f.astate))
            rnd = rnd + 1
            if f.n_pods > 1:
                def merge(g):
                    return (pod_merge(cfg, g, px, faults=faults) if part_on
                            else pod_merge(cfg, g))
                f = jax.lax.cond(rnd % cfg.hierarchical_period == 0,
                                 merge, lambda g: g, f)
            return (f, rnd), flm

        def no_fl(op):
            return op, fl_transport.fl_zero_metrics()

        (flt, rounds), flm = jax.lax.cond(fl, with_fl, no_fl, (flt, rounds))
        if ran is None:
            ep_metrics = {k: v.mean() for k, v in metrics.items()}
        else:  # alive-weighted: a frozen agent's episode did not happen
            w = ran.astype(jnp.float32)
            d = jnp.maximum(jnp.sum(w), 1.0)
            ep_metrics = {k: jnp.sum(v * w) / d for k, v in metrics.items()}
        ep_metrics.update(flm)
        if stream:
            names = tuple(sorted(ep_metrics))
            jax.debug.callback(partial(_sink_emit, names), sink_id, ep_i,
                               tuple(ep_metrics[k] for k in names),
                               ordered=(stream == "ordered"))
        return (flt, rounds), ep_metrics

    (fleet, _), history = jax.lax.scan(
        body, (fleet, rounds0),
        (rates_eps, avail, do_fl, ep_idx, crash_eps, byz_eps, part_eps))
    return fleet, history


_SCAN_FNS: Dict[bool, Any] = {}


def _scan_fn(donate: bool):
    if donate not in _SCAN_FNS:
        kw = dict(static_argnums=(0, 11, 12, 13, 14, 15, 16, 17))
        if donate:
            kw["donate_argnums"] = (1,)
        _SCAN_FNS[donate] = jax.jit(_scan_driver, **kw)
    return _SCAN_FNS[donate]


def _prep_scan_args(cfg: FCPOConfig, fleet: Fleet, traces: jnp.ndarray,
                    learn, federated, straggler_prob, seed, mesh,
                    env_backend, transport, faults, guards,
                    episode_offset, total_episodes,
                    sink_id, stream, health=None):
    """Host-side argument prep shared by ``train_fleet_scan`` and
    ``lower_fleet_scan``: FL schedule, availability draws, fault plan,
    episode-major rate reshape, optional mesh sharding — returns the exact
    positional argument tuple for ``_scan_driver``/``_scan_fn``."""
    backend = get_backend(env_backend)
    transport = DEFAULT_TRANSPORT if transport is None else transport
    faults, guards = _normalize_chaos(faults, guards)
    fleet = _ensure_health(cfg, fleet, health)
    a, total = traces.shape
    n_eps = total // cfg.n_steps
    total_eps = (episode_offset + n_eps if total_episodes is None
                 else total_episodes)
    if total_eps < episode_offset + n_eps:
        raise ValueError(f"total_episodes={total_eps} < episode_offset="
                         f"{episode_offset} + {n_eps} trace episodes")
    schedule = fed.fl_schedule(cfg, total_eps, federated=federated,
                               learn=learn)
    avail = fed.draw_availability(schedule, a, straggler_prob, seed)
    plan = rfaults.draw_fault_plan(schedule, a, fleet.n_pods, faults)
    sl = slice(episode_offset, episode_offset + n_eps)
    rounds0 = int(schedule[:episode_offset].sum())

    rates_eps = jnp.asarray(traces[:, :n_eps * cfg.n_steps]).reshape(
        a, n_eps, cfg.n_steps).transpose(1, 0, 2)
    avail = jnp.asarray(avail[sl])
    do_fl = jnp.asarray(schedule[sl])
    ep_idx = jnp.arange(episode_offset, episode_offset + n_eps,
                        dtype=jnp.int32)
    crash_eps = jnp.asarray(plan.crash[sl])
    byz_eps = jnp.asarray(plan.byzantine[sl])
    part_eps = jnp.asarray(plan.partition[sl])

    if mesh is not None:
        fleet = jax.device_put(fleet, fleet_shardings(fleet, mesh))
        xs_shard = lambda x: jax.device_put(
            x, NamedSharding(mesh, shd.agent_batch_spec(x.shape, mesh)))
        rates_eps, avail = xs_shard(rates_eps), xs_shard(avail)

    return (cfg, fleet, rates_eps, avail, do_fl, ep_idx,
            jnp.asarray(sink_id, jnp.int32), crash_eps, byz_eps, part_eps,
            jnp.asarray(rounds0, jnp.int32), learn, backend, transport,
            faults, guards, stream, health)


def _mesh_context(mesh):
    return jax.set_mesh(mesh) if mesh is not None else nullcontext()


def lower_fleet_scan(cfg: FCPOConfig, fleet: Fleet, traces: jnp.ndarray,
                     learn: bool = True, federated: bool = True,
                     straggler_prob: float = 0.0, seed: int = 0,
                     mesh=None, donate: bool = True, env_backend=None,
                     transport: Optional[TransportConfig] = None,
                     faults: Optional[FaultConfig] = None,
                     guards: Optional[GuardConfig] = None,
                     episode_offset: int = 0,
                     total_episodes: Optional[int] = None,
                     health: Optional[HealthConfig] = None):
    """Lower (without running) the exact scanned-driver program that
    ``train_fleet_scan`` would dispatch for these arguments — including
    buffer donation — and return the ``jax.stages.Lowered``. This is the
    entry point ``repro.obs.profile`` uses for XLA cost/memory accounting
    and the donation audit: the program analyzed is the program trained."""
    args = _prep_scan_args(cfg, fleet, traces, learn, federated,
                           straggler_prob, seed, mesh, env_backend,
                           transport, faults, guards, episode_offset,
                           total_episodes, sink_id=0, stream=False,
                           health=health)
    # trace under the mesh so the in-graph sharding hints
    # (sharding.ambient_mesh) resolve — the analyzed program is the meshed
    # program train_fleet_scan would run
    with _mesh_context(mesh):
        return _scan_fn(bool(donate)).lower(*args)


def train_fleet_scan(cfg: FCPOConfig, fleet: Fleet, traces: jnp.ndarray,
                     learn: bool = True, federated: bool = True,
                     straggler_prob: float = 0.0, seed: int = 0,
                     mesh=None, donate: Optional[bool] = None,
                     env_backend=None,
                     transport: Optional[TransportConfig] = None,
                     metrics_sink=None,
                     faults: Optional[FaultConfig] = None,
                     guards: Optional[GuardConfig] = None,
                     episode_offset: int = 0,
                     total_episodes: Optional[int] = None,
                     health: Optional[HealthConfig] = None):
    """Scanned fleet driver: episodes over ``traces`` (A, total_steps), FL
    every ``fl_every`` episodes (stragglers masked by pre-drawn availability
    bits), cross-pod merge every ``hierarchical_period`` rounds — all inside
    ONE jitted ``lax.scan``; O(1) host dispatches per run.

    ``mesh``: install fleet shardings (agents over data, pods over the FL
    hierarchy) on inputs before the call AND enter the mesh for the
    dispatch, so the in-graph hints turn the Alg. 1 segment-sums, the
    base-network downlink gather, and the pod merge into real collectives
    over the mesh — the scan then runs SPMD (``launch.mesh.make_fleet_mesh``
    builds the (pod, data) mesh; tests/test_mesh.py locks meshed == single-
    device seed-for-seed).
    ``donate``: donate the input fleet's buffers to the compiled call
    (defaults to on except on CPU, where XLA cannot donate).
    ``env_backend``: ``"fluid"`` / ``"twin"`` / an ``EnvBackend`` — with the
    twin, every control interval nests K data-plane microticks *inside* the
    same single scan (no host Python per microtick; ``fleet`` must have been
    built with the same backend).
    ``transport``: a jit-static ``repro.fl.TransportConfig`` — delta codec,
    round deadline (emergent stragglers compose with the Bernoulli
    ``straggler_prob`` mask), and async staleness semantics; the per-round
    communication metrics (``fl_payload_bytes``/``fl_uplink_s``/
    ``fl_missed``/``fl_stale_used``) appear in the history, zero on
    episodes without a round.
    ``metrics_sink``: any object with ``.append(record)`` (e.g.
    ``repro.eval.stream.MetricsSink``) — every episode's metrics are tapped
    out of the scan through an ordered host callback as they complete, so a
    long run is observable live (``launch/watch.py``) while still being ONE
    dispatch. Off (None) by default, in which case the traced program is
    exactly the sink-free one.
    ``faults``: a jit-static ``repro.resilience.FaultConfig`` — injected
    crashes / byzantine deltas / pod partitions, pre-drawn on host
    (``draw_fault_plan``) and consumed as scan xs, so the chaos run is
    still ONE jitted scan. ``guards``: a jit-static
    ``repro.resilience.GuardConfig`` — robust aggregation / delta clipping
    / non-finite rejection. The defaults compile to the exact pre-chaos
    program, bit-for-bit seed-for-seed.
    ``episode_offset``/``total_episodes``: run episodes
    [offset, offset + traces-episodes) of a ``total_episodes``-long
    schedule — straggler draws, fault plans, FL cadence, and the
    hierarchical-merge counter all follow the *absolute* episode index, so
    a run chunked across checkpoint save/restore boundaries is
    value-identical to the uninterrupted run.
    ``health``: a jit-static ``repro.health.HealthConfig`` — the fleet
    health observatory: per-agent telemetry sketches + drift detectors
    advanced per control interval, FL contribution attribution per round,
    all as pure pytree state inside the same single scan; the per-episode
    summaries (``repro.health.HEALTH_METRIC_KEYS``) join the history and
    the metrics stream. A fleet without health state gets fresh state
    attached (``_ensure_health``). Off (None) by default, in which case
    the traced program is exactly the health-free one — bit-identical
    histories, unchanged donation audit.
    Returns (fleet, history) with history as per-episode numpy arrays,
    fetched in a single device->host transfer.

    Under the JAX profiler the host stages land as ``fleet.prep`` (argument
    prep and transfer), ``fleet.call`` (the jitted call's dispatch) and
    ``fleet.fetch`` (the history fetch, which waits for the device), on
    the clock of the device ops; the compiled scan's stages carry the
    ``fcpo_*`` and ``fl_*`` named scopes."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    # ordered callbacks are a single-device-only effect in XLA; on a multi-
    # device mesh the tap switches to an unordered callback, which the scan's
    # sequential data dependence still fires once per episode, in order
    stream = False if metrics_sink is None else \
        ("ordered" if mesh is None or mesh.size == 1 else "unordered")
    sid = _register_sink(metrics_sink) if stream else 0
    with TraceAnnotation("fleet.prep"):
        args = _prep_scan_args(cfg, fleet, traces, learn, federated,
                               straggler_prob, seed, mesh, env_backend,
                               transport, faults, guards, episode_offset,
                               total_episodes, sink_id=sid, stream=stream,
                               health=health)
    try:
        # setting the mesh as the context mesh activates the in-graph sharding
        # hints (agents over (pod, data), pods over the FL hierarchy): the
        # Alg. 1 segment-sums and the pod merge lower to real collectives.
        # Without a mesh the hints are no-ops and the traced program is the
        # exact single-device one.
        with _mesh_context(mesh):
            with TraceAnnotation("fleet.call"):
                fleet, history = _scan_fn(bool(donate))(*args)
            with TraceAnnotation("fleet.fetch"):
                history = jax.device_get(history)
    finally:
        if stream:
            # the history fetch blocks on the compute; the callback effects
            # drain behind it — barrier before releasing the sink slot
            jax.effects_barrier()
            _METRIC_SINKS.pop(sid, None)
    return fleet, history


def train_fleet(cfg: FCPOConfig, fleet: Fleet, traces: jnp.ndarray,
                learn: bool = True, federated: bool = True,
                straggler_prob: float = 0.0, seed: int = 0,
                env_backend=None, transport: Optional[TransportConfig] = None,
                metrics_sink=None, faults: Optional[FaultConfig] = None,
                guards: Optional[GuardConfig] = None,
                health: Optional[HealthConfig] = None):
    """Compatibility entry point — delegates to the scanned driver. Buffer
    donation stays off so callers may keep using the input fleet (forking a
    fleet into warm/cold copies is a common pattern in the benchmarks)."""
    return train_fleet_scan(cfg, fleet, traces, learn=learn,
                            federated=federated,
                            straggler_prob=straggler_prob, seed=seed,
                            donate=False, env_backend=env_backend,
                            transport=transport, metrics_sink=metrics_sink,
                            faults=faults, guards=guards, health=health)
