"""Fleet training launcher — the scanned FCPO driver from the CLI.

Runs the full federated-continual cadence (CRL episodes -> Eq. 7 selection ->
Alg. 1 aggregation -> Alg. 2 fine-tune -> hierarchical pod merge) as ONE
compiled program via ``train_fleet_scan``. ``--driver reference`` selects the
Python-loop oracle for A/B timing; ``--mesh`` installs the fleet shardings
(agents over ``data``, pods over the FL hierarchy) so the same command is
SPMD on a real mesh; ``--env-backend twin`` trains in the request-level
digital twin ("train where you serve") with K nested microticks per control
interval — still one jitted scan; ``--scenario`` picks the workload from the
scenario library (``repro.sim.scenarios``).

Examples:
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --pods 2 \
      --episodes 200
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --episodes 100 \
      --env-backend twin --scenario switching    # train in the twin
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 16 --episodes 100 \
      --straggler-prob 0.3 --driver reference   # O(n_episodes) dispatches
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --episodes 100 \
      --fl-codec int8 --fl-deadline-s 0.02 --fl-async  # compressed async FL
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --mesh debug
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train_fleet --agents 64 --pods 2 \
      --mesh fleet --state-dtype lean   # SPMD fleet mesh + lean state

``--fl-codec/--fl-deadline-s/--fl-async`` configure the federated transport
subsystem (``repro.fl``): compressed ``params - base`` deltas with error
feedback, uplink-time round deadlines (emergent stragglers), and
staleness-tolerant async rounds — all inside the same single jitted scan.

``--fault-*`` / ``--robust-agg`` configure the chaos layer
(``repro.resilience``): injected crashes / byzantine deltas / pod
partitions and the robust-aggregation defenses. ``--ckpt-dir`` +
``--ckpt-every`` add periodic checkpointing with auto-resume: a killed run
relaunched with the same command restarts from ``latest_step`` and
produces the same numbers as an uninterrupted run (straggler draws, fault
plans, and merge cadence all follow the absolute episode index).

  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --episodes 100 \
      --fault-byzantine-frac 0.2 --robust-agg trimmed   # survive poison
  PYTHONPATH=src python -m repro.launch.train_fleet --agents 8 --episodes 100 \
      --ckpt-dir /tmp/run1 --ckpt-every 10              # kill-safe training
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fcpo import FCPOConfig
from repro.core.backends import BACKENDS, get_backend
from repro.core.fleet import (fleet_device_bytes, fleet_init,
                              fleet_state_bytes, train_fleet_reference,
                              train_fleet_scan)
from repro.eval.stream import MetricsSink
from repro.fl import CODECS, TransportConfig
from repro.health import HealthConfig
from repro.health.alerts import AlertEngine
from repro.core.dtypes import POLICIES
from repro.launch import compile_cache
from repro.launch.mesh import (make_debug_mesh, make_fleet_mesh,
                               make_production_mesh)
from repro.resilience import BYZANTINE_MODES, FaultConfig, GuardConfig
from repro.resilience.guards import AGG_METHODS
from repro.sim import SCENARIOS, SimParams, make_scenario
from repro.training import checkpoint as ckpt_mod


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=8)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--episodes", type=int, default=200)
    ap.add_argument("--fl-every", type=int, default=None,
                    help="override cfg.fl_every")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="probability an agent is offline for an FL round "
                         "(Bernoulli draw, the legacy straggler model). "
                         "Composes with the EMERGENT deadline stragglers of "
                         "--fl-deadline-s: an agent joins a round only if it "
                         "is Bernoulli-available AND its encoded upload fits "
                         "the deadline over its own link")
    ap.add_argument("--fl-codec", choices=CODECS, default="float32",
                    help="on-wire FL delta codec (repro.fl): float32 is the "
                         "lossless legacy path; int8/topk compress the "
                         "params-base delta with error feedback")
    ap.add_argument("--fl-topk-frac", type=float, default=0.05,
                    help="fraction of coordinates the topk codec keeps per "
                         "tensor")
    ap.add_argument("--fl-deadline-s", type=float, default=0.0,
                    help="FL round deadline (s); uplink time = encoded "
                         "payload bits / per-agent bandwidth, so slow links "
                         "emergently miss rounds. <= 0 disables")
    ap.add_argument("--fl-async", action="store_true",
                    help="staleness-tolerant rounds: a selected client that "
                         "misses the deadline parks its encoded delta and "
                         "joins the next round staleness-discounted")
    ap.add_argument("--fl-pallas", action="store_true",
                    help="route the delta codec through the fused Pallas "
                         "delta_codec kernel")
    ap.add_argument("--no-federated", action="store_true")
    ap.add_argument("--no-learn", action="store_true")
    ap.add_argument("--driver", choices=("scan", "reference"), default="scan")
    ap.add_argument("--mesh", choices=("none", "debug", "production",
                                       "fleet"),
                    default="none",
                    help="fleet = the scaling mesh: ('pod', 'data') over "
                         "every visible device, pods over the FL-hierarchy "
                         "axis (simulate multi-device on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--state-dtype", choices=tuple(POLICIES), dest="state_dtype",
                    default="float32",
                    help="per-agent stored-state precision policy "
                         "(repro.core.dtypes): float32 is the bit-identical "
                         "legacy layout; bf16 halves optimizer/env/transport "
                         "state; lean adds int8 replay payloads + bf16 "
                         "params for ~2x peak-memory at A=2048. All math "
                         "still runs in float32")
    ap.add_argument("--env-backend", choices=BACKENDS, default="fluid",
                    help="environment the CRL episodes run in: the fluid "
                         "MDP or the request-level digital twin")
    ap.add_argument("--scenario", choices=SCENARIOS, default="nominal",
                    help="workload scenario for the training traces "
                         "(default: the historical make_trace workload — "
                         "same seed reproduces pre-scenario-library runs)")
    ap.add_argument("--dt", type=float, default=0.05,
                    help="twin microtick length (s)")
    ap.add_argument("--k-ticks", type=int, default=20,
                    help="twin microticks per control interval")
    ap.add_argument("--ring", type=int, default=512,
                    help="twin ring capacity (power of two)")
    ap.add_argument("--pallas", action="store_true",
                    help="route the twin data plane through the fused "
                         "Pallas queue_advance kernel")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="stream per-episode metrics (reward, "
                         "fl_payload_bytes, miss/stale rates, ...) to this "
                         "JSONL file while training runs; tail it live with "
                         "python -m repro.launch.watch <file> --follow")
    ap.add_argument("--trace-out", type=str, default=None,
                    metavar="DIR",
                    help="run training under the JAX profiler and write its "
                         "trace to this directory (plugins/profile/<time>/"
                         "*.trace.json.gz and perfetto_trace.json.gz, open "
                         "in Perfetto): device ops under the fcpo_rollout/"
                         "fcpo_buffer/fcpo_update and fl_uplink/fl_encode/"
                         "fl_aggregate/fl_finetune scopes, host spans "
                         "fleet.prep/fleet.call/fleet.fetch, on one clock")
    # --- fleet health observatory (repro.health) ---
    ap.add_argument("--health", action="store_true",
                    help="attach the fleet health observatory: per-agent "
                         "telemetry sketches + drift detectors advanced "
                         "inside the scan, FL contribution attribution per "
                         "round; per-episode health_* summaries join the "
                         "history and the --metrics-out stream")
    ap.add_argument("--health-bins", type=int, default=16,
                    help="histogram sketch resolution (quantile error is "
                         "bounded by one bin width)")
    ap.add_argument("--susp-threshold", type=float, default=0.0,
                    help="act on the attribution evidence: clients whose "
                         "suspicion EMA exceeds this are dropped from Eq. 7 "
                         "selection (one round behind by construction). "
                         "0 observes without acting; requires --health")
    ap.add_argument("--alerts-out", type=str, default=None,
                    help="evaluate the declarative health alert rules "
                         "(repro.health.alerts.DEFAULT_RULES) over the "
                         "metrics stream and write fire/resolve lines to "
                         "this ALERTS.jsonl; requires --health")
    # --- chaos layer: fault injection (repro.resilience.FaultConfig) ---
    ap.add_argument("--fault-crash-prob", type=float, default=0.0,
                    help="per-agent per-episode crash probability: the "
                         "agent's state freezes (params zeroed), it leaves "
                         "episodes and Eq. 7 selection for "
                         "--fault-crash-recovery episodes, then rejoins "
                         "warm-started from its pod base network. Unlike "
                         "--straggler-prob (one missed FL round, Bernoulli "
                         "per round) a crash is a multi-episode outage")
    ap.add_argument("--fault-crash-recovery", type=int, default=2,
                    help="episodes a crashed agent stays down")
    ap.add_argument("--fault-byzantine-frac", type=float, default=0.0,
                    help="per-agent per-round probability of shipping a "
                         "corrupted delta (applied post-codec, so it "
                         "composes with --fl-codec int8/topk)")
    ap.add_argument("--fault-byzantine-mode", choices=BYZANTINE_MODES,
                    default="sign_flip",
                    help="corruption: sign_flip (scaled negation), noise "
                         "(additive gaussian), nan (poisoned upload)")
    ap.add_argument("--fault-byzantine-scale", type=float, default=10.0,
                    help="magnitude of sign_flip/noise corruption")
    ap.add_argument("--fault-partition-prob", type=float, default=0.0,
                    help="per-pod probability, at each hierarchical merge, "
                         "of dropping off the cloud tier for "
                         "--fault-partition-merges merge events")
    ap.add_argument("--fault-partition-merges", type=int, default=1,
                    help="merge events a partitioned pod skips")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault plan (independent of --seed so "
                         "the same workload can be replayed under different "
                         "fault draws)")
    # --- chaos layer: defenses (repro.resilience.GuardConfig) ---
    ap.add_argument("--robust-agg", choices=AGG_METHODS, default="mean",
                    help="Algorithm 1 statistic: mean is the paper's "
                         "aggregation (bit-identical legacy path); trimmed/"
                         "median are coordinate-wise robust variants that "
                         "bound byzantine influence. Composes with "
                         "--straggler-prob and --fl-deadline-s: the robust "
                         "statistic runs over whatever clients survived "
                         "availability + deadline selection")
    ap.add_argument("--trim-frac", type=float, default=0.2,
                    help="per-side trim fraction of the trimmed-mean "
                         "aggregator (in [0, 0.5))")
    ap.add_argument("--clip-factor", type=float, default=0.0,
                    help="clip each client delta leaf to this multiple of "
                         "the selected-client median leaf norm; 0 disables")
    ap.add_argument("--no-reject-nonfinite", action="store_true",
                    help="disable the NaN/Inf contribution rejection "
                         "(on by default; only useful for demonstrating "
                         "what poison does to an unguarded fleet)")
    # --- periodic checkpoint + auto-resume ---
    ap.add_argument("--ckpt-dir", type=str, default=None,
                    help="checkpoint directory (training.checkpoint "
                         "layout). If it already holds checkpoints, the run "
                         "AUTO-RESUMES from latest_step and reproduces the "
                         "uninterrupted run's numbers exactly")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a checkpoint every N episodes (requires "
                         "--ckpt-dir; 0 saves only at the end of the run)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="prune all but the newest N checkpoints after "
                         "every save")
    ap.add_argument("--stop-after", type=int, default=0,
                    help="exit after this many episodes of THIS invocation "
                         "(kill-and-resume drills; requires --ckpt-dir). "
                         "0 disables")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.episodes < 1:
        ap.error("--episodes must be >= 1")
    if args.fl_every is not None and args.fl_every < 1:
        ap.error("--fl-every must be >= 1 (use --no-federated to disable FL)")
    if args.ring <= 0 or args.ring & (args.ring - 1):
        ap.error("--ring must be a positive power of two")
    if args.env_backend == "fluid" and (
            args.pallas or args.dt != 0.05 or args.k_ticks != 20
            or args.ring != 512):
        ap.error("--pallas/--dt/--k-ticks/--ring configure the twin data "
                 "plane and are silent no-ops on the fluid backend; add "
                 "--env-backend twin")

    if args.fl_async and args.fl_deadline_s <= 0:
        ap.error("--fl-async parks deadline-missed uploads and needs "
                 "--fl-deadline-s > 0 to ever have one")
    if args.fl_pallas and args.fl_codec == "float32":
        ap.error("--fl-pallas routes the delta codec through the fused "
                 "kernel, but the float32 codec skips the codec entirely "
                 "(lossless identity path); add --fl-codec int8 or topk")
    if args.fl_topk_frac != 0.05 and args.fl_codec != "topk":
        ap.error("--fl-topk-frac only affects the topk codec; add "
                 "--fl-codec topk")
    if args.ckpt_every and not args.ckpt_dir:
        ap.error("--ckpt-every needs --ckpt-dir")
    if args.stop_after and not args.ckpt_dir:
        ap.error("--stop-after simulates a kill mid-run and only makes "
                 "sense with --ckpt-dir (nothing would survive otherwise)")
    if args.ckpt_dir and args.driver == "reference":
        ap.error("--ckpt-dir periodic checkpointing drives the scan "
                 "driver; drop --driver reference")
    if args.ckpt_every < 0 or args.stop_after < 0 or args.keep_last < 1:
        ap.error("--ckpt-every/--stop-after must be >= 0, --keep-last >= 1")
    if args.susp_threshold and not args.health:
        ap.error("--susp-threshold gates selection on the suspicion EMA "
                 "the observatory maintains; add --health")
    if args.alerts_out and not args.health:
        ap.error("--alerts-out evaluates rules over the health_* metrics; "
                 "add --health")
    if args.health_bins != 16 and not args.health:
        ap.error("--health-bins only affects the observatory; add --health")

    return args


def build(args):
    """The run ``args`` describe: (cfg, fleet, traces, mesh, kw) with ``kw``
    the keywords ``train_fleet_scan`` / ``lower_fleet_scan`` take besides
    the metrics sink."""
    cfg = FCPOConfig() if args.fl_every is None else \
        FCPOConfig(fl_every=args.fl_every)
    faults = FaultConfig(
        crash_prob=args.fault_crash_prob,
        crash_recovery=args.fault_crash_recovery,
        byzantine_frac=args.fault_byzantine_frac,
        byzantine_mode=args.fault_byzantine_mode,
        byzantine_scale=args.fault_byzantine_scale,
        partition_prob=args.fault_partition_prob,
        partition_merges=args.fault_partition_merges,
        seed=args.fault_seed)
    guards = GuardConfig(agg=args.robust_agg, trim_frac=args.trim_frac,
                         clip_factor=args.clip_factor,
                         reject_nonfinite=not args.no_reject_nonfinite,
                         susp_threshold=args.susp_threshold)
    health = HealthConfig(bins=args.health_bins) if args.health else None
    transport = TransportConfig(codec=args.fl_codec,
                                topk_frac=args.fl_topk_frac,
                                deadline_s=args.fl_deadline_s,
                                async_rounds=args.fl_async,
                                use_pallas=args.fl_pallas)
    backend = get_backend(args.env_backend,
                          sim_params=SimParams(dt=args.dt,
                                               k_ticks=args.k_ticks,
                                               ring=args.ring),
                          use_pallas=args.pallas)
    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh(jax.device_count(), 1)
    elif args.mesh == "production":
        mesh = make_production_mesh(multi_pod=args.pods > 1)
    elif args.mesh == "fleet":
        mesh = make_fleet_mesh(jax.device_count(), args.pods)

    fleet = fleet_init(cfg, args.agents, jax.random.PRNGKey(args.seed),
                       n_pods=args.pods, mesh=mesh, env_backend=backend,
                       state_policy=(args.state_dtype
                                     if args.state_dtype != "float32"
                                     else None),
                       health=health)
    traces = make_scenario(args.scenario, jax.random.PRNGKey(args.seed + 1),
                           args.agents, args.episodes * cfg.n_steps)
    print(f"fleet: {args.agents} iAgents, {args.pods} pods, "
          f"{args.episodes} episodes, driver={args.driver}, "
          f"env={backend.name}, scenario={args.scenario}, "
          f"mesh={args.mesh}, state_dtype={args.state_dtype}, "
          f"backend={jax.default_backend()} "
          f"({jax.device_count()} devices)")

    kw = dict(learn=not args.no_learn, federated=not args.no_federated,
              straggler_prob=args.straggler_prob, seed=args.seed,
              env_backend=backend, transport=transport,
              faults=faults if faults.active else None, guards=guards,
              health=health)
    return cfg, fleet, traces, mesh, kw


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    cfg, fleet, traces, mesh, kw = build(args)
    health, faults, guards = kw["health"], kw["faults"], kw["guards"]
    backend = kw["env_backend"]
    # detect the auto-resume BEFORE opening the metrics sink: a resumed run
    # must append to the metrics file, not truncate the pre-kill episodes
    resume_from = (ckpt_mod.latest_step(args.ckpt_dir) or 0) \
        if args.ckpt_dir else 0
    sink = None
    if args.metrics_out:
        sink = MetricsSink(args.metrics_out, meta=dict(
            agents=args.agents, pods=args.pods, episodes=args.episodes,
            driver=args.driver, env_backend=backend.name,
            scenario=args.scenario, fl_codec=args.fl_codec,
            robust_agg=args.robust_agg, seed=args.seed),
            resume=resume_from > 0)
        if resume_from > 0 and sink.n_records:
            print(f"metrics resume: appending to {args.metrics_out} "
                  f"({sink.n_records} episodes already recorded)")
        kw["metrics_sink"] = sink
    engine = None
    if args.alerts_out:
        # the alert engine tees in front of the JSONL sink (or runs
        # standalone without --metrics-out): every streamed record is
        # forwarded AND evaluated against the rulebook
        engine = AlertEngine(args.alerts_out, forward=sink)
        kw["metrics_sink"] = engine
    if args.trace_out:
        # jax.profiler.trace's start/stop pair, spanning the try below; the
        # Python tracer stays off (its events, compilation's above all,
        # would fill the trace file's million-event cap before the run's)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(args.trace_out, create_perfetto_trace=True,
                                 profiler_options=opts)
    t0 = time.time()
    try:
        if args.ckpt_dir:
            # Periodic checkpointing + auto-resume. The full traces cover
            # [0, episodes); each chunk replays its slice with the absolute
            # episode_offset so straggler draws, fault plans, and merge
            # cadence match the uninterrupted run exactly.
            start = resume_from
            if start >= args.episodes:
                print(f"checkpoint step {start} >= --episodes "
                      f"{args.episodes}: run already complete, nothing to do")
                return fleet, {}
            if start > 0:
                fleet, _ = ckpt_mod.restore(args.ckpt_dir, start, fleet)
                print(f"auto-resume: restored episode {start} from "
                      f"{args.ckpt_dir}")
            chunk = args.ckpt_every or (args.episodes - start)
            hists, e, done_here = [], start, 0
            while e < args.episodes:
                n = min(chunk, args.episodes - e)
                if args.stop_after:
                    n = min(n, args.stop_after - done_here)
                tr = traces[:, e * cfg.n_steps:(e + n) * cfg.n_steps]
                fleet, h = train_fleet_scan(cfg, fleet, tr, mesh=mesh,
                                            episode_offset=e,
                                            total_episodes=args.episodes,
                                            **kw)
                hists.append(h)
                e += n
                done_here += n
                ckpt_mod.save(args.ckpt_dir, e, fleet, extra=dict(
                    episodes=args.episodes, agents=args.agents,
                    pods=args.pods, seed=args.seed,
                    scenario=args.scenario))
                ckpt_mod.keep_last(args.ckpt_dir, args.keep_last)
                if args.stop_after and done_here >= args.stop_after:
                    print(f"--stop-after {args.stop_after}: stopping at "
                          f"episode {e}/{args.episodes} (rerun the same "
                          f"command to resume)")
                    break
            hist = {k: np.concatenate([np.asarray(h[k]) for h in hists])
                    for k in hists[0]}
        elif args.driver == "scan":
            fleet, hist = train_fleet_scan(cfg, fleet, traces, mesh=mesh,
                                           **kw)
        else:
            fleet, hist = train_fleet_reference(cfg, fleet, traces, **kw)
        wall = time.time() - t0
        if sink is not None:
            # one trailing scaling record (same sink, same JSONL protocol):
            # wall-clock step time + where the fleet state actually landed,
            # device by device — launch/watch.py renders it as the scaling row
            n_rec = len(np.asarray(hist["reward"]))
            row = {"devices": float(mesh.size if mesh is not None else 1),
                   "agents": float(args.agents),
                   "step_time_s": wall / max(n_rec, 1),
                   "step_time_per_agent_s":
                       wall / max(n_rec, 1) / max(args.agents, 1),
                   "state_bytes_per_agent":
                       fleet_state_bytes(fleet)["per_agent"]}
            for d, b in sorted(fleet_device_bytes(fleet).items()):
                row[f"dev{d}_bytes"] = b
            sink.append(row)
    finally:
        if engine is not None:
            engine.close()  # closes the forwarded sink too
        elif sink is not None:
            sink.close()
        if args.trace_out:
            jax.profiler.stop_trace()
            print(f"profiler trace -> {args.trace_out} (open its "
                  f"perfetto_trace.json.gz in Perfetto)")

    n_run = len(np.asarray(hist["reward"]))
    k = max(n_run // 10, 1)
    print(f"\nwall {wall:.2f}s  ({wall / n_run * 1e3:.1f} ms/episode "
          f"incl. compile)")
    print(f"{'':24s}{'first ' + str(k) + ' eps':>16s}{'last ' + str(k) + ' eps':>16s}")
    for key, scale, unit in (("reward", 1, ""), ("throughput", 1, "/s"),
                             ("effective_throughput", 1, "/s"),
                             ("latency", 1e3, "ms"), ("gated", 1, "")):
        a, b = hist[key][:k].mean() * scale, hist[key][-k:].mean() * scale
        print(f"{key:24s}{a:12.3f}{unit:4s}{b:12.3f}{unit}")

    fl_eps = np.flatnonzero(hist.get("fl_payload_bytes", np.zeros(1)))
    if fl_eps.size:
        print(f"\nFL transport (codec={args.fl_codec}, "
              f"deadline={args.fl_deadline_s}s, async={args.fl_async}): "
              f"{fl_eps.size} rounds, "
              f"{hist['fl_payload_bytes'][fl_eps].mean() / 1024:.1f} KB/round, "
              f"uplink {hist['fl_uplink_s'][fl_eps].mean() * 1e3:.1f} ms, "
              f"missed {hist['fl_missed'][fl_eps].mean():.2f}/round, "
              f"stale joins {hist['fl_stale_used'][fl_eps].mean():.2f}/round, "
              f"rejected {np.asarray(hist.get('fl_rejected', 0.0)).sum():.0f}, "
              f"clipped {np.asarray(hist.get('fl_clipped', 0.0)).sum():.0f}")
    if health is not None and "health_drift_score" in hist:
        flags = np.asarray(hist["health_drift_flag"])
        print(f"\nhealth: drift flags on {np.count_nonzero(flags)} of "
              f"{flags.size} episodes, "
              f"drift score last {hist['health_drift_score'][-1]:.2f}, "
              f"reward p50 last {hist['health_reward_p50'][-1]:.3f}, "
              f"susp last {hist['health_susp'][-1]:.3f}"
              + (f"; {engine.n_alerts} alerts -> {args.alerts_out}"
                 if engine is not None else ""))
    if faults is not None:
        print(f"\nchaos: crash_prob={faults.crash_prob}, "
              f"byzantine={faults.byzantine_frac} "
              f"({faults.byzantine_mode} x{faults.byzantine_scale}), "
              f"partition={faults.partition_prob}; defenses: "
              f"agg={guards.agg}, clip={guards.clip_factor}, "
              f"reject_nonfinite={guards.reject_nonfinite}; "
              f"update_rejected "
              f"{np.asarray(hist.get('update_rejected', 0.0)).sum():.0f}")
    return fleet, hist


if __name__ == "__main__":
    main()
