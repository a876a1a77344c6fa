"""Request-level twin launcher — evaluate FCPO policies on the digital twin.

Builds a fleet (optionally quick-trained on the fluid MDP first), drives it
through the tensorized request-level simulator (``repro.sim``) on a named
workload scenario, and prints request-grade metrics: throughput, effective
throughput, p50/p99 end-to-end latency, and drops. ``--compare-fluid``
additionally evaluates the same policies on the fluid ``core/env.py`` MDP
over the same traces and prints the fidelity gap.

``--stages 2`` evaluates pipelines: every camera runs a detector on each
frame and a second model (re-ID) on every object it finds, one iAgent per
model, coupled through the twin's fan-out (``repro.sim.Pipeline``); the
objects per frame come from ``data.workload.object_counts``. Each stage
takes its own speed factor and SLO (``--stage-speed``, ``--stage-slo``).

Examples:
  PYTHONPATH=src python -m repro.launch.simulate --agents 8 --intervals 60
  PYTHONPATH=src python -m repro.launch.simulate --agents 16 --scenario ood \
      --train-episodes 40 --compare-fluid
  PYTHONPATH=src python -m repro.launch.simulate --agents 4 --pallas
  PYTHONPATH=src python -m repro.launch.simulate --agents 40 --stages 2 \
      --stage-speed 1,4 --stage-slo 0.15,0.10 --scenario burst --pallas
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.fcpo import FCPOConfig
from repro.core.backends import BACKENDS, get_backend
from repro.core.fleet import device_speeds, fleet_init, train_fleet
from repro.data.workload import fleet_traces, object_counts
from repro.launch import compile_cache
from repro.sim import (SCENARIOS, Pipeline, SimParams, make_scenario,
                       pipeline_env_params, simulate_fleet)


def _floats(text):
    return [float(x) for x in text.split(",")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=8,
                    help="cameras; each runs --stages models, one iAgent "
                         "per model")
    ap.add_argument("--stages", type=int, default=1,
                    help="models chained per camera (2 = detector -> "
                         "re-ID); a stage after the first gets its "
                         "upstream's completions times the objects per "
                         "frame")
    ap.add_argument("--stage-speed", type=_floats, metavar="F,F,...",
                    help="per-stage device speed factor, times the "
                         "camera's device (default: 1 for every stage)")
    ap.add_argument("--stage-slo", type=_floats, metavar="S,S,...",
                    help="per-stage deadline (s) from the stage's own "
                         "arrival (default: the SLO split evenly)")
    ap.add_argument("--intervals", type=int, default=60,
                    help="control intervals to simulate")
    ap.add_argument("--scenario", choices=SCENARIOS, default="dynamic")
    ap.add_argument("--train-episodes", type=int, default=0,
                    help="warmup training episodes before evaluation "
                         "(0 = untrained policies)")
    ap.add_argument("--train-backend", choices=BACKENDS, default="fluid",
                    help="environment backend the warmup episodes train in "
                         "(twin = 'train where you serve')")
    ap.add_argument("--dt", type=float, default=0.05,
                    help="microtick length in seconds")
    ap.add_argument("--k-ticks", type=int, default=20,
                    help="microticks per control interval")
    ap.add_argument("--ring", type=int, default=512,
                    help="ring capacity (power of two)")
    ap.add_argument("--hist", type=int, default=64,
                    help="latency histogram buckets (ticks)")
    ap.add_argument("--pallas", action="store_true",
                    help="route the data plane through the fused Pallas "
                         "queue_advance kernel")
    ap.add_argument("--compare-fluid", action="store_true",
                    help="also evaluate on the fluid MDP and print the gap")
    ap.add_argument("--attribution", action="store_true",
                    help="record per-microtick counters and print the "
                         "per-request stage latency decomposition "
                         "(jnp path only)")
    ap.add_argument("--attr-sample", type=int, default=16,
                    help="keep every Nth request in the attribution "
                         "records / Chrome trace")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="write the sampled request lifecycles as Chrome "
                         "trace-event JSON (open in Perfetto); implies "
                         "--attribution")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.intervals < 1:
        ap.error("--intervals must be >= 1")
    if args.ring <= 0 or args.ring & (args.ring - 1):
        ap.error("--ring must be a positive power of two")
    if args.trace_out:
        args.attribution = True
    if args.attribution and args.pallas:
        ap.error("--attribution needs the jnp data plane (drop --pallas): "
                 "the fused kernel advances whole intervals per call")
    if args.stages < 1:
        ap.error("--stages must be >= 1")
    for name in ("stage_speed", "stage_slo"):
        val = getattr(args, name)
        if val is not None and len(val) != args.stages:
            ap.error(f"--{name.replace('_', '-')} needs one value per stage "
                     f"({args.stages}), got {len(val)}")
    if args.stages > 1 and args.train_episodes > 0:
        ap.error("--stages > 1 evaluates untrained policies: training "
                 "through coupled stages is not supported yet")
    if args.stages > 1 and args.compare_fluid:
        ap.error("--compare-fluid has no pipeline counterpart in the fluid "
                 "MDP; drop it with --stages > 1")

    cfg = FCPOConfig()
    pipe = Pipeline(args.agents, args.stages)
    if args.compare_fluid and args.intervals % cfg.n_steps:
        # the fluid plane evaluates in whole episodes; keep both planes on
        # the identical workload window
        args.intervals = max(args.intervals // cfg.n_steps, 1) * cfg.n_steps
        print(f"note: --compare-fluid rounds the horizon to whole episodes "
              f"-> {args.intervals} intervals")
    sp = SimParams(dt=args.dt, k_ticks=args.k_ticks, ring=args.ring,
                   hist_n=args.hist)
    train_be = get_backend(args.train_backend, sim_params=sp,
                           use_pallas=args.pallas)
    fleet = fleet_init(cfg, pipe.agents, jax.random.PRNGKey(args.seed),
                       env_backend=train_be)
    if args.train_episodes > 0:
        warmup = fleet_traces(jax.random.PRNGKey(args.seed + 1), args.agents,
                              args.train_episodes * cfg.n_steps)
        fleet, _ = train_fleet(cfg, fleet, warmup, env_backend=train_be)
    objects = None
    if args.stages > 1 or args.stage_speed or args.stage_slo:
        fleet = fleet._replace(env_params=pipeline_env_params(
            pipe, device_speeds(args.agents),
            args.stage_speed or [1.0] * args.stages,
            args.stage_slo or [cfg.slo_s / args.stages] * args.stages))
    if args.stages > 1:
        objects = object_counts(jax.random.PRNGKey(args.seed + 4),
                                args.agents, args.intervals)
    traces = make_scenario(args.scenario, jax.random.PRNGKey(args.seed + 2),
                           args.agents, args.intervals)

    print(f"twin: {args.agents} cameras x {args.stages} stage(s), "
          f"{args.intervals} intervals, "
          f"K={sp.k_ticks} microticks of {sp.dt * 1e3:.0f} ms, "
          f"ring={sp.ring}, scenario={args.scenario}, "
          f"pallas={args.pallas}, trained={args.train_episodes} eps "
          f"on {train_be.name}, backend={jax.default_backend()}")
    t0 = time.time()
    state, history, summ, _ = simulate_fleet(
        cfg, sp, fleet.astate.params, fleet.masks, fleet.env_params, traces,
        jax.random.PRNGKey(args.seed + 3), use_pallas=args.pallas,
        record_ticks=args.attribution, pipeline=pipe, objects=objects)
    jax.block_until_ready(state.counters)
    wall = time.time() - t0
    ticks = args.intervals * sp.k_ticks
    print(f"wall {wall:.2f}s incl. compile "
          f"({wall / ticks * 1e6:.0f} us/microtick for the fleet)\n")

    rows = [("throughput", "req/s"), ("effective_throughput", "req/s"),
            ("mean_latency_s", "s"), ("p50_latency_s", "s"),
            ("p99_latency_s", "s"), ("drop_rate", ""),
            ("hist_censored", "")]
    print(f"{'metric':24s}{'fleet mean':>12s}{'min':>10s}{'max':>10s}")
    for k, unit in rows:
        v = np.asarray(summ[k])
        print(f"{k:24s}{v.mean():10.3f} {unit:4s}{v.min():9.3f}{v.max():10.3f}")
    print(f"{'requests':24s}arrived={int(np.asarray(summ['arrived']).sum())} "
          f"completed={int(np.asarray(summ['completed']).sum())} "
          f"dropped={int(np.asarray(summ['dropped']).sum())}")
    if args.stages > 1:
        print(stage_table(pipe, summ, history))
    # >1% right-censored completions triggers warn_if_censored inside
    # simulate_fleet (one shared check); the hist_censored row above is the
    # always-on surface.

    if args.attribution:
        from repro.obs import requests as obs_requests
        from repro.sim.metrics import stage_breakdown_table

        attr = obs_requests.attribute_run(history, state,
                                          sample_every=args.attr_sample)
        bad = [i for i, rep in enumerate(attr["conservation"])
               if not rep["ok"]]
        dec = obs_requests.stage_decomposition(attr["agents"], sp.dt)
        print(f"\nrequest attribution ({len(attr['records'])} sampled "
              f"records, 1/{args.attr_sample}; conservation "
              f"{'FAILED for agents ' + str(bad) if bad else 'exact'})")
        print(stage_breakdown_table(dec))
        if args.trace_out:
            from repro.obs.trace import Tracer

            tr = Tracer()
            n = obs_requests.records_to_chrome(tr, attr["records"], sp.dt)
            tr.export(args.trace_out)
            print(f"wrote {n} request slices -> {args.trace_out} "
                  f"(open in Perfetto / chrome://tracing)")

    if args.compare_fluid:
        hist = _fluid_eval(cfg, fleet, traces)
        eff_f = float(np.mean(hist["effective_throughput"]))
        eff_t = float(np.asarray(summ["effective_throughput"]).mean())
        gap = abs(eff_f - eff_t) / max(abs(eff_f), 1e-9)
        print(f"\nfluid-vs-twin effective throughput: fluid={eff_f:.2f} "
              f"twin={eff_t:.2f} gap={gap * 100:.1f}%")
    return summ


def stage_table(pipe, summ, history):
    """Per-stage request totals of a pipeline run: the requests that
    arrived (and of them, those routed in from upstream), and the shares
    completed, completed late and dropped."""
    lines = [f"{'stage':8s}{'arrived':>10s}{'routed':>10s}{'completed':>11s}"
             f"{'late':>8s}{'dropped':>9s}"]
    routed = np.asarray(history["routed"]).sum(axis=0)
    for s in range(pipe.stages):
        r = pipe.rows(s)
        tot = {k: int(np.asarray(summ[k])[r].sum())
               for k in ("arrived", "completed", "effective", "dropped")}
        n = max(tot["arrived"], 1)
        lines.append(
            f"{s:<8d}{tot['arrived']:10d}{int(routed[r].sum()):10d}"
            f"{tot['completed'] / n:11.3f}"
            f"{(tot['completed'] - tot['effective']) / n:8.3f}"
            f"{tot['dropped'] / n:9.3f}")
    return "\n".join(lines)


def _fluid_eval(cfg, fleet, traces):
    """Evaluate (no learning) on the fluid MDP over the same traces. The
    fleet may have been trained on any backend — its env states are swapped
    for fresh fluid ones so the policies (not the env leaves) carry over."""
    from repro.core.env import env_init

    a = traces.shape[0]
    fluid_states = jax.vmap(lambda _: env_init(cfg))(jax.numpy.arange(a))
    fleet = fleet._replace(astate=fleet.astate._replace(
        env_state=fluid_states))
    n_eps = max(traces.shape[1] // cfg.n_steps, 1)
    _, hist = train_fleet(cfg, fleet, traces[:, :n_eps * cfg.n_steps],
                          learn=False, federated=False)
    return hist


if __name__ == "__main__":
    main()
