"""Digital-twin benchmark: twin vs the host-side Python data plane, and the
fluid-MDP vs request-level fidelity gap.

Two measurements over identical arrivals/caps/seeds:

  * ``speed`` — the tensorized twin (one jitted ``lax.scan``, vmapped over
    A=64 agents; jnp path and the fused Pallas ``queue_advance`` kernel)
    against the ``serving/slo.py`` Python oracle (``repro.sim.oracle``, the
    deque/list data plane driven agent-by-agent from the host). Service
    capacities are integer-representable so the two paths must also agree
    request-for-request — the ``totals_match`` column is an equivalence
    gate, not an approximation.
  * ``fidelity`` — a fluid-MDP-trained fleet evaluated on BOTH planes over
    the same traces: per-interval effective throughput from ``core/env.py``
    (Little's-law latency surface) vs the twin's per-request deadline
    accounting, reported as a relative gap plus the twin-only request-grade
    metrics (p50/p99 latency, drops) the fluid model cannot produce.

Reported: warm wall clock per simulated run, twin speedup vs the Python
path (acceptance: >= 5x at A=64 on CPU), and the fluid-vs-twin gap.
``--min-speedup`` is the CI regression gate (smoke shapes).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import load_rows, save_bench, save_rows, time_call
from repro.configs.fcpo import FCPOConfig
from repro.core.fleet import fleet_init, train_fleet
from repro.data.workload import fleet_traces
from repro.sim import SimParams, sim_init, sim_interval, simulate_fleet, \
    spread_arrivals
from repro.sim.oracle import simulate_python_fleet

# Integer-representable caps (pre/tick, post/tick, batch, t_batch ticks,
# qcap, slo ticks): exact in float32 and float64, so twin == slo.py exactly.
SPEED_CAPS = (6.0, 8.0, 16.0, 2.0, 21.0, 5.0)


@partial(jax.jit, static_argnames=("use_pallas",))
def _twin_run(state, arr_seq, caps, use_pallas=False):
    def body(s, arr):
        return sim_interval(s, arr, caps, use_pallas), None
    s, _ = jax.lax.scan(body, state, arr_seq)
    return s


def run_speed(n_agents=64, n_intervals=10, ring=64, hist_n=16, iters=7,
              with_pallas=True):
    """Data-plane-only A/B on a fixed action schedule (policy cost excluded
    on every path so the comparison is queue dynamics vs queue dynamics)."""
    sp = SimParams(dt=0.05, k_ticks=20, ring=ring, hist_n=hist_n)
    rng = np.random.default_rng(0)
    rates = rng.uniform(150, 350, (n_agents, n_intervals)).astype(np.float32)
    arrivals = np.asarray(jax.vmap(jax.vmap(
        lambda r: spread_arrivals(sp, r)[0]))(jnp.asarray(rates)))  # (A,T,K)
    caps = jnp.broadcast_to(jnp.asarray(SPEED_CAPS, jnp.float32),
                            (n_agents, 6))
    state0 = jax.vmap(lambda _: sim_init(sp))(jnp.arange(n_agents))
    arr_seq = jnp.asarray(arrivals.transpose(1, 0, 2))  # (T, A, K)

    import time as _time
    py_caps = np.broadcast_to(np.asarray(caps[0]),
                              (n_agents, n_intervals, 6)).copy()
    py_ts = []
    for _ in range(3):
        t0 = _time.perf_counter()
        py = simulate_python_fleet(arrivals, py_caps, sp)
        py_ts.append(_time.perf_counter() - t0)
    py_us = float(np.median(py_ts)) * 1e6
    totals = {k: sum(p[k] for p in py)
              for k in ("completed", "dropped", "effective", "lat_sum")}

    shape = {"agents": n_agents, "intervals": n_intervals,
             "microticks": n_intervals * sp.k_ticks, "ring": ring}
    rows = [{"name": "sim_python_oracle", "us_per_call": py_us, **shape,
             "speedup_vs_python": 1.0, "totals_match": True}]
    drivers = [("sim_twin_jnp", False)]
    if with_pallas:
        drivers.append(("sim_twin_pallas", True))
    for name, use_pallas in drivers:
        us = time_call(partial(_twin_run, use_pallas=use_pallas),
                       state0, arr_seq, caps, iters=iters)
        out = _twin_run(state0, arr_seq, caps, use_pallas=use_pallas)
        match = (int(out.completed.sum()) == totals["completed"]
                 and int(out.dropped.sum()) == totals["dropped"]
                 and int(out.effective.sum()) == totals["effective"]
                 and float(out.lat_sum.sum()) == totals["lat_sum"])
        rows.append({"name": name, "us_per_call": us, **shape,
                     "speedup_vs_python": py_us / us,
                     "totals_match": bool(match)})
    return rows


def run_fidelity(n_agents=8, train_episodes=40, eval_intervals=40, seed=0):
    """Fluid-vs-twin effective-throughput gap for a trained policy."""
    cfg = FCPOConfig()
    sp = SimParams()
    fleet = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed))
    if train_episodes > 0:
        warmup = fleet_traces(jax.random.PRNGKey(seed + 1), n_agents,
                              train_episodes * cfg.n_steps)
        fleet, _ = train_fleet(cfg, fleet, warmup)

    n_eps = max(eval_intervals // cfg.n_steps, 1)
    traces = fleet_traces(jax.random.PRNGKey(seed + 2), n_agents,
                          n_eps * cfg.n_steps)
    _, hist_fluid = train_fleet(cfg, fleet, traces, learn=False,
                                federated=False)
    summ = simulate_fleet(cfg, sp, fleet.astate.params, fleet.masks,
                          fleet.env_params, traces,
                          jax.random.PRNGKey(seed + 3)).summary
    eff_fluid = float(np.mean(hist_fluid["effective_throughput"]))
    eff_twin = float(np.asarray(summ["effective_throughput"]).mean())
    thr_fluid = float(np.mean(hist_fluid["throughput"]))
    thr_twin = float(np.asarray(summ["throughput"]).mean())
    return [{
        "name": "sim_fidelity_fluid_vs_twin",
        "us_per_call": 0.0,
        "agents": n_agents,
        "train_episodes": train_episodes,
        "thr_fluid": thr_fluid,
        "thr_twin": thr_twin,
        "thr_gap": abs(thr_fluid - thr_twin) / max(abs(thr_fluid), 1e-9),
        "eff_fluid": eff_fluid,
        "eff_twin": eff_twin,
        "eff_gap": abs(eff_fluid - eff_twin) / max(abs(eff_fluid), 1e-9),
        "twin_p50_s": float(np.asarray(summ["p50_latency_s"]).mean()),
        "twin_p99_s": float(np.asarray(summ["p99_latency_s"]).mean()),
        "twin_drop_rate": float(np.asarray(summ["drop_rate"]).mean()),
    }]


def run(quick: bool = True, smoke: bool = False, fresh: bool = False):
    """Raw benchmark rows. ``smoke``: tiny CI shapes, never cached.
    ``fresh``: bypass the artifact cache (a regression gate must measure
    this run, not a stale artifact)."""
    if smoke:
        return (run_speed(n_agents=4, n_intervals=3, iters=3)
                + run_fidelity(n_agents=2, train_episodes=2,
                               eval_intervals=10))
    if not fresh:
        cached = load_rows("fig_sim_fidelity")
        if cached:
            return cached
    rows = (run_speed(iters=7 if quick else 21)
            + run_fidelity(train_episodes=40 if quick else 120))
    save_rows("fig_sim_fidelity", rows)
    return rows


def format_rows(rows):
    out = []
    for r in rows:
        if "eff_gap" in r:
            derived = (f"A={r['agents']} "
                       f"thr_gap={r['thr_gap'] * 100:.1f}% "
                       f"eff_fluid={r['eff_fluid']:.2f}/s "
                       f"eff_twin={r['eff_twin']:.2f}/s "
                       f"eff_gap={r['eff_gap'] * 100:.1f}% "
                       f"p50={r['twin_p50_s'] * 1e3:.0f}ms "
                       f"p99={r['twin_p99_s'] * 1e3:.0f}ms "
                       f"drops={r['twin_drop_rate'] * 100:.1f}%")
        else:
            derived = (f"A={r['agents']} ticks={r['microticks']} "
                       f"ring={r['ring']} "
                       f"speedup={r['speedup_vs_python']:.1f}x "
                       f"totals_match={r['totals_match']}")
        out.append({"name": r["name"],
                    "us_per_call": f"{r['us_per_call']:.0f}",
                    "derived": derived})
    return out


def _run_and_save(quick: bool = True, smoke: bool = False,
                  fresh: bool = False):
    rows = run(quick, smoke=smoke, fresh=fresh)
    save_bench("sim_fidelity" + ("_smoke" if smoke else ""), rows)
    return rows


def main(quick: bool = True, smoke: bool = False):
    return format_rows(_run_and_save(quick, smoke=smoke))


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit_csv

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI perf-path regression checks")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="exit nonzero unless the jnp twin beats the Python "
                         "slo.py path by this factor (always re-measures)")
    args = ap.parse_args()
    raw = _run_and_save(smoke=args.smoke,
                        fresh=args.min_speedup is not None)
    emit_csv(format_rows(raw))
    if args.min_speedup is not None:
        for r in raw:
            if r["name"].startswith("sim_twin"):
                assert r["totals_match"], \
                    f"{r['name']} diverged from the slo.py oracle"
        twin = next(r for r in raw if r["name"] == "sim_twin_jnp")
        speedup = twin["speedup_vs_python"]
        assert speedup >= args.min_speedup, (
            f"twin speedup {speedup:.2f}x < required "
            f"{args.min_speedup:.2f}x")
