"""Fleet-health benchmark: detection delay, attribution precision, overhead.

Four measurements over the health observatory (``repro.health``):

  * ``detection`` — a scripted load step (steady 15 req/interval, then a
    sustained jump to 90 at a known episode) against the in-scan drift
    detectors (CUSUM + Page-Hinkley over the standardized reward / arrival
    streams). Gates: the fleet-mean drift flag fires within
    ``DETECT_DELAY_MAX`` episodes of the change, and never fires in the
    armed window before it (no post-warmup false alarms). The same run
    streams through an ``AlertEngine`` writing ``ALERTS[_smoke].jsonl``
    (the CI artifact) — the ``drift-detected`` rule must fire.
  * ``attribution`` — the fig_chaos fault plan (A=8, 20% sign-flip
    byzantine uploads at 25x) replayed in ``fl_every``-episode chunks so
    every FL round's raw attribution snapshot (``health.susp_last`` /
    ``sel_last``) can be read back and scored against the host-side
    ground truth (``draw_fault_plan``). Gate: mean precision@k — the k
    corrupted clients of each round ranked inside the top-k suspicion
    slots among that round's selected clients — at least
    ``PRECISION_MIN``.
  * ``overhead`` — health-on vs health-off wall time on representative
    episode lengths.
    Gates: overhead within ``OVERHEAD_MAX``, and the health-on cadence
    stays ONE jitted scan (no per-episode host entries, same-shaped rerun
    hits the compiled executable).
  * ``identity`` — the off-mode contract: with ``health=None`` the staged
    program IS the pre-health program (the ``Fleet.health`` subtree
    flattens away), and with health ON every non-health output — shared
    metrics and every non-health fleet leaf — must stay bit-identical to
    the health-off run. Telemetry must observe, never perturb.

``--smoke --gate`` is the CI regression gate: asserts all of the above on
tiny shapes and writes ``BENCH_health_smoke.json`` (full runs write
``BENCH_health.json``). Policy in docs/observability.md.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import BENCH_DIR, load_rows, save_bench, save_rows
from repro.configs.fcpo import FCPOConfig
from repro.core import federated as fed
from repro.core.fleet import (_scan_fn, fleet_episode, fleet_init,
                              train_fleet_scan)
from repro.health import HealthConfig
from repro.health.alerts import AlertEngine, read_alerts
from repro.resilience import FaultConfig, GuardConfig, draw_fault_plan

# Episodes the drift flag may lag the scripted change by. The rate channel
# standardizes against the steady-state EMA, so a 15 -> 90 step is a
# clipped-z (|z| = zclip = 8) excursion and CUSUM (k=0.5, h=10) crosses in
# ceil(10 / 7.5) = 2 stride-mean samples — inside the first post-change
# episode at the default stride; the budget leaves one episode of slack
# for coarser stride/episode ratios.
DETECT_DELAY_MAX = 2
# Mean per-round precision@k of the suspicion ranking (k = number of
# corrupted selected clients that round). Sign-flip at 25x separates by
# both magnitude and direction, so the expected score is ~1.0; 0.8 tolerates
# one swapped round in five without letting ranking quality regress.
PRECISION_MIN = 0.8
# Health-on wall-time budget relative to health-off — the sketches are
# O(bins) scatter-adds per interval, far off the env+policy critical path.
OVERHEAD_MAX = 0.05
# fig_chaos's headline fault plan (the acceptance criterion names it).
BYZ_FRAC = 0.2
BYZ_SCALE = 25.0
TRIM_FRAC = 0.4


def _paired_overhead(fn_a, fn_b, iters):
    """ABBA-paired timing -> (min_us_a, min_us_b, overhead_frac).

    CI wall clocks flap in multi-second bursts larger than the budget
    being gated, so neither blocked min-of-N (all A, then all B) nor
    min(B)/min(A) over interleaved samples is stable. Back-to-back
    samples DO share their noise environment, so per-iteration ratios
    are stable even when both raw times are inflated — but a plain A,B
    pair still aliases monotone bursts onto whichever side runs second.
    Each iteration therefore times A,B,B,A and takes the ratio
    (b1+b2)/(a1+a2): a linear drift within the iteration contributes
    equally to both sums and cancels to first order. The gate uses the
    median of the iteration ratios (the mins are reported for absolute
    context only)."""
    def clock(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    ta, tb, ratios = [], [], []
    for _ in range(iters):
        a1 = clock(fn_a)
        b1 = clock(fn_b)
        b2 = clock(fn_b)
        a2 = clock(fn_a)
        ta += [a1, a2]
        tb += [b1, b2]
        ratios.append((b1 + b2) / (a1 + a2))
    ratios.sort()
    return (float(min(ta) * 1e6), float(min(tb) * 1e6),
            float(ratios[len(ratios) // 2] - 1.0))


def _step_traces(n_agents, n_eps, change_ep, n_steps, lo=15.0, hi=90.0):
    """Scripted fleet-wide load step: ``lo`` req/interval for episodes
    [0, change_ep), ``hi`` after — the cleanest possible change point, so
    the gate measures the detector, not the trace generator's noise."""
    t = np.arange(n_eps * n_steps)
    rates = np.where(t < change_ep * n_steps, lo, hi).astype(np.float32)
    return np.broadcast_to(rates, (n_agents, rates.size)).copy()


def _alerts_path(smoke: bool) -> str:
    os.makedirs(BENCH_DIR, exist_ok=True)
    return os.path.join(BENCH_DIR,
                        "ALERTS" + ("_smoke" if smoke else "") + ".jsonl")


def run_detection(n_agents=4, n_eps=16, change_ep=12, seed=0,
                  alerts_path=None):
    """Scripted step change vs the drift detectors, frozen policy.

    ``learn=False`` keeps the reward stream stationary before the change
    (a learning policy's improving reward IS drift — correct to flag, but
    it would confound the false-alarm window), so the pre-change flags
    measure detector noise alone."""
    cfg = FCPOConfig()
    health = HealthConfig()
    # the detectors arm after `warmup` stride-mean samples of EMA boot
    armed_ep = -(-(health.warmup * health.stride) // cfg.n_steps)  # ceil
    traces = _step_traces(n_agents, n_eps, change_ep, cfg.n_steps)
    fleet = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed),
                       health=health)
    engine = None
    if alerts_path is not None:
        engine = AlertEngine(alerts_path)
    fleet, hist = train_fleet_scan(cfg, fleet, traces, learn=False,
                                   donate=False, health=health,
                                   metrics_sink=engine)
    if engine is not None:
        engine.close()
    flags = np.asarray(hist["health_drift_flag"], dtype=np.float64)
    false_alarm_eps = [e for e in range(armed_ep, change_ep) if flags[e] > 0]
    fired = [e for e in range(change_ep, n_eps) if flags[e] > 0]
    delay = (fired[0] - change_ep) if fired else -1
    alerts = read_alerts(alerts_path) if alerts_path is not None else []
    drift_alerts = sum(1 for a in alerts if a.get("kind") == "alert"
                       and a.get("rule") == "drift-detected")
    return [{
        "name": "health_detection",
        "us_per_call": 0.0,
        "agents": n_agents, "episodes": n_eps,
        "change_ep": change_ep, "armed_ep": armed_ep,
        "detect_delay_eps": delay,
        "false_alarms": len(false_alarm_eps),
        "drift_score_final": float(np.asarray(
            hist["health_drift_score"])[-1]),
        "drift_alerts": drift_alerts,
        "alerts_path": alerts_path or "",
    }]


def run_attribution(n_agents=8, n_eps=16, seed=0):
    """fig_chaos's sign-flip plan, chunked at the FL cadence so each
    round's raw suspicion snapshot is scored against the pre-drawn ground
    truth. Chunking at ``fl_every`` keeps the chunked run identical to the
    uninterrupted one (the checkpoint-resume contract: fault and straggler
    draws are burned per ``episode_offset``)."""
    cfg = FCPOConfig()
    health = HealthConfig()
    faults = FaultConfig(byzantine_frac=BYZ_FRAC, byzantine_mode="sign_flip",
                         byzantine_scale=BYZ_SCALE, seed=seed)
    # trimmed aggregation keeps training sane under the 25x uploads (the
    # fig_chaos defense); attribution scores the wire contribs regardless
    guards = GuardConfig(agg="trimmed", trim_frac=TRIM_FRAC)
    schedule = fed.fl_schedule(cfg, n_eps)
    plan = draw_fault_plan(schedule, n_agents, 1, faults)
    from repro.data.workload import fleet_traces
    traces = np.asarray(fleet_traces(jax.random.PRNGKey(seed + 1), n_agents,
                                     n_eps * cfg.n_steps))
    fleet = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed),
                       health=health)
    chunk = cfg.fl_every
    precisions, rounds_scored = [], 0
    for off in range(0, n_eps, chunk):
        tr = traces[:, off * cfg.n_steps:(off + chunk) * cfg.n_steps]
        fleet, _ = train_fleet_scan(cfg, fleet, tr, donate=False,
                                    faults=faults, guards=guards,
                                    seed=seed, episode_offset=off,
                                    total_episodes=n_eps, health=health)
        round_ep = off + chunk - 1  # the chunk's FL episode (0-indexed)
        if not schedule[round_ep]:
            continue
        sel = np.asarray(fleet.health.sel_last) > 0
        susp = np.asarray(fleet.health.susp_last, dtype=np.float64)
        byz = plan.byzantine[round_ep] & sel
        k = int(byz.sum())
        if k == 0 or k == int(sel.sum()):
            continue  # no ranking to score this round
        # top-k suspicion among the selected clients
        sel_idx = np.flatnonzero(sel)
        order = sel_idx[np.argsort(-susp[sel_idx], kind="stable")]
        topk = set(order[:k].tolist())
        precisions.append(len(topk & set(np.flatnonzero(byz))) / k)
        rounds_scored += 1
    precision = float(np.mean(precisions)) if precisions else -1.0
    return [{
        "name": "health_attribution",
        "us_per_call": 0.0,
        "agents": n_agents, "episodes": n_eps,
        "byzantine_frac": BYZ_FRAC, "byzantine_scale": BYZ_SCALE,
        "rounds_scored": rounds_scored,
        "precision_at_k": precision,
        "susp_final_max": float(np.asarray(fleet.health.susp).max()),
    }]


def run_overhead(n_agents=4, n_eps=4, n_steps=4000, iters=7, seed=0):
    """Health-on vs health-off A/B on one fleet run: wall-time overhead,
    off-mode bit-identity of every shared output, and the structural scan
    gates. ``n_steps`` is raised above the config default: the overhead
    *fraction* only means something against representative episode
    durations."""
    cfg = FCPOConfig(n_steps=n_steps)
    health = HealthConfig()
    from repro.data.workload import fleet_traces
    traces = fleet_traces(jax.random.PRNGKey(seed + 1), n_agents,
                          n_eps * cfg.n_steps)
    fleet_off = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed))
    fleet_on = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed),
                          health=health)

    # donate=False so the same fleet pytrees can be replayed for timing
    run_off = lambda: train_fleet_scan(cfg, fleet_off, traces, donate=False)
    run_on = lambda: train_fleet_scan(cfg, fleet_on, traces, donate=False,
                                      health=health)
    f0, h0 = run_off()  # also the warmup/compile for each variant
    ep_before = fleet_episode._cache_size()
    f1, h1 = run_on()
    one_jitted_scan = fleet_episode._cache_size() == ep_before

    # health must observe, never perturb: every output the two runs share
    # — the health-off metrics and every non-health fleet leaf — must be
    # bit-identical (the health-on run only ADDS the health_* keys and the
    # Fleet.health subtree)
    shared_metrics = all(
        np.array_equal(np.asarray(h0[k]), np.asarray(h1[k])) for k in h0)
    off_leaves = jax.tree.leaves(f0._replace(health=None))
    on_leaves = jax.tree.leaves(f1._replace(health=None))
    shared_state = (len(off_leaves) == len(on_leaves) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(off_leaves, on_leaves)))

    # a same-shaped health-on rerun must hit the compiled executable
    size = _scan_fn(False)._cache_size()
    run_on()
    compiled_once = _scan_fn(False)._cache_size() == size

    us_off, us_on, overhead_frac = _paired_overhead(run_off, run_on, iters)
    return [{
        "name": "health_overhead",
        "us_per_call": us_on,
        "agents": n_agents, "episodes": n_eps, "n_steps": n_steps,
        "iters": iters,
        "us_off": us_off, "us_on": us_on,
        "overhead_frac": overhead_frac,
        "bit_identical_metrics": bool(shared_metrics),
        "bit_identical_state": bool(shared_state),
        "one_jitted_scan": bool(one_jitted_scan),
        "compiled_once": bool(compiled_once),
        "extra_health_leaves": len(jax.tree.leaves(f1))
        - len(jax.tree.leaves(f0)),
    }]


def run(quick: bool = True, smoke: bool = False, fresh: bool = False):
    """Raw benchmark rows. ``smoke``: tiny CI shapes, never cached.
    ``fresh``: bypass the artifact cache (the gate must measure this
    run)."""
    if smoke:
        return (run_detection(alerts_path=_alerts_path(True))
                + run_attribution()
                + run_overhead())
    if not fresh:
        cached = load_rows("fig_health")
        if cached:
            return cached
    rows = (run_detection(n_eps=28, change_ep=20,
                          alerts_path=_alerts_path(False))
            + run_attribution(n_eps=32)
            + run_overhead(n_steps=4000, iters=7 if quick else 11))
    save_rows("fig_health", rows)
    return rows


def format_rows(rows):
    out = []
    for r in rows:
        derived = f"A={r['agents']} eps={r['episodes']}"
        if "detect_delay_eps" in r:
            derived += (f" delay={r['detect_delay_eps']} eps "
                        f"false_alarms={r['false_alarms']} "
                        f"alerts={r['drift_alerts']}")
        if "precision_at_k" in r:
            derived += (f" precision@k={r['precision_at_k']:.2f} "
                        f"over {r['rounds_scored']} rounds")
        if "overhead_frac" in r:
            derived += (f" overhead={r['overhead_frac'] * 100:+.1f}% "
                        f"identical={r['bit_identical_metrics'] and r['bit_identical_state']} "
                        f"one_jitted_scan={r['one_jitted_scan']} "
                        f"compiled_once={r['compiled_once']}")
        out.append({"name": r["name"], "us_per_call":
                    f"{r['us_per_call']:.0f}", "derived": derived})
    return out


def _run_and_save(quick: bool = True, smoke: bool = False,
                  fresh: bool = False):
    rows = run(quick, smoke=smoke, fresh=fresh)
    save_bench("health" + ("_smoke" if smoke else ""), rows)
    return rows


def main(quick: bool = True, smoke: bool = False):
    return format_rows(_run_and_save(quick, smoke=smoke))


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit_csv

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI regression checks")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless the drift flag fires within "
                         "the delay budget with no armed-window false "
                         "alarms, the suspicion ranking isolates the "
                         "byzantine clients, health-on stays within the "
                         "overhead budget as one compiled scan, and "
                         "health-off outputs stay bit-identical "
                         "(always re-measures)")
    args = ap.parse_args()
    raw = _run_and_save(smoke=args.smoke, fresh=args.gate)
    emit_csv(format_rows(raw))
    if args.gate:
        by = {r["name"]: r for r in raw}
        det = by["health_detection"]
        assert det["detect_delay_eps"] >= 0, (
            "drift detectors never flagged the scripted 15 -> 90 load step")
        assert det["detect_delay_eps"] <= DETECT_DELAY_MAX, (
            f"drift detection lagged the change by "
            f"{det['detect_delay_eps']} episodes "
            f"(budget {DETECT_DELAY_MAX})")
        assert det["false_alarms"] == 0, (
            f"drift flag fired {det['false_alarms']} time(s) in the armed "
            f"pre-change window — the detectors are alarming on a "
            f"stationary stream")
        assert det["drift_alerts"] >= 1, (
            "the drift-detected alert rule never fired on a detected "
            "change — the AlertEngine tee is not seeing the health metrics")
        att = by["health_attribution"]
        assert att["rounds_scored"] > 0, (
            "no FL round had a scoreable byzantine/honest split — the "
            "fault plan is not injecting")
        assert att["precision_at_k"] >= PRECISION_MIN, (
            f"suspicion ranking no longer isolates the sign-flip clients: "
            f"precision@k {att['precision_at_k']:.2f} over "
            f"{att['rounds_scored']} rounds (min {PRECISION_MIN})")
        ov = by["health_overhead"]
        assert ov["bit_identical_metrics"] and ov["bit_identical_state"], (
            "health-on run perturbed a shared output — telemetry must "
            "observe, never steer (bit-identity contract)")
        assert ov["one_jitted_scan"], (
            "health-on run touched the per-episode host entry point — the "
            "sketches must stay inside the ONE jitted scan")
        assert ov["compiled_once"], (
            "health-on scan recompiled on a same-shaped rerun")
        assert ov["overhead_frac"] <= OVERHEAD_MAX, (
            f"health overhead {ov['overhead_frac'] * 100:.1f}% exceeds "
            f"the {OVERHEAD_MAX * 100:.0f}% budget")
        print("health gate: pass")
