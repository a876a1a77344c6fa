"""Flight-recorder benchmark: XLA cost and memory accounting.

One envelope (``BENCH_profile.json``): cost/memory accounting of the EXACT
compiled programs the training path runs. ``obs.profile.profile_fleet_scan``
lowers the same ``_scan_fn`` the driver dispatches (donation included) and
reads XLA's ``cost_analysis``/``memory_analysis``; ``profile_kernels`` does
the same for every kernel jit in ``kernels.ops.KERNEL_JITS`` at its
canonical workload shape. The donation audit (every fleet leaf wired to an
aliased output in the stablehlo) is a ``--gate`` assertion — a refactor
that silently drops donation doubles training peak memory. Phase timing is
the profiler's, measured on the chip by the ``bench/`` harness.

Deltas: ``flops`` / ``bytes_accessed`` / ``peak_bytes`` against the
previous envelope at the same path are attached as ``prev_*`` fields
(cross-backend baselines are refused via the leaderboard's
``sanitize_envelope`` — a CPU-vs-TPU memory diff is noise, not signal).
"""
from __future__ import annotations

import jax

from benchmarks.common import load_bench, load_rows, save_bench, save_rows
from repro.configs.fcpo import FCPOConfig
from repro.core.fleet import fleet_init
from repro.obs.profile import profile_fleet_scan, profile_kernels
from repro.sim import make_scenario

DELTA_METRICS = ("flops", "bytes_accessed", "peak_bytes")


def run_static(n_agents=8, episodes=4, seed=0):
    """Cost/memory rows for the scanned fleet driver + every kernel jit."""
    cfg = FCPOConfig()
    fleet = fleet_init(cfg, n_agents, jax.random.PRNGKey(seed))
    traces = make_scenario("steady", jax.random.PRNGKey(seed + 1), n_agents,
                           episodes * cfg.n_steps)
    stats = profile_fleet_scan(cfg, fleet, traces, donate=True)
    rows = [{"name": "profile_fleet_scan", "us_per_call": 0.0,
             "agents": n_agents, "episodes": episodes, **stats}]
    for kname, ks in sorted(profile_kernels().items()):
        rows.append({"name": f"profile_kernel_{kname}",
                     "us_per_call": 0.0, **ks})
    return rows


def run(quick: bool = True, smoke: bool = False, fresh: bool = False):
    """Raw benchmark rows. ``smoke``: tiny CI shapes, never cached.
    ``fresh``: bypass the artifact cache (a regression gate must measure
    this run, not a stale artifact)."""
    if smoke:
        return run_static(n_agents=4, episodes=2)
    if not fresh:
        cached = load_rows("fig_profile")
        if cached:
            return cached
    rows = run_static()
    save_rows("fig_profile", rows)
    return rows


def attach_prev(rows, prev_envelope):
    """Attach ``prev_<metric>`` / ``delta_<metric>`` fields from the
    previous envelope's same-named rows (None envelope: no-op)."""
    if not prev_envelope:
        return rows
    by_name = {r.get("name"): r for r in prev_envelope.get("results", [])
               if isinstance(r, dict)}
    for r in rows:
        p = by_name.get(r.get("name"))
        if not p:
            continue
        for m in DELTA_METRICS:
            try:
                prev, new = float(p[m]), float(r[m])
            except (KeyError, TypeError, ValueError):
                continue
            r[f"prev_{m}"] = prev
            r[f"delta_{m}"] = new - prev
    return rows


def format_rows(rows):
    out = []
    for r in rows:
        derived = (f"flops={r['flops']:.3g} "
                   f"bytes={r['bytes_accessed']:.3g} "
                   f"peak={r['peak_bytes'] / 1e6:.2f}MB")
        if "donation_ok" in r:
            derived += (f" donated={r['donated_leaves']:.0f} "
                        f"aliased={r['aliased_args']:.0f} "
                        f"donation_ok={bool(r['donation_ok'])}")
        if "delta_peak_bytes" in r:
            derived += f" dpeak={r['delta_peak_bytes'] / 1e6:+.2f}MB"
        out.append({"name": r["name"],
                    "us_per_call": f"{r['us_per_call']:.0f}",
                    "derived": derived})
    return out


def _run_and_save(quick: bool = True, smoke: bool = False,
                  fresh: bool = False):
    from repro.eval.leaderboard import sanitize_envelope
    name = "profile" + ("_smoke" if smoke else "")
    rows = run(quick, smoke=smoke, fresh=fresh)
    prev = sanitize_envelope(load_bench(name), warn=print)
    attach_prev(rows, prev)
    save_bench(name, rows)
    return rows


def main(quick: bool = True, smoke: bool = False):
    return format_rows(_run_and_save(quick, smoke=smoke))


if __name__ == "__main__":
    import argparse

    from benchmarks.common import emit_csv

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI perf-path regression checks")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless the donation audit passes "
                         "(always re-measures)")
    args = ap.parse_args()
    raw = _run_and_save(smoke=args.smoke, fresh=args.gate)
    emit_csv(format_rows(raw))
    if args.gate:
        scan = next(r for r in raw if r["name"] == "profile_fleet_scan")
        assert scan["donation_ok"], (
            f"donation audit failed: {scan['aliased_args']:.0f} aliased "
            f"outputs for {scan['donated_leaves']:.0f} donated fleet "
            f"leaves — a donated buffer is no longer reused in-place and "
            f"training peak memory roughly doubles")
