"""Policy leaderboard: every checkpoint scored on every cell of the
scenario × backend × codec grid.

FCPO's headline claims are *grid* claims — 5× effective throughput and 60%
latency reduction only mean something across workloads, environments, and
communication regimes. A leaderboard **cell** is one point of that grid:

    (scenario ∈ repro.sim.SCENARIOS)          — which workload
  × (backend  ∈ {fluid, twin})                — which environment the
                                                continual cadence adapts in
  × (codec    ∈ repro.fl.CODECS)              — which FL transport the
                                                rounds ship deltas over

Evaluating a checkpoint on a cell runs the *real* production cadence, not a
side-channel re-implementation: the checkpoint fleet (env states swapped for
the cell backend's) continually adapts over the cell scenario via
``train_fleet_scan`` — episodes → Eq. 7 selection → Alg. 1 aggregation →
Alg. 2 fine-tune, ONE jitted scan, with the cell codec's ``TransportConfig``
— and the adapted policies are then driven through the request-level twin
(``sim.harness.eval_fleet``) on a held-out trace of the same scenario for
request-grade metrics. Per cell × replicate that yields:

  * ``reward``            — adaptation reward (tail mean of the run history)
  * ``eval_eff``          — held-out twin effective throughput (req/s)
  * ``eval_p99``          — held-out twin p99 end-to-end latency (s)
  * ``eval_slo``          — held-out SLO attainment (effective/completed)
  * ``fl_payload_bytes``  — mean FL round payload under the cell codec

Replicates re-draw the workload and eval keys from deterministic per-cell
seeds (``cell_seed`` — a crc32 fold of the cell name, never Python's
randomized ``hash``), so every cell is a pure function of
(checkpoint, cell, seed, shapes): two runs — or any ``n_jobs`` interleaving
of cells — produce bit-identical metrics (tests/test_leaderboard.py).

``attach_deltas`` diffs a new row set against the previous
``BENCH_leaderboard.json`` envelope and ``check_regressions`` turns those
deltas into a CI gate: a cell whose reward or held-out effective throughput
fell beyond a per-cell tolerance fails the run (``benchmarks/leaderboard.py
--gate``). Reward and perf claims become diffable artifacts instead of
one-off benchmark runs.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fcpo import FCPOConfig
from repro.core.backends import BACKENDS, get_backend
from repro.core.fleet import Fleet, fleet_init, train_fleet_scan
from repro.fl import CODECS, TransportConfig
from repro.sim import SCENARIOS, SimParams, make_scenario
from repro.sim.harness import eval_fleet
from repro.training import checkpoint as ckpt_mod

GRID_SCENARIOS: Tuple[str, ...] = SCENARIOS          # all 9 named workloads
GRID_BACKENDS: Tuple[str, ...] = BACKENDS            # fluid | twin
GRID_CODECS: Tuple[str, ...] = CODECS                # float32 | int8 | topk
REPLICATES = 3

# higher-is-better metrics the regression gate watches, with an absolute
# floor so near-zero baselines don't turn the relative tolerance into a
# zero-width band (reward sits in [-1, 1]; throughput in req/s).
GATE_METRICS: Dict[str, float] = {"reward_mean": 0.05, "eval_eff_mean": 1.0}
# informational deltas carried in the envelope alongside the gated ones
DELTA_KEYS: Tuple[str, ...] = ("reward_mean", "eval_eff_mean",
                               "eval_p99_mean", "eval_slo_mean",
                               "fl_payload_bytes")
DEFAULT_TOL = 0.10
# hist_n=128 keeps held-out p99 uncensored out to 6.35 s — untrained tails
# on ood/switching exceed the default 3.15 s cap (same as fig_twin_training)
EVAL_SP = SimParams(hist_n=128)


@dataclass(frozen=True)
class Cell:
    scenario: str
    backend: str
    codec: str

    @property
    def name(self) -> str:
        return f"leaderboard_{self.scenario}_{self.backend}_{self.codec}"


def grid_cells(scenarios: Sequence[str] = GRID_SCENARIOS,
               backends: Sequence[str] = GRID_BACKENDS,
               codecs: Sequence[str] = GRID_CODECS) -> List[Cell]:
    """The (dense) grid, scenario-major — the canonical leaderboard order."""
    return [Cell(s, b, c) for s in scenarios for b in backends
            for c in codecs]


def cell_seed(base_seed: int, cell: Cell, rep: int, tag: str = "") -> int:
    """Deterministic per-(cell, replicate, stream) seed. crc32, not
    ``hash()`` — Python string hashing is salted per process, which would
    silently break run-to-run determinism."""
    token = f"{cell.scenario}|{cell.backend}|{cell.codec}|{rep}|{tag}"
    return int((base_seed + zlib.crc32(token.encode())) % (2 ** 31 - 1))


def _with_env_states(cfg: FCPOConfig, fleet: Fleet, backend) -> Fleet:
    """The checkpoint's policies/optimizers/buffers with FRESH env states of
    the cell backend — a fluid-trained checkpoint is evaluable in the twin
    (and vice versa) because the 8-dim observation has one definition."""
    a = fleet.pod_ids.shape[0]
    states = jax.vmap(lambda _: backend.init(cfg))(jnp.arange(a))
    return fleet._replace(astate=fleet.astate._replace(env_state=states))


def evaluate_cell(cfg: FCPOConfig, fleet: Fleet, cell: Cell, *,
                  episodes: int = 6, eval_intervals: int = 30,
                  replicates: int = REPLICATES, seed: int = 0,
                  sim_params: Optional[SimParams] = None,
                  eval_sp: SimParams = EVAL_SP) -> Dict[str, Any]:
    """Score one checkpoint on one grid cell.

    ``episodes`` of the full continual cadence (FL rounds under the cell
    codec included) on the cell scenario/backend, then a held-out
    request-grade twin evaluation — per replicate. Returns the per-cell row:
    mean ± std over replicates for every metric, plus the raw per-replicate
    values (``*_reps``) so downstream tooling can re-aggregate."""
    backend = get_backend(cell.backend, sim_params=sim_params)
    transport = TransportConfig(codec=cell.codec)
    a = fleet.pod_ids.shape[0]
    tail = max(episodes // 2, 1)
    reps: Dict[str, List[float]] = {k: [] for k in
                                    ("reward", "train_eff", "eval_eff",
                                     "eval_p99", "eval_slo", "payload")}
    for r in range(replicates):
        s = cell_seed(seed, cell, r)
        f = _with_env_states(cfg, fleet, backend)
        traces = make_scenario(cell.scenario, jax.random.PRNGKey(s), a,
                               episodes * cfg.n_steps)
        f, hist = train_fleet_scan(cfg, f, traces, env_backend=backend,
                                   transport=transport, seed=s, donate=False)
        fl_eps = np.flatnonzero(hist["fl_payload_bytes"])
        reps["reward"].append(float(np.mean(hist["reward"][-tail:])))
        reps["train_eff"].append(
            float(np.mean(hist["effective_throughput"][-tail:])))
        reps["payload"].append(
            float(hist["fl_payload_bytes"][fl_eps].mean()) if fl_eps.size
            else 0.0)

        ev = make_scenario(cell.scenario,
                           jax.random.PRNGKey(cell_seed(seed, cell, r,
                                                        "eval")),
                           a, eval_intervals)
        summ = eval_fleet(cfg, eval_sp, f, ev,
                          jax.random.PRNGKey(cell_seed(seed, cell, r,
                                                       "key"))).summary
        reps["eval_eff"].append(
            float(np.asarray(summ["effective_throughput"]).mean()))
        reps["eval_p99"].append(
            float(np.asarray(summ["p99_latency_s"]).mean()))
        reps["eval_slo"].append(
            float(np.asarray(summ["slo_attainment"]).mean()))

    row: Dict[str, Any] = {
        "name": cell.name,
        "scenario": cell.scenario, "env_backend": cell.backend,
        "codec": cell.codec, "agents": a, "episodes": episodes,
        "eval_intervals": eval_intervals, "replicates": replicates,
        "seed": seed,
    }
    for key, out in (("reward", "reward"), ("train_eff", "train_eff"),
                     ("eval_eff", "eval_eff"), ("eval_p99", "eval_p99"),
                     ("eval_slo", "eval_slo")):
        row[f"{out}_mean"] = float(np.mean(reps[key]))
        row[f"{out}_std"] = float(np.std(reps[key]))
        row[f"{out}_reps"] = reps[key]
    row["fl_payload_bytes"] = float(np.mean(reps["payload"]))
    return row


def run_leaderboard(cfg: FCPOConfig, fleet: Fleet,
                    cells: Optional[Iterable[Cell]] = None, *,
                    episodes: int = 6, eval_intervals: int = 30,
                    replicates: int = REPLICATES, seed: int = 0,
                    sim_params: Optional[SimParams] = None,
                    eval_sp: SimParams = EVAL_SP, n_jobs: int = 1,
                    log=None) -> List[Dict[str, Any]]:
    """Score a checkpoint over a cell list (default: the full grid).

    ``n_jobs`` round-robins the cells into that many stripes and evaluates
    stripe-by-stripe — a deterministic *reordering* only (each cell's seeds
    are self-contained, so metrics are bit-identical for any ``n_jobs``;
    asserted in tests/test_leaderboard.py). Rows come back in the input
    cell order regardless."""
    cells = list(grid_cells() if cells is None else cells)
    if n_jobs < 1:
        raise ValueError(f"n_jobs must be >= 1, got {n_jobs}")
    order = [i for j in range(n_jobs) for i in range(j, len(cells), n_jobs)]
    rows: Dict[int, Dict[str, Any]] = {}
    for i in order:
        rows[i] = evaluate_cell(cfg, fleet, cells[i], episodes=episodes,
                                eval_intervals=eval_intervals,
                                replicates=replicates, seed=seed,
                                sim_params=sim_params, eval_sp=eval_sp)
        if log is not None:
            r = rows[i]
            log(f"{r['name']}: reward={r['reward_mean']:+.3f} "
                f"eff={r['eval_eff_mean']:.2f}/s "
                f"p99={r['eval_p99_mean'] * 1e3:.0f}ms "
                f"slo={r['eval_slo_mean'] * 100:.0f}% "
                f"payload={r['fl_payload_bytes'] / 1024:.1f}KB")
    return [rows[i] for i in range(len(cells))]


# ---------------------------------------------------------------------------
# Envelope deltas + the regression gate
# ---------------------------------------------------------------------------
# fields that must agree between a row and its previous measurement for the
# comparison to mean anything — a changed shape (different agent count,
# episode budget, replicate count, ...) is an incompatible grid, and gating
# against it would flag phantom regressions
COMPAT_KEYS: Tuple[str, ...] = ("agents", "episodes", "eval_intervals",
                                "replicates", "seed")


def sanitize_envelope(prev_envelope, warn=None):
    """Defensive read of a previous ``BENCH_leaderboard*.json`` envelope.

    Returns the envelope when it is usable (a dict whose ``results`` is a
    list) and None otherwise — a missing, truncated, or non-envelope file
    degrades the gate to "no baseline" with a warning instead of crashing
    CI. An envelope measured on a DIFFERENT jax backend or device count
    (``save_bench`` stamps both) is also refused: timings and memory moved
    for hardware reasons, so gating against it would flag phantom
    regressions (or hide real ones) on cross-backend noise. ``warn`` is an
    optional ``print``-like callable."""
    if prev_envelope is None:
        return None
    if (not isinstance(prev_envelope, dict)
            or not isinstance(prev_envelope.get("results"), list)):
        if warn is not None:
            warn("leaderboard: previous envelope is not a results envelope "
                 "— treating as no baseline")
        return None
    import jax

    here = {"backend": jax.default_backend(),
            "device_count": jax.device_count()}
    for key, cur in here.items():
        prev = prev_envelope.get(key)
        # legacy envelopes (pre device_count stamp) pass: nothing to refuse
        if prev is not None and prev != cur:
            if warn is not None:
                warn(f"leaderboard: previous envelope is from {key}="
                     f"{prev!r} but this run is {key}={cur!r} — refusing "
                     f"the cross-backend diff, treating as no baseline")
            return None
    return prev_envelope


def _compatible(row, prev) -> bool:
    return all(prev.get(k) == row.get(k) for k in COMPAT_KEYS)


def attach_deltas(rows: List[Dict[str, Any]],
                  prev_envelope: Optional[Dict[str, Any]],
                  warn=None) -> List[Dict[str, Any]]:
    """Fold the previous envelope into ``rows`` (in place): for every cell
    present in both, ``prev_<k>`` and ``delta_<k>`` (new − prev) for each
    ``DELTA_KEYS`` metric. Cells with no previous measurement carry no
    delta fields — a grown grid is not a regression.

    Degrades gracefully: an unusable envelope (``sanitize_envelope``), a
    cell row measured on an incompatible grid (``COMPAT_KEYS`` mismatch),
    or a torn/non-numeric previous value each skip the delta (warn via
    ``warn`` when given) instead of raising — a corrupted baseline must
    not take the CI gate down with it."""
    prev_envelope = sanitize_envelope(prev_envelope, warn)
    prev_rows = {r["name"]: r
                 for r in (prev_envelope or {}).get("results", [])
                 if isinstance(r, dict) and "name" in r}
    for row in rows:
        prev = prev_rows.get(row["name"])
        if prev is None:
            continue
        if not _compatible(row, prev):
            if warn is not None:
                diffs = [k for k in COMPAT_KEYS
                         if prev.get(k) != row.get(k)]
                warn(f"leaderboard: {row['name']} previous row is from an "
                     f"incompatible grid ({', '.join(diffs)} changed) — "
                     f"no baseline for this cell")
            continue
        for k in DELTA_KEYS:
            if k in prev and k in row:
                try:
                    pv, nv = float(prev[k]), float(row[k])
                except (TypeError, ValueError):
                    continue
                if not np.isfinite(pv):
                    continue
                row[f"prev_{k}"] = pv
                row[f"delta_{k}"] = nv - pv
    return rows


def check_regressions(rows: List[Dict[str, Any]], tol: float = DEFAULT_TOL,
                      tolerances: Optional[Dict[str, float]] = None
                      ) -> List[str]:
    """The gate: one failure string per (cell, gated metric) whose new value
    fell more than the tolerance below the previous envelope's.

    Tolerance per cell: ``tolerances[cell_name]`` overrides ``tol``; the
    allowed drop is ``tol * max(|prev|, floor)`` with the metric's absolute
    floor from ``GATE_METRICS``, so noisy near-zero cells don't gate on
    roundoff. Rows without ``prev_*`` fields (first run, new cells,
    incompatible or corrupt baselines — see ``attach_deltas``) never fail.
    Call ``attach_deltas`` first."""
    failures = []
    for row in rows:
        if not isinstance(row, dict) or "name" not in row:
            continue
        cell_tol = (tolerances or {}).get(row["name"], tol)
        for metric, floor in GATE_METRICS.items():
            prev_key = f"prev_{metric}"
            if prev_key not in row or metric not in row:
                continue
            try:
                prev, new = float(row[prev_key]), float(row[metric])
            except (TypeError, ValueError):
                continue
            if not (np.isfinite(prev) and np.isfinite(new)):
                continue
            allowed = cell_tol * max(abs(prev), floor)
            if prev - new > allowed:
                failures.append(
                    f"{row['name']}: {metric} regressed {prev:.4f} -> "
                    f"{new:.4f} (drop {prev - new:.4f} > allowed "
                    f"{allowed:.4f} at tol {cell_tol:.0%})")
    return failures


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------
def load_fleet(cfg: FCPOConfig, ckpt_dir: str, step: Optional[int] = None, *,
               n_agents: int, n_pods: int = 1, env_backend=None) -> Fleet:
    """Restore a ``Fleet`` checkpoint (training/checkpoint.py format) for
    leaderboard evaluation. The template fleet supplies structure + static
    aux; ``env_backend`` must match the backend the checkpoint was saved
    with (its env-state leaves are part of the on-disk structure)."""
    if step is None:
        step = ckpt_mod.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint manifests in {ckpt_dir}")
    template = fleet_init(cfg, n_agents, jax.random.PRNGKey(0),
                          n_pods=n_pods, env_backend=env_backend)
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        template)
    fleet, _manifest = ckpt_mod.restore(ckpt_dir, step, like)
    return fleet
