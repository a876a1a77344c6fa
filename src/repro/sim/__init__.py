"""Request-level data-plane simulator (digital twin) — see
docs/architecture.md, "Request-level simulator" and "Environment
backends"."""
from repro.sim.harness import (SimCarry, SimRun, eval_fleet, sim_observe,
                               sim_start, simulate_fleet)
from repro.sim.metrics import hist_percentile, summarize, warn_if_censored
from repro.sim.scenarios import SCENARIOS, make_scenario
from repro.sim.state import (Pipeline, SimParams, SimState, action_caps,
                             effective_queue_cap, fanout_arrivals,
                             pipeline_env_params, sim_init, spread_arrivals)
from repro.sim.step import sim_interval, sim_interval_agent, sim_interval_ref

__all__ = [
    "SCENARIOS", "Pipeline", "SimCarry", "SimParams", "SimRun", "SimState",
    "action_caps", "effective_queue_cap", "eval_fleet", "fanout_arrivals",
    "hist_percentile", "make_scenario", "pipeline_env_params", "sim_init",
    "sim_interval", "sim_interval_agent", "sim_interval_ref", "sim_observe",
    "sim_start", "simulate_fleet", "spread_arrivals", "summarize",
    "warn_if_censored",
]
