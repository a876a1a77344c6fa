"""Twin-evaluation cells: pipelines of models evaluated in the request-level
twin, one chunk of control intervals per dispatch.

Each dispatch is one call of ``repro.sim.simulate_fleet`` over ``T_CHUNK``
control intervals (5 minutes of deployment time) of every camera's
pipeline, carrying the twin's state, the actions in force, the arrival
phases and the sampling key from one call to the next (``SimCarry``), as a
long evaluation is split into calls. The call ends by fetching its
per-interval history.

Set-up builds the policies from the seed through the program's
``fleet_init``, each stage's device profiles, and the traffic on the device
(``N_SLICES`` slices of ``T_CHUNK`` intervals of frame rates and objects per
frame, cycled). It then drives the first two chunks, whose readings the
comparison keeps: the second starts from the first's carried state, as
every timed dispatch does, and warms that call.
"""
from __future__ import annotations

import sys

import numpy as np

T_CHUNK = 300        # control intervals per dispatch: 5 minutes
N_SLICES = 64        # distinct traffic slices the window cycles through
# the configuration keys this entry reads; the harness refuses any other
READS = {"cameras", "stages", "stage_speed", "stage_slo_s", "twin",
         "queue_advance_kernel", "iagent"}


class Entry:
    """One twin-evaluation cell: set up, dispatch, free, and the readings
    for the comparison with the reference."""

    def __init__(self, config, mix, key, chips, n_agents=None):
        from repro.configs.fcpo import FCPOConfig
        from repro.sim import Pipeline, SimParams

        if chips != 1:
            raise ValueError("twin-evaluation cells run on one chip")
        self.config, self.mix, self.key = config, mix, key
        stages = len(config["stages"])
        cameras = (n_agents // stages if n_agents else config["cameras"])
        self.pipe = Pipeline(cameras, stages)
        self.n_agents = self.pipe.agents
        self.cfg = FCPOConfig(**config["iagent"])
        self.sp = SimParams(**config["twin"])
        self.use_pallas = config["queue_advance_kernel"]
        self.intervals_per_dispatch = self.n_agents * T_CHUNK
        self.episodes_per_dispatch = 0
        self.rounds_per_dispatch = 0
        self.chunk = 0
        self.carry = None
        self.readings = {}

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        from repro.core.fleet import device_speeds, fleet_init
        from repro.sim import pipeline_env_params

        from bench import traffic_gen, traffic_objects

        self.k_fleet, k_traffic, k_sim = jax.random.split(self.key, 3)
        fleet = fleet_init(self.cfg, self.n_agents, self.k_fleet)
        self.params, self.masks = fleet.astate.params, fleet.masks
        self.env_params = pipeline_env_params(
            self.pipe, device_speeds(self.pipe.cameras),
            self.config["stage_speed"], self.config["stage_slo_s"])
        k_rates, k_objects = jax.random.split(k_traffic)
        n = N_SLICES * T_CHUNK
        rates = np.asarray(traffic_gen.make_traces(
            self.mix, k_rates, self.pipe.cameras, n))
        objects = np.asarray(traffic_objects.make_objects(
            self.mix, k_objects, self.pipe.cameras, n))
        cut = lambda x, i: jax.device_put(
            x[:, i * T_CHUNK:(i + 1) * T_CHUNK])
        self.slices = [(cut(rates, i), cut(objects, i))
                       for i in range(N_SLICES)]
        self.check_traffic = (rates[:, :2 * T_CHUNK],
                              objects[:, :2 * T_CHUNK])
        runs = [self.dispatch(k_sim)]
        runs.append(self.dispatch())
        both = lambda name: np.concatenate([r.history[name] for r in runs])
        self.readings = {"counters": np.asarray(runs[1].state.counters),
                         "lat_sum": np.asarray(runs[1].state.lat_sum),
                         "routed": both("routed"), "logp": both("logp"),
                         "actions": both("actions")}

    def dispatch(self, key=None):
        """One chunk through the program's evaluation entry, ended by the
        history fetch inside it."""
        from repro.sim import simulate_fleet

        rates, objects = self.slices[self.chunk % N_SLICES]
        run = simulate_fleet(self.cfg, self.sp, self.params, self.masks,
                             self.env_params, rates, key,
                             use_pallas=self.use_pallas, pipeline=self.pipe,
                             objects=objects, carry=self.carry)
        self.carry = run.carry
        self.chunk += 1
        return run

    def free(self):
        """Report each stage's request shares over the run (set-up and
        window), then let go of the program's state."""
        from bench.reference.fcpo import (ARRIVED, COMPLETED, DROPPED,
                                          EFFECTIVE)

        c = np.asarray(self.carry.state.counters, np.int64)
        for s, name in enumerate(self.config["stages"]):
            r = c[self.pipe.rows(s)]
            n = max(int(r[:, ARRIVED].sum()), 1)
            done, late = r[:, COMPLETED].sum(), (r[:, COMPLETED]
                                                 - r[:, EFFECTIVE]).sum()
            print(f"stage {name}: {n} arrived over {self.chunk * T_CHUNK} "
                  f"intervals; completed {float(done / n)!r}, late "
                  f"{float(late / n)!r}, dropped "
                  f"{float(r[:, DROPPED].sum() / n)!r}",
                  file=sys.stderr, flush=True)
        self.carry = self.slices = self.params = self.env_params = None

    # -- the comparison ----------------------------------------------------
    def numbers(self, control=False):
        """{name: (value, detail)}: the program's first two chunks (the
        second from the first's carry) against the reference's run through
        the same intervals from its own state, given the program's
        actions; with ``control``, the reference computed in bfloat16
        stands in the program's place."""
        import jax.numpy as jnp

        from bench import compare_sim
        from bench.reference import pipeline

        rates, objects = self.check_traffic
        ref = lambda dt: pipeline.readings(
            self.config, self.k_fleet, self.n_agents, rates, objects,
            self.readings["actions"], dt)
        got = ref(jnp.bfloat16) if control else self.readings
        return compare_sim.sim_numbers(got, ref(jnp.float32))
