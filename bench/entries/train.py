"""Training cells: a continual fleet trainer fed one FL round per dispatch.

Each dispatch is one call of ``repro.core.fleet.train_fleet_scan`` over
``fl_every`` episodes (one FL round; every ``hierarchical_period``-th round
also merges the pods), with ``episode_offset`` advancing and the fleet
donated from one call to the next, as the launcher's ``--ckpt-dir`` chunk
loop feeds it. The call ends by fetching its per-episode history.

Set-up builds the fleet on the device from the seed and drives the first
``CHECK_ROUNDS`` rounds through the same call; the readings the comparison
needs are copied off as they go (the donated buffers do not survive the
next round), and the same fleet then goes on into the measured window.
"""
from __future__ import annotations

import numpy as np

CHECK_ROUNDS = 4     # the fourth round carries the first pod merge
N_SLICES = 64        # distinct trace slices the window cycles through
# the configuration keys this entry reads; the harness refuses any other
READS = {"agents", "pods", "env_backend", "twin", "queue_advance_kernel",
         "fl_codec", "delta_codec_kernel", "iagent", "rl", "fl"}


def _fcpo_config(c):
    from repro.configs.fcpo import FCPOConfig

    return FCPOConfig(**c["iagent"], **c["rl"], **c["fl"])


class Entry:
    """One training cell: set up, dispatch, free, and the readings for the
    comparison with the reference."""

    def __init__(self, config, mix, key, chips, n_agents=None):
        from repro.core.backends import get_backend
        from repro.fl import TransportConfig
        from repro.sim import SimParams

        if chips != 1:
            raise ValueError("training cells run on one chip")
        self.config, self.mix, self.key = config, mix, key
        self.n_agents = n_agents or config["agents"]
        self.cfg = _fcpo_config(config)
        twin = config.get("twin")
        self.backend = get_backend(
            config["env_backend"],
            sim_params=SimParams(**twin) if twin else None,
            use_pallas=config["queue_advance_kernel"])
        self.transport = TransportConfig(
            codec=config["fl_codec"], use_pallas=config["delta_codec_kernel"])
        self.steps = self.cfg.n_steps * self.cfg.fl_every
        self.intervals_per_dispatch = self.n_agents * self.steps
        self.episodes_per_dispatch = self.cfg.fl_every
        self.rounds_per_dispatch = 1
        self.round = 0
        self.readings = {}

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        from repro.core.fleet import fleet_init

        from bench import traffic_gen
        from bench.compare import flat_program

        k_fleet, k_traffic = jax.random.split(self.key)
        self.fleet = fleet_init(self.cfg, self.n_agents, k_fleet,
                                n_pods=self.config["pods"],
                                env_backend=self.backend)
        traces = traffic_gen.make_traces(self.mix, k_traffic, self.n_agents,
                                         N_SLICES * self.steps)
        host = np.asarray(traces)
        self.slices = [jax.device_put(host[:, i * self.steps:
                                           (i + 1) * self.steps])
                       for i in range(N_SLICES)]
        self.check_rates = host[:, :CHECK_ROUNDS * self.steps]
        host = lambda t: flat_program(jax.device_get(t))
        self.readings["params0"] = host(self.fleet.astate.params)
        losses = []
        for i in range(CHECK_ROUNDS):
            hist = self.dispatch()
            losses.extend(np.asarray(hist["loss"], np.float64).tolist())
            if i == 0:
                self.readings["m1"] = host(self.fleet.astate.opt["m"])
        self.readings["losses"] = losses
        self.readings["params4"] = host(self.fleet.astate.params)
        self.readings["base4"] = host(self.fleet.base_params)
        self.readings["counters4"] = (
            np.asarray(self.fleet.astate.env_state.sim.counters)
            if self.config["env_backend"] == "twin" else None)

    def dispatch(self):
        """One FL round through the program's entry, ended by the history
        fetch inside it."""
        from repro.core.fleet import train_fleet_scan

        rates = self.slices[self.round % N_SLICES]
        self.fleet, hist = train_fleet_scan(
            self.cfg, self.fleet, rates, donate=True,
            env_backend=self.backend, transport=self.transport,
            episode_offset=self.round * self.cfg.fl_every)
        self.round += 1
        return hist

    def free(self):
        self.fleet = None
        self.slices = None

    # -- the comparison ----------------------------------------------------
    def numbers(self, control=False):
        """{name: (value, detail)}: the program's first rounds against the
        reference's; with ``control``, the reference computed in bfloat16
        stands in the program's place."""
        import jax.numpy as jnp

        from bench import compare

        ref = lambda dt: compare.ref_train_readings(
            self.config, self.key, self.n_agents, self.check_rates,
            CHECK_ROUNDS, dt)
        got = ref(jnp.bfloat16) if control else self.readings
        return compare.train_numbers(got, ref(jnp.float32))
