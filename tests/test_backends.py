"""Pluggable environment backends: observation unification, twin-backed
training equivalence (scan vs reference, jnp vs Pallas), and the
fluid-vs-twin fidelity envelope asserted in tier-1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fcpo import FCPOConfig
from repro.core import env as env_mod
from repro.core.backends import (BACKENDS, FLUID, FluidBackend, TwinBackend,
                                 TwinEnvState, get_backend)
from repro.core.fleet import (fleet_init, train_fleet, train_fleet_reference,
                              train_fleet_scan)
from repro.sim import SimParams, make_scenario, sim_observe, simulate_fleet
from repro.sim.state import effective_queue_cap

CFG = FCPOConfig()
KEY = jax.random.PRNGKey(0)
SP = SimParams(dt=0.05, k_ticks=8, ring=64, hist_n=32)


class TestInterface:
    def test_get_backend_resolution(self):
        assert get_backend(None) is FLUID
        assert get_backend("fluid") is FLUID
        tw = get_backend("twin", sim_params=SP, use_pallas=True)
        assert isinstance(tw, TwinBackend) and tw.sp == SP and tw.use_pallas
        assert get_backend(tw) is tw
        with pytest.raises(ValueError, match="unknown env backend"):
            get_backend("nope")
        assert set(BACKENDS) == {"fluid", "twin"}

    def test_backends_are_hashable_jit_statics(self):
        assert hash(FluidBackend()) == hash(FluidBackend())
        assert hash(TwinBackend(sp=SP)) == hash(TwinBackend(sp=SP))
        assert TwinBackend(sp=SP) != TwinBackend(sp=SP, use_pallas=True)


class TestObservationUnification:
    """The 8-dim state vector has ONE definition (env.observe_vector)."""

    def test_fluid_backend_observe_is_env_observe(self):
        ep = env_mod.default_env_params()
        s = env_mod.EnvState(
            pre_q=jnp.float32(17.0), post_q=jnp.float32(4.0),
            drops=jnp.float32(3.0),
            cur_action=jnp.asarray([2, 5, 1], jnp.int32),
            ema_lat=jnp.float32(0.1), t=jnp.int32(9))
        rate = jnp.float32(42.0)
        np.testing.assert_array_equal(
            np.asarray(FLUID.observe(CFG, ep, s, rate)),
            np.asarray(env_mod.observe(CFG, ep, s, rate)))

    def test_twin_backend_observe_matches_sim_observe_fieldwise(self):
        """The training-side twin observation and the evaluation harness's
        ``sim_observe`` read the same normalizations — field for field."""
        be = TwinBackend(sp=SP)
        ep = env_mod.default_env_params()
        state = be.init(CFG)
        rng = jax.random.PRNGKey(1)
        for i in range(4):  # drive to a non-trivial queue state
            rng, k = jax.random.split(rng)
            action = jax.random.randint(k, (3,), 0, 3)
            state, _, _ = be.step(CFG, ep, state, action, jnp.float32(80.0))
        obs_backend = be.observe(CFG, ep, state, jnp.float32(55.0))
        obs_harness = sim_observe(CFG, SP, ep, state.sim, state.drops_prev,
                                  state.cur_action, jnp.float32(55.0))
        np.testing.assert_array_equal(np.asarray(obs_backend),
                                      np.asarray(obs_harness))
        assert obs_backend.shape == (CFG.state_dim,)

    def test_twin_and_fluid_share_normalization_constants(self):
        """Same raw readings => same observation, whichever backend
        normalized them (the queue term uses the twin's effective cap)."""
        ep = env_mod.default_env_params()
        raw = dict(rate=jnp.float32(70.0),
                   cur_action=jnp.asarray([1, 3, 2], jnp.int32),
                   drops=jnp.float32(7.0), pre_q=jnp.float32(12.0),
                   post_q=jnp.float32(5.0), slo_s=ep.slo_s)
        a = env_mod.observe_vector(CFG, queue_cap=ep.queue_cap, **raw)
        b = env_mod.observe_vector(
            CFG, queue_cap=effective_queue_cap(SP, ep), **raw)
        # only the two queue-occupancy fields may differ (different caps)
        np.testing.assert_array_equal(np.asarray(a[:5]), np.asarray(b[:5]))
        np.testing.assert_array_equal(np.asarray(a[7]), np.asarray(b[7]))


class TestTwinStep:
    def test_step_conserves_and_rewards_in_range(self):
        be = TwinBackend(sp=SP)
        ep = env_mod.default_env_params()
        state = be.init(CFG)
        rng = jax.random.PRNGKey(2)
        for _ in range(6):
            rng, k = jax.random.split(rng)
            action = jax.random.randint(k, (3,), 0, 3)
            state, r, info = be.step(CFG, ep, state, action, jnp.float32(120.0))
            assert -1.0 <= float(r) <= 1.0
            assert float(info["effective_throughput"]) <= \
                float(info["throughput"]) + 1e-6
        sim = state.sim
        assert int(sim.arrived) == int(sim.dropped) + int(sim.completed) \
            + int(sim.in_flight)
        assert int(sim.completed) > 0
        # fl_round's Eq. 7 memory stat reads env_state.pre_q on any backend
        assert float(state.pre_q) == float(sim.pre_q)

    def test_phase_carry_admits_fractional_rates(self):
        """The fractional-arrival phase carries across control intervals, so
        a steady fractional rate is admitted on average (no floor deficit)."""
        be = TwinBackend(sp=SP)
        ep = env_mod.default_env_params()
        state = be.init(CFG)
        rate = jnp.float32(30.9)
        n_int = 25
        for _ in range(n_int):
            state, _, _ = be.step(CFG, ep, state,
                                  jnp.zeros((3,), jnp.int32), rate)
        expect = float(rate) * SP.interval_s * n_int
        assert abs(int(state.sim.arrived) - expect) <= 1.0


class TestTrainingEquivalence:
    def _fleet(self, be, n=3, n_pods=2):
        return fleet_init(CFG, n, KEY, n_pods=n_pods, env_backend=be)

    def test_twin_scan_matches_reference(self):
        """The twin-backed scanned driver == the Python-loop oracle through
        FL rounds, pod merges, and straggler masking."""
        be = TwinBackend(sp=SP)
        n = 3
        traces = make_scenario("dynamic", jax.random.PRNGKey(1), n,
                               8 * CFG.n_steps)
        kw = dict(straggler_prob=0.3, seed=7, env_backend=be)
        rf, rh = train_fleet_reference(CFG, self._fleet(be, n), traces, **kw)
        sf, sh = train_fleet_scan(CFG, self._fleet(be, n), traces, **kw)
        assert sorted(rh) == sorted(sh)
        for k in rh:
            np.testing.assert_allclose(sh[k], rh[k], rtol=1e-4, atol=1e-5,
                                       err_msg=k)
        for a, b in zip(jax.tree.leaves(rf.astate.params),
                        jax.tree.leaves(sf.astate.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        # the twin env state itself must match exactly (integer counters)
        for a, b in zip(jax.tree.leaves(rf.astate.env_state),
                        jax.tree.leaves(sf.astate.env_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.pallas
    def test_twin_pallas_training_matches_jnp(self):
        """Training through the fused Pallas queue_advance kernel is
        bit-identical to the jnp microtick scan (same keys => same
        trajectories => same updates)."""
        n = 2
        traces = make_scenario("dynamic", jax.random.PRNGKey(1), n,
                               4 * CFG.n_steps)
        outs = []
        for use_pallas in (False, True):
            be = TwinBackend(sp=SP, use_pallas=use_pallas)
            fleet, hist = train_fleet(CFG, self._fleet(be, n, n_pods=1),
                                      traces, env_backend=be)
            outs.append((fleet, hist))
        (fj, hj), (fp, hp) = outs
        for k in hj:
            np.testing.assert_allclose(hp[k], hj[k], rtol=1e-5, atol=1e-6,
                                       err_msg=k)
        for a, b in zip(jax.tree.leaves(fj.astate.env_state),
                        jax.tree.leaves(fp.astate.env_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFidelityEnvelope:
    def test_fluid_vs_twin_total_throughput_gap_under_2pct_on_steady(self):
        """The PR 3 fidelity envelope, asserted in tier-1: both planes move
        the same total flow on the steady scenario (<2% relative gap) — the
        backends model the same pipeline, they differ in request-grade
        accounting, not in bulk throughput."""
        a = 4
        sp = SimParams()  # production geometry: ring 512 fits queue_cap 128
        fleet = fleet_init(CFG, a, KEY)
        traces = make_scenario("steady", jax.random.PRNGKey(2), a,
                               2 * CFG.n_steps)
        _, hist = train_fleet(CFG, fleet, traces, learn=False,
                              federated=False)
        summ = simulate_fleet(CFG, sp, fleet.astate.params, fleet.masks,
                              fleet.env_params, traces,
                              jax.random.PRNGKey(3)).summary
        thr_fluid = float(np.mean(hist["throughput"]))
        thr_twin = float(np.asarray(summ["throughput"]).mean())
        gap = abs(thr_fluid - thr_twin) / max(abs(thr_fluid), 1e-9)
        assert gap < 0.02, (thr_fluid, thr_twin, gap)
