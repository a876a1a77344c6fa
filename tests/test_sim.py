"""Request-level twin: Pallas kernel vs jnp oracle bit-identity, twin vs the
Python slo.py data plane (request-for-request), and the closed-loop
``simulate_fleet`` harness."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fcpo import FCPOConfig
from repro.core.fleet import fleet_init
from repro.data.workload import fleet_traces
from repro.kernels import ops as kops
from repro.kernels import queue_advance as qa_mod
from repro.kernels import ref as kref
from repro.kernels.queue_advance import queue_advance
from repro.sim import (SimParams, SimState, action_caps, hist_percentile,
                       sim_init, sim_interval, sim_interval_ref,
                       simulate_fleet, spread_arrivals)
from repro.sim.oracle import simulate_python_agent

KEY = jax.random.PRNGKey(0)
SP = SimParams(dt=0.05, k_ticks=8, ring=32, hist_n=16)
CAPS = jnp.asarray([2.5, 3.0, 4.0, 2.0, 8.0, 5.0], jnp.float32)


def _batched_state(a):
    return jax.vmap(lambda _: sim_init(SP))(jnp.arange(a))


def _random_args(a, key):
    k1, k2 = jax.random.split(key)
    arrivals = jax.random.randint(k1, (a, SP.k_ticks), 0, 7)
    jitter = jax.random.randint(k2, (a, 6), 0, 3).astype(jnp.float32)
    caps = CAPS[None] + jitter * jnp.asarray([0.5, 0.5, 1.0, 1.0, 0.0, 0.0])
    return arrivals, caps


class TestQueueAdvanceKernel:
    pytestmark = pytest.mark.pallas

    @pytest.mark.parametrize("a", [1, 3, 13, 40])
    def test_kernel_matches_oracle_batched_bit_identical(self, a):
        """Fused kernel (interpret mode on CPU) == vmap'd jnp oracle,
        bit-for-bit, chained over several control intervals."""
        state = _batched_state(a)
        for i in range(5):
            arrivals, caps = _random_args(a, jax.random.fold_in(KEY, i))
            out_pal = queue_advance(*state, arrivals, caps, interpret=True)
            out_ref = jax.vmap(kref.queue_advance_ref)(*state, arrivals, caps)
            for name, p, r in zip(SimState._fields, out_pal, out_ref):
                np.testing.assert_array_equal(np.asarray(p), np.asarray(r),
                                              err_msg=f"{name} @ interval {i}")
            if a == 1:   # one agent's operands, without the agent dim
                one = kops.queue_advance(*(x[0] for x in state),
                                         arrivals[0], caps[0])
                for name, p, r in zip(SimState._fields, one, out_ref):
                    np.testing.assert_array_equal(np.asarray(p),
                                                  np.asarray(r[0]),
                                                  err_msg=name)
            state = SimState(*out_pal)
        assert int(state.completed.sum()) > 0  # the chain did real work

    @pytest.mark.parametrize("a", [1, 3, 13, 40])
    def test_kernel_bit_identical_under_vmap(self, a, pallas_grids):
        """vmap of one agent's ``ops.queue_advance`` (the training route)
        == the batched call == vmap of the oracle, and the batching rule
        makes it one kernel call over blocks of agents, not one grid step
        per agent."""
        state = _batched_state(a)
        arrivals, caps = _random_args(a, KEY)
        out_batch = queue_advance(*state, arrivals, caps, interpret=True)
        out_vmap = jax.vmap(kops.queue_advance)(*state, arrivals, caps)
        out_ref = jax.vmap(kref.queue_advance_ref)(*state, arrivals, caps)
        for name, b, v, r in zip(SimState._fields, out_batch, out_vmap,
                                 out_ref):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(v),
                                          err_msg=name)
            np.testing.assert_array_equal(np.asarray(b), np.asarray(r),
                                          err_msg=name)
        assert pallas_grids(jax.vmap(kops.queue_advance), *state, arrivals,
                            caps) == [(1,)]

    def test_kernel_pads_the_last_agent_block(self, monkeypatch,
                                              pallas_grids):
        """A fleet larger than one block runs as blocks of a multiple of 8
        agents, the last one padded; the padding changes no agent."""
        monkeypatch.setattr(qa_mod, "_VMEM_BUDGET", 8 * 4 * 256)
        a = 13
        assert qa_mod.agent_block(a, SP.ring, SP.hist_n, SP.k_ticks) == 8
        state = _batched_state(a)
        arrivals, caps = _random_args(a, KEY)
        out_pal = queue_advance(*state, arrivals, caps, interpret=True)
        out_ref = jax.vmap(kref.queue_advance_ref)(*state, arrivals, caps)
        for name, p, r in zip(SimState._fields, out_pal, out_ref):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(r),
                                          err_msg=name)
        assert pallas_grids(functools.partial(queue_advance, interpret=True),
                            *state, arrivals, caps) == [(2,)]

    def test_kernel_bit_identical_at_bucket_edge_and_large_lat_sum(self):
        """In-flight requests whose latencies straddle the last histogram
        bucket, lat_sum past 2**24 (where float32 steps by 2, so a sum added
        in another order or grouping would show), and a pipeline fast
        enough that ring slots complete twice within one call (the
        kernel's histogram then bins before the slot's second
        completion)."""
        a, ring, h = 13, SP.ring, SP.hist_n
        state = _batched_state(a)
        n0 = 10                    # requests waiting in the post queue
        m0 = 40                    # the current microtick
        lat0 = h - 4 + jnp.arange(ring) % 8     # latencies h-3 .. h+4
        arrive = jnp.broadcast_to(m0 + 1 - lat0, (a, ring)).astype(jnp.int32)
        counters = jnp.zeros((a, kref.SIM_NCOUNTERS), jnp.int32)
        for j in (kref.SIM_TAIL, kref.SIM_PPRE, kref.SIM_LAUNCH,
                  kref.SIM_PINF):
            counters = counters.at[:, j].set(n0)
        counters = counters.at[:, kref.SIM_TICK].set(m0)
        lat_sum = 2.0 ** 24 + 2.0 * jnp.arange(a, dtype=jnp.float32)
        state = state._replace(arrive=arrive, counters=counters,
                               lat_sum=lat_sum)
        arrivals = jnp.full((a, SP.k_ticks), 9, jnp.int32)
        caps = jnp.broadcast_to(jnp.asarray([9.5, 9.5, 9.0, 1.0, 10.0, 30.0],
                                            jnp.float32), (a, 6))
        out_pal = queue_advance(*state, arrivals, caps, interpret=True)
        out_ref = jax.vmap(kref.queue_advance_ref)(*state, arrivals, caps)
        for name, p, r in zip(SimState._fields, out_pal, out_ref):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(r),
                                          err_msg=name)
        out = SimState(*out_ref)
        assert (np.asarray(out.completed) > ring).all()   # slots reused
        assert (np.asarray(out.hist)[:, h - 1] > 0).all()  # clipped edge
        assert (np.asarray(out.lat_sum) > 2.0 ** 24).all()

    def test_block_microtick_matches_per_agent(self):
        """The block form of the microtick (agents on rows, per-agent
        values as (B, 1) columns) == ``sim_microtick`` vmapped over the
        agents, for every output of the tick."""
        a = 5
        state = _batched_state(a)
        for i in range(4):
            arrivals, caps = _random_args(a, jax.random.fold_in(KEY, i))
            state = SimState(*jax.vmap(kref.queue_advance_ref)(
                *state, arrivals, caps))
        n_arr = arrivals[:, :1]
        col = lambda x: tuple(x[:, j:j + 1] for j in range(x.shape[1]))
        arrive, c, credits, lat_sum, bucket = kref.sim_microtick_rows(
            state.arrive, col(state.counters), col(state.credits),
            state.lat_sum[:, None], n_arr, col(caps), SP.hist_n)
        per = jax.vmap(kref.sim_microtick)(*state, n_arr[:, 0], caps)
        hist = state.hist + (bucket[:, :, None]
                             == jnp.arange(SP.hist_n)).sum(1)
        got = (arrive, jnp.concatenate(c, 1), jnp.concatenate(credits, 1),
               lat_sum[:, 0], hist)
        for name, g, p in zip(SimState._fields, got, per):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(p),
                                          err_msg=name)


class TestPythonOracleEquivalence:
    def test_twin_matches_slo_reference_request_for_request(self):
        """Tensorized twin == serving/slo.py data plane on a single-agent
        config: same completions, drops, effective throughput, and summed
        latency (integer-representable caps => exact)."""
        t_ints = 12
        arrivals = np.asarray(
            jax.random.randint(jax.random.PRNGKey(3), (t_ints, SP.k_ticks),
                               0, 7))
        rng = np.random.default_rng(0)
        caps = np.stack([
            rng.choice([1.5, 2.0, 2.5, 3.0], t_ints),
            rng.choice([2.0, 3.0, 4.0], t_ints),
            rng.choice([2.0, 4.0, 8.0], t_ints),
            rng.choice([1.0, 2.0, 3.0], t_ints),
            np.full(t_ints, 8.0),
            np.full(t_ints, 5.0),
        ], axis=1).astype(np.float32)

        s = sim_init(SP)
        for t in range(t_ints):
            s = sim_interval_ref(s, jnp.asarray(arrivals[t]),
                                 jnp.asarray(caps[t]))
        py = simulate_python_agent(arrivals, caps, SP)

        assert int(s.arrived) == py["arrived"]
        assert int(s.dropped) == py["dropped"]
        assert int(s.completed) == py["completed"]
        assert int(s.effective) == py["effective"]
        assert float(s.lat_sum) == py["lat_sum"]
        assert int(s.in_flight) == py["in_flight"]
        assert py["dropped"] > 0 and py["completed"] > 0  # both regimes hit


class TestHarness:
    def _fleet(self, a):
        cfg = FCPOConfig()
        fleet = fleet_init(cfg, a, KEY)
        traces = fleet_traces(jax.random.PRNGKey(1), a, 6)
        return cfg, fleet, traces

    def test_simulate_fleet_runs_jitted_and_conserves(self):
        cfg, fleet, traces = self._fleet(3)
        state, hist, summ = simulate_fleet(
            cfg, SP, fleet.astate.params, fleet.masks, fleet.env_params,
            traces, jax.random.PRNGKey(2))
        assert hist["throughput"].shape == (6, 3)
        conserved = (state.arrived
                     == state.dropped + state.completed + state.in_flight)
        assert bool(conserved.all())
        for k in ("throughput", "effective_throughput", "p50_latency_s",
                  "p99_latency_s", "drop_rate"):
            assert np.isfinite(np.asarray(summ[k])).all(), k
        assert (np.asarray(summ["effective"])
                <= np.asarray(summ["completed"])).all()

    @pytest.mark.pallas
    def test_pallas_harness_matches_jnp_harness(self):
        """Same key, same traces: the kernel-backed closed loop must be
        bit-identical to the jnp one (actions depend on twin state, so any
        data-plane divergence compounds — exact equality is the gate)."""
        cfg, fleet, traces = self._fleet(2)
        out_j = simulate_fleet(cfg, SP, fleet.astate.params, fleet.masks,
                               fleet.env_params, traces,
                               jax.random.PRNGKey(2))
        out_p = simulate_fleet(cfg, SP, fleet.astate.params, fleet.masks,
                               fleet.env_params, traces,
                               jax.random.PRNGKey(2), use_pallas=True)
        for name, j, p in zip(SimState._fields, out_j[0], out_p[0]):
            np.testing.assert_array_equal(np.asarray(j), np.asarray(p),
                                          err_msg=name)


class TestStateAndMetrics:
    def test_spread_arrivals_totals_and_bounds(self):
        for rate in (0.0, 1.0, 17.3, 399.9):
            arr, phase = spread_arrivals(SP, jnp.float32(rate))
            arr = np.asarray(arr)
            assert arr.shape == (SP.k_ticks,) and (arr >= 0).all()
            assert arr.sum() == int(np.floor(np.float32(rate) * SP.k_ticks
                                             * np.float32(SP.dt)))
            assert 0.0 <= float(phase) < 1.0

    def test_spread_arrivals_phase_carry_removes_rounding_bias(self):
        """Chaining intervals with the phase carry admits the fractional
        request rate on average (floor-per-interval would lose it)."""
        rate = jnp.float32(30.9)  # 12.36 requests per 8-tick interval
        total, phase = 0, jnp.float32(0.0)
        n_int = 50
        for _ in range(n_int):
            arr, phase = spread_arrivals(SP, rate, phase)
            total += int(np.asarray(arr).sum())
        expect = float(rate) * SP.k_ticks * SP.dt * n_int
        assert abs(total - expect) <= 1.0  # not floor()*n_int = -18 deficit

    def test_action_caps_are_positive_and_discrete(self):
        cfg = FCPOConfig()
        from repro.core.env import default_env_params
        ep = default_env_params()
        for a in ([0, 0, 0], [3, 6, 3], [1, 4, 2]):
            caps = np.asarray(action_caps(cfg, SP, ep,
                                          jnp.asarray(a, jnp.int32)))
            assert caps.shape == (kref.SIM_NCAPS,)
            assert (caps > 0).all()
            for i in (kref.CAP_BATCH, kref.CAP_TBATCH, kref.CAP_QCAP,
                      kref.CAP_SLO):
                assert caps[i] == int(caps[i])  # integer-valued
            assert caps[kref.CAP_QCAP] <= SP.ring // 3

    def test_hist_percentile(self):
        hist = jnp.asarray([0, 10, 0, 0, 0, 0, 0, 1])
        assert int(hist_percentile(hist, 0.5)) == 1
        assert int(hist_percentile(hist, 0.99)) == 7
        assert int(hist_percentile(jnp.zeros(8, jnp.int32), 0.5)) == 0

    def test_sim_interval_batched_equals_single(self):
        a = 3
        state = _batched_state(a)
        arrivals, caps = _random_args(a, KEY)
        out = sim_interval(state, arrivals, caps)
        one = sim_interval_ref(jax.tree.map(lambda x: x[1], state),
                               arrivals[1], caps[1])
        for name, b, s in zip(SimState._fields, out, one):
            np.testing.assert_array_equal(np.asarray(b[1]), np.asarray(s),
                                          err_msg=name)
