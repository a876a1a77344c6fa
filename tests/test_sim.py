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
from repro.sim import (Pipeline, SimParams, SimState, action_caps,
                       fanout_arrivals, hist_percentile, pipeline_env_params,
                       sim_init, sim_interval, sim_interval_ref,
                       simulate_fleet, spread_arrivals)
from repro.sim.oracle import simulate_python_agent, simulate_python_pipeline
from repro.sim.step import sim_interval_recorded, tick_completions

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


class TestPipelineKernel:
    """The fused pipeline kernel (every stage in one call, the fan-out
    inside it) == the stage-by-stage jnp route, bit for bit, chained over
    several intervals: the state and every later stage's phase."""
    pytestmark = pytest.mark.pallas

    def _check(self, cams, stages, monkeypatch=None, budget=None):
        if budget:
            monkeypatch.setattr(qa_mod, "_VMEM_BUDGET", budget)
        pipe = Pipeline(cams, stages)
        a = pipe.agents
        state = ref = _batched_state(a)
        phase = ref_phase = jnp.zeros((a,), jnp.float32)
        for i in range(4):
            k = jax.random.fold_in(KEY, i)
            arrivals, caps = _random_args(a, k)
            obj = jax.random.randint(jax.random.fold_in(k, 9), (cams,), 0,
                                     5 * 256).astype(jnp.float32) / 256
            fan = jnp.concatenate([jnp.zeros_like(obj), obj]
                                  + [jnp.ones_like(obj)] * (stages - 2))
            *out, phase = qa_mod.pipeline_advance(
                *state, arrivals, caps, fan, phase, stages=stages,
                interpret=True)
            state = SimState(*out)
            parts, phases, done = [], [ref_phase[:cams]], None
            for s in range(stages):
                r = pipe.rows(s)
                arr = arrivals[r]
                if s > 0:
                    arr, ph = fanout_arrivals(fan[r], done, ref_phase[r])
                    phases.append(ph)
                st = jax.tree.map(lambda x: x[r], ref)
                st2, tk = jax.vmap(sim_interval_recorded)(st, arr, caps[r])
                done = tick_completions(st, tk)
                parts.append(st2)
            ref = SimState(*(jnp.concatenate(x) for x in zip(*parts)))
            ref_phase = jnp.concatenate(phases)
            for name, p, r in zip(SimState._fields, state, ref):
                np.testing.assert_array_equal(np.asarray(p), np.asarray(r),
                                              err_msg=f"{name} @ {i}")
            np.testing.assert_array_equal(np.asarray(phase),
                                          np.asarray(ref_phase))
        assert int(np.asarray(state.arrived)[pipe.rows(1)].sum()) > 0
        return pipe

    @pytest.mark.parametrize("cams, stages", [(1, 2), (4, 2), (3, 3)])
    def test_matches_stage_by_stage(self, cams, stages, pallas_grids):
        self._check(cams, stages)

    def test_pads_the_last_camera_block(self, monkeypatch, pallas_grids):
        """Cameras beyond one block run as blocks of a multiple of 8, the
        last one padded; a block holds its cameras' every stage."""
        self._check(13, 2, monkeypatch, 8 * 4 * 256 * 2)
        assert qa_mod.agent_block(13, SP.ring, SP.hist_n, SP.k_ticks + 3,
                                  rows_per_agent=2) == 8
        args = _random_args(26, KEY)
        call = functools.partial(qa_mod.pipeline_advance,
                                 stages=2, interpret=True)
        z = jnp.zeros((26,), jnp.float32)
        assert pallas_grids(lambda *x: call(*x, z, z), *_batched_state(26),
                            *args) == [(2,)]

    def test_bit_identical_under_vmap(self, pallas_grids):
        """vmap over independent fleets (each with its own objects and a
        mid-interval phase) == one call per fleet, as one kernel call whose
        grid gains the fleet axis."""
        cams, stages, n = 3, 2, 2
        a = cams * stages
        call = functools.partial(qa_mod.pipeline_advance, stages=stages,
                                 interpret=True)
        fleets = []
        for b in range(n):
            k = jax.random.fold_in(KEY, b)
            obj = jax.random.randint(jax.random.fold_in(k, 9), (cams,), 0,
                                     5 * 256).astype(jnp.float32) / 256
            fleets.append((*_batched_state(a), *_random_args(a, k),
                           jnp.concatenate([jnp.zeros_like(obj), obj]),
                           jax.random.uniform(jax.random.fold_in(k, 3),
                                              (a,))))
        batched = jax.tree.map(lambda *v: jnp.stack(v), *fleets)
        out = jax.vmap(call)(*batched)
        for b, args in enumerate(fleets):
            for name, v, s in zip(SimState._fields + ("phase",), out,
                                  call(*args)):
                np.testing.assert_array_equal(np.asarray(v[b]),
                                              np.asarray(s),
                                              err_msg=f"{name} @ fleet {b}")
        assert int(np.asarray(out[1])[:, cams:, kref.SIM_ARRIVED].sum()) > 0
        assert pallas_grids(jax.vmap(call), *batched) == [(n, 1)]


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
        state, hist, summ, _ = simulate_fleet(
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


CAMERAS, STAGES, INTERVALS = 3, 2, 40
PIPE = Pipeline(CAMERAS, STAGES)


def _pipeline_inputs():
    """Seeded untrained policies on 3 cameras x (detector, re-ID), the
    re-ID stage at 4x the camera's device and the 0.15 / 0.10 s SLO split;
    frame rates and objects per frame with a 4x frame burst and a 3x
    object burst."""
    from repro.core.fleet import device_speeds
    from repro.data.workload import object_counts

    cfg = FCPOConfig()
    fleet = fleet_init(cfg, PIPE.agents, KEY)
    ep = pipeline_env_params(PIPE, device_speeds(CAMERAS), (1.0, 4.0),
                             (0.15, 0.10))
    traces = fleet_traces(jax.random.PRNGKey(1), CAMERAS, INTERVALS)
    traces = traces.at[:, 10:13].multiply(4.0)
    objects = object_counts(jax.random.PRNGKey(5), CAMERAS, INTERVALS)
    objects = jnp.minimum(objects.at[:, 20:23].multiply(3.0), 12.0)
    return cfg, fleet.astate.params, fleet.masks, ep, traces, objects


def _run_pipeline(use_pallas=False, **kw):
    cfg, params, masks, ep, traces, objects = _pipeline_inputs()
    return simulate_fleet(cfg, SP, params, masks, ep, traces,
                          jax.random.PRNGKey(2), use_pallas=use_pallas,
                          pipeline=PIPE, objects=objects, **kw)


class TestPipeline:
    @pytest.mark.parametrize("use_pallas", [
        False, pytest.param(True, marks=pytest.mark.pallas)])
    def test_matches_python_pipeline_oracle(self, use_pallas):
        """Detector -> re-ID through the stage-ordered, fan-out-coupled
        twin == the per-tick Python pipeline oracle given the same decoded
        caps: every agent's totals and every interval's routed count."""
        cfg, _, _, ep, traces, objects = _pipeline_inputs()
        run = _run_pipeline(use_pallas)
        actions = jnp.asarray(run.history["actions"])          # (T, A, 3)
        caps = jax.vmap(jax.vmap(lambda e, ac: action_caps(cfg, SP, e, ac)),
                        in_axes=(None, 0))(ep, actions)          # (T, A, 6)
        arrivals, phase = [], jnp.zeros((CAMERAS,), jnp.float32)
        for t in range(INTERVALS):
            arr, phase = jax.vmap(lambda r, ph: spread_arrivals(SP, r, ph))(
                traces[:, t], phase)
            arrivals.append(np.asarray(arr))
        totals, routed = simulate_python_pipeline(
            np.stack(arrivals, axis=1), np.asarray(objects),
            np.asarray(caps).transpose(1, 0, 2), SP, STAGES)
        st = run.state
        for k in ("arrived", "dropped", "completed", "effective",
                  "in_flight"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st, k)), [t[k] for t in totals],
                err_msg=k)
        np.testing.assert_array_equal(np.asarray(st.lat_sum),
                                      [t["lat_sum"] for t in totals])
        np.testing.assert_array_equal(run.history["routed"], routed)
        # both stages did work, the burst overflowed somewhere, and the
        # re-ID stage got exactly what the fan-out routed
        re_id = PIPE.rows(1)
        assert (np.asarray(st.completed)[PIPE.rows(0)] > 0).all()
        assert (np.asarray(st.arrived)[re_id] == routed[:, re_id].sum(0)).all()
        assert int(np.asarray(st.dropped).sum()) > 0

    @pytest.mark.pallas
    def test_kernel_route_equals_jnp_route(self):
        a, b = _run_pipeline(False), _run_pipeline(True)
        for name, x, y in zip(SimState._fields, a.state, b.state):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
        for k in a.history:
            np.testing.assert_array_equal(a.history[k], b.history[k],
                                          err_msg=k)

    @pytest.mark.parametrize("use_pallas", [
        False, pytest.param(True, marks=pytest.mark.pallas)])
    def test_two_chunks_equal_one_call(self, use_pallas):
        """Carrying the state from call to call: two chunks of T/2
        intervals == one of T, bit for bit (state, carry and history)."""
        cfg, params, masks, ep, traces, objects = _pipeline_inputs()
        half = INTERVALS // 2
        whole = _run_pipeline(use_pallas)
        kw = dict(use_pallas=use_pallas, pipeline=PIPE)
        first = simulate_fleet(cfg, SP, params, masks, ep, traces[:, :half],
                               jax.random.PRNGKey(2),
                               objects=objects[:, :half], **kw)
        second = simulate_fleet(cfg, SP, params, masks, ep, traces[:, half:],
                                None, objects=objects[:, half:],
                                carry=first.carry, **kw)
        for x, y in zip(jax.tree.leaves(whole.carry),
                        jax.tree.leaves(second.carry)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for k, v in whole.history.items():
            np.testing.assert_array_equal(
                v, np.concatenate([first.history[k], second.history[k]]),
                err_msg=k)

    @pytest.mark.parametrize("use_pallas", [
        False, pytest.param(True, marks=pytest.mark.pallas)])
    def test_one_stage_is_the_single_model_program(self, use_pallas):
        """S = 1 without objects is the single-model evaluation as it was
        before pipelines: its scan body, written out here, gives the same
        state and history bit for bit."""
        cfg, fleet, traces = TestHarness()._fleet(3)
        key = jax.random.PRNGKey(2)
        args = (fleet.astate.params, fleet.masks, fleet.env_params)
        run = simulate_fleet(cfg, SP, *args, traces, key,
                             use_pallas=use_pallas)
        state, hist = _single_model_simulate(cfg, SP, *args, traces, key,
                                             use_pallas)
        for name, x, y in zip(SimState._fields, run.state, state):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
        for k, v in hist.items():
            np.testing.assert_array_equal(run.history[k], np.asarray(v),
                                          err_msg=k)

    def test_fanout_conserves_objects_with_the_phase_carried(self):
        """Over many intervals the routed total is the floor of all the
        objects the completions sent on, and the phase holds the rest."""
        from fractions import Fraction

        rng = np.random.default_rng(7)
        n_int, cams = 50, 4
        done = rng.integers(0, 6, (n_int, cams, SP.k_ticks)).astype(np.int32)
        objects = rng.integers(0, 12 * 256, (n_int, cams)) / 256.0
        phase = jnp.zeros((cams,), jnp.float32)
        total = np.zeros(cams, np.int64)
        for t in range(n_int):
            arr, phase = fanout_arrivals(jnp.asarray(objects[t], jnp.float32),
                                         jnp.asarray(done[t]), phase)
            arr = np.asarray(arr)
            assert (arr >= 0).all()
            total += arr.sum(axis=1)
        for c in range(cams):
            sent = sum(Fraction(objects[t, c]) * int(done[t, c].sum())
                       for t in range(n_int))
            assert total[c] == int(sent)   # floor: every term is >= 0
            assert Fraction(float(phase[c])) == sent - int(sent)

    def test_needs_objects_and_matching_cameras(self):
        cfg, params, masks, ep, traces, objects = _pipeline_inputs()
        with pytest.raises(ValueError, match="objects per frame"):
            simulate_fleet(cfg, SP, params, masks, ep, traces, KEY,
                           pipeline=PIPE)
        with pytest.raises(ValueError, match="cameras"):
            simulate_fleet(cfg, SP, params, masks, ep, traces[:2], KEY,
                           pipeline=PIPE, objects=objects[:2])


def _single_model_simulate(cfg, sp, params, masks, env_params, traces, key,
                           use_pallas):
    """The single-model evaluation's scan as it stood before pipelines
    (one agent per trace, the trace's spread into its own ring)."""
    from repro.core.agent import sample_actions
    from repro.sim import sim_observe

    a = traces.shape[0]
    state0 = jax.vmap(lambda _: sim_init(sp))(jnp.arange(a))

    def interval(carry, rate):
        state, drops_prev, cur_action, phase, rng = carry
        rng, k = jax.random.split(rng)
        obs = jax.vmap(
            lambda e, s, d, ca, r: sim_observe(cfg, sp, e, s, d, ca, r)
        )(env_params, state, drops_prev, cur_action, rate)
        actions, _, _ = jax.vmap(
            lambda p, o, m, kk: sample_actions(cfg, p, o, m, kk)
        )(params, obs, masks, jax.random.split(k, a))
        caps = jax.vmap(
            lambda e, ac: action_caps(cfg, sp, e, ac))(env_params, actions)
        arrivals, phase = jax.vmap(
            lambda r, ph: spread_arrivals(sp, r, ph))(rate, phase)
        state2 = sim_interval(state, arrivals, caps, use_pallas)
        d_comp = (state2.completed - state.completed).astype(jnp.float32)
        d_drop = state2.dropped - state.dropped
        ys = {
            "throughput": d_comp / sp.interval_s,
            "effective_throughput":
                (state2.effective - state.effective).astype(jnp.float32)
                / sp.interval_s,
            "drops": d_drop.astype(jnp.float32),
            "latency": (state2.lat_sum - state.lat_sum)
                / jnp.maximum(d_comp, 1.0) * sp.dt,
            "pre_q": state2.pre_q.astype(jnp.float32),
            "post_q": state2.post_q.astype(jnp.float32),
            "actions": actions,
        }
        return (state2, d_drop, actions, phase, rng), ys

    init = (state0, jnp.zeros((a,), jnp.int32),
            jnp.zeros((a, 3), jnp.int32), jnp.zeros((a,), jnp.float32), key)
    (state, *_), history = jax.jit(
        lambda i, x: jax.lax.scan(interval, i, x))(init, traces.T)
    return state, history


class TestLauncher:
    """``launch/simulate.py``'s pipeline options."""

    def test_two_stage_pipeline(self, capsys):
        from repro.launch import simulate

        summ = simulate.main(["--agents", "3", "--stages", "2",
                              "--stage-speed", "1,4", "--stage-slo",
                              "0.15,0.10", "--intervals", "6",
                              "--scenario", "burst"])
        out = capsys.readouterr().out
        assert "3 cameras x 2 stage(s)" in out
        assert np.asarray(summ["arrived"]).shape == (6,)
        rows = [l.split() for l in out.splitlines()
                if l.split()[:1] in (["0"], ["1"])]
        assert [r[0] for r in rows] == ["0", "1"]
        assert int(rows[0][2]) == 0 and int(rows[1][2]) == int(rows[1][1])
        assert int(rows[1][1]) > 0     # the re-ID stage got objects

    @pytest.mark.parametrize("argv, message", [
        (["--stages", "2", "--stage-speed", "1"], "one value per stage"),
        (["--stages", "2", "--stage-slo", "0.1,0.1,0.1"],
         "one value per stage"),
        (["--stages", "0"], "--stages must be"),
        (["--stages", "2", "--train-episodes", "2"], "coupled stages"),
        (["--stages", "2", "--compare-fluid"], "no pipeline counterpart"),
    ])
    def test_pipeline_options_are_checked(self, argv, message, capsys):
        from repro.launch import simulate

        with pytest.raises(SystemExit):
            simulate.main(["--agents", "2", "--intervals", "2"] + argv)
        assert message in capsys.readouterr().err
