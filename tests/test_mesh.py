"""Real multi-device mesh execution of the fleet (simulated host devices).

Run the multi-device cases with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI mesh job
does): 8 simulated CPU devices, a (pod=2, data=4) fleet mesh, and the full
scanned driver executing SPMD. The contract under test is the tentpole of
the scaling work: the meshed run must match the single-device run to
reduction-order ULPs (the sharding hints and collectives are placement,
not math), with the fleet state actually partitioned across devices.

Spec-only cases (no multi-device requirement) always run, so the default
single-device tier-1 suite still covers the sharding rules.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.fcpo import FCPOConfig
from repro.core.fleet import (fleet_device_bytes, fleet_init,
                              fleet_shardings, train_fleet_scan)
from repro.data.workload import fleet_traces
from repro.distributed import sharding as shd
from repro.launch.mesh import make_fleet_mesh

CFG = FCPOConfig()
KEY = jax.random.PRNGKey(0)

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


class TestFleetMeshFactory:
    def test_pod_by_data_factorization(self):
        if jax.device_count() < 8:
            pytest.skip("needs 8 devices")
        mesh = make_fleet_mesh(8, 2)
        assert dict(mesh.shape) == {"pod": 2, "data": 4}

    def test_indivisible_pods_fall_back_to_data_only(self):
        mesh = make_fleet_mesh(jax.device_count(), 3)
        if jax.device_count() % 3 == 0:
            assert mesh.shape["pod"] == 3
        else:
            assert mesh.shape["pod"] == 1
            assert mesh.shape["data"] == jax.device_count()


class _SpecMesh:
    """Shape-only stand-in for a Mesh: ``greedy_spec`` and the fleet spec
    rules read nothing but ``mesh.shape``, so the placement logic is
    testable on any device count."""
    shape = {"pod": 2, "data": 4}


class TestFleetShardingSpecs:
    """Placement rules — valid on any device count (specs are symbolic)."""

    def test_agent_leaves_shard_over_pod_data(self):
        mesh = _SpecMesh()
        assert shd.agent_spec((8, 31), mesh) == P(("pod", "data"))
        # A=4 does not fill pod*data=8 -> falls through to data alone
        assert shd.agent_spec((4, 31), mesh) == P("data")
        # A=3 divides nothing -> replicated
        assert shd.agent_spec((3, 31), mesh) == P()

    def test_pod_leaves_ride_the_fl_hierarchy_axis(self):
        mesh = _SpecMesh()
        # the pod axis is tried first and wins whenever P divides it
        assert shd.pod_spec((2, 31), mesh) == P("pod")
        assert shd.pod_spec((4, 31), mesh) == P("pod")
        # indivisible P -> replicated (always valid for the small base nets)
        assert shd.pod_spec((3, 31), mesh) == P()

    def test_pod_leaves_fall_back_to_data_without_a_pod_axis(self):
        class _DataMesh:
            shape = {"data": 4}
        assert shd.pod_spec((4, 31), _DataMesh()) == P("data")
        assert shd.pod_spec((2, 31), _DataMesh()) == P()

    @multi_device
    def test_fleet_shardings_field_placement(self):
        mesh = make_fleet_mesh(8, 2)
        fleet = fleet_init(CFG, 8, KEY, n_pods=2)
        shards = fleet_shardings(fleet, mesh)
        agent = P(("pod", "data"))
        for leaf in jax.tree.leaves(shards.astate.params):
            assert leaf.spec == agent
        for leaf in jax.tree.leaves(shards.astate.buffer):
            assert leaf.spec == agent
        for leaf in jax.tree.leaves(shards.residuals):
            assert leaf.spec == agent
        # per-pod base networks + partition timer ride the FL hierarchy
        for leaf in jax.tree.leaves(shards.base_params):
            assert leaf.spec == P("pod")
        assert shards.partition_timer.spec == P("pod")
        # the scalar episode counter is replicated
        assert shards.episode.spec == P()


@multi_device
class TestMeshedTraining:
    def test_meshed_scan_matches_single_device(self):
        """The tentpole contract: agents over (pod, data), pods over the FL
        hierarchy, Alg. 1 + pod-merge as real collectives — and the numbers
        do not move beyond reduction-order ULPs. Per-agent math is
        elementwise (identical under any placement); cross-agent means
        become partitioned collectives whose float accumulation order
        depends on the device split, and that ULP drift compounds through
        the training feedback loop — observed max absolute drift 4e-6
        after 8 episodes on 8 devices, so the contract is tight numeric
        equivalence (atol 1e-5), not bitwise equality."""
        n, eps = 16, 8
        traces = fleet_traces(jax.random.PRNGKey(1), n, eps * CFG.n_steps)
        kw = dict(straggler_prob=0.3, seed=7)

        f0 = fleet_init(CFG, n, KEY, n_pods=2)
        sf, sh = train_fleet_scan(CFG, f0, traces, **kw)

        mesh = make_fleet_mesh(8, 2)
        f1 = fleet_init(CFG, n, KEY, n_pods=2, mesh=mesh)
        mf, mh = train_fleet_scan(CFG, f1, traces, mesh=mesh, **kw)

        tol = dict(rtol=1e-5, atol=1e-5)
        for k in sh:
            np.testing.assert_allclose(np.asarray(sh[k], dtype=np.float32),
                                       np.asarray(mh[k], dtype=np.float32),
                                       err_msg=k, **tol)
        for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(mf)):
            a, b = np.asarray(a), np.asarray(b)
            if np.issubdtype(a.dtype, np.floating):
                np.testing.assert_allclose(a.astype(np.float32),
                                           b.astype(np.float32), **tol)
            else:
                np.testing.assert_array_equal(a, b)

    def test_meshed_outputs_are_sharded(self):
        """The result must actually live distributed — a run that silently
        de-shards to replicated would pass the equality test while scaling
        nowhere."""
        n, eps = 16, 2
        mesh = make_fleet_mesh(8, 2)
        traces = fleet_traces(jax.random.PRNGKey(1), n, eps * CFG.n_steps)
        fleet = fleet_init(CFG, n, KEY, n_pods=2, mesh=mesh)
        out, _ = train_fleet_scan(CFG, fleet, traces, mesh=mesh)
        leaf = jax.tree.leaves(out.astate.params)[0]
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec == P(("pod", "data"))
        assert len(leaf.sharding.device_set) == 8
        # per-device accounting sees a balanced split of the fleet state
        per = fleet_device_bytes(out)
        assert len(per) == 8
        vals = sorted(per.values())
        assert vals[-1] <= 2.0 * vals[0]

    def test_meshed_stream_contents_match_history(self, tmp_path):
        """The metrics tap under SPMD: on a mesh the per-episode callback
        switches to an unordered one (ordered callbacks are single-device-
        only in XLA), but the scan's sequential data dependence still fires
        it once per episode. The stream must be complete, in episode order,
        and carry the same numbers the returned history does — a dropped or
        duplicated record would silently corrupt every live watcher."""
        from repro.eval.stream import MetricsSink, read_metrics
        n, eps = 16, 6
        mesh = make_fleet_mesh(8, 2)
        traces = fleet_traces(jax.random.PRNGKey(1), n, eps * CFG.n_steps)
        fleet = fleet_init(CFG, n, KEY, n_pods=2, mesh=mesh)
        path = str(tmp_path / "run.jsonl")
        with MetricsSink(path, meta={"agents": n}) as sink:
            _, hist = train_fleet_scan(CFG, fleet, traces, mesh=mesh,
                                       metrics_sink=sink, seed=3)
        meta, records = read_metrics(path)
        assert meta["agents"] == n
        assert [r["episode"] for r in records] == list(range(eps))
        for e, rec in enumerate(records):
            for k, v in rec.items():
                if k == "episode" or k not in hist:
                    continue
                np.testing.assert_allclose(
                    v, float(np.asarray(hist[k])[e]), rtol=1e-6, atol=1e-7,
                    err_msg=f"{k}@{e}")

    def test_meshed_run_with_lean_state_and_transport(self):
        """Mesh x dtype-policy x FL-codec composition: the lean fleet trains
        SPMD with the int8 transport codec and stays finite."""
        from repro.fl import TransportConfig
        n, eps = 16, 6
        mesh = make_fleet_mesh(8, 2)
        traces = fleet_traces(jax.random.PRNGKey(1), n, eps * CFG.n_steps)
        fleet = fleet_init(CFG, n, KEY, n_pods=2, mesh=mesh,
                           state_policy="lean")
        out, hist = train_fleet_scan(
            CFG, fleet, traces, mesh=mesh,
            transport=TransportConfig(codec="int8"))
        assert np.isfinite(np.asarray(hist["reward"])).all()
        assert jax.tree.leaves(out.astate.opt["m"])[0].dtype == jnp.bfloat16

    def test_meshed_kernels_match_single_device(self):
        """The fused twin and codec kernels on the mesh: Mosaic kernels
        cannot be partitioned by SPMD, so the ops dispatch runs them in
        shard_map over the agent axes. Same numbers as one device."""
        from repro.core.backends import get_backend
        from repro.fl import TransportConfig
        from repro.sim import SimParams
        n, eps = 16, 4
        backend = get_backend("twin", sim_params=SimParams(ring=64),
                              use_pallas=True)
        traces = fleet_traces(jax.random.PRNGKey(1), n, eps * CFG.n_steps)
        kw = dict(env_backend=backend, seed=3,
                  transport=TransportConfig(codec="int8", use_pallas=True))
        with pytest.warns(UserWarning, match="clamps queue_cap"):
            f0 = fleet_init(CFG, n, KEY, n_pods=2, env_backend=backend)
            mesh = make_fleet_mesh(8, 2)
            f1 = fleet_init(CFG, n, KEY, n_pods=2, env_backend=backend,
                            mesh=mesh)
        _, sh = train_fleet_scan(CFG, f0, traces, **kw)
        mf, mh = train_fleet_scan(CFG, f1, traces, mesh=mesh, **kw)
        for k in sh:
            np.testing.assert_allclose(np.asarray(sh[k], dtype=np.float32),
                                       np.asarray(mh[k], dtype=np.float32),
                                       rtol=1e-5, atol=1e-5, err_msg=k)
        per = fleet_device_bytes(mf)
        assert len(per) == 8
