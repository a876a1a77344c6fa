"""Ahead-of-time TPU v5e compiles of the main path's Pallas kernels.

Nothing runs: each test compiles a kernel at its real width for a described
(not attached) v5e chip and checks that Mosaic took it (``tpu_custom_call``
in the compiled HLO) — the refusals the CPU interpreter cannot show
(block tiling, unsupported primitives, layouts). The topology is described
inside a module fixture, never at import time: only one process at a time
may load the TPU compiler library, so collection must not touch it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import delta_codec as dc_mod
from repro.kernels import diversity as div_mod
from repro.kernels import flash_attention as fa_mod
from repro.kernels import ops as kops
from repro.kernels import packing as pack_mod
from repro.kernels import queue_advance as qa_mod
from repro.kernels import ref as kref

pytestmark = pytest.mark.pallas

# fleet-kernel widths: the phase-1 fleet of chip_smoke.py (1024 iAgents,
# ring 512, K=20 microticks) and the iAgent's flat parameter length
A, RING, K, HIST = 1024, 512, 20, 64
PARAMS_L = 4524
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # keep the compiler's logs off the disk, and these compiles out of the
    # persistent cache: they cannot be read back without a chip
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


def shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _twin_avals(sharding, a=A):
    return shapes(sharding, ((a, RING), I32), ((a, kref.SIM_NCOUNTERS), I32),
                  ((a, 2), F32), ((a,), F32), ((a, HIST), I32), ((a, K), I32),
                  ((a, kref.SIM_NCAPS), F32))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops dispatch asks the (CPU) backend whether to interpret:
    steer it to compile, and drop traces the interpreter made."""
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_queue_advance_compiles(one_chip):
    txt = compiled_text(qa_mod.queue_advance, *_twin_avals(one_chip))
    assert "tpu_custom_call" in txt


def test_queue_advance_per_agent_under_vmap_compiles(one_chip,
                                                     compiled_kernels):
    """The twin backend's call: one agent's operands, batched by the
    fleet's vmap into one call on the whole batch."""
    txt = compiled_text(jax.vmap(kops.queue_advance), *_twin_avals(one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("a", [40, A])
def test_queue_advance_vmap_is_one_call_over_agent_blocks(one_chip,
                                                          compiled_kernels,
                                                          pallas_grids, a):
    """Under the fleet's vmap the twin is one Mosaic call whose grid walks
    blocks of agents (one block for the 40-camera cell), never one grid
    step per agent."""
    avals = _twin_avals(one_chip, a)
    fn = jax.vmap(kops.queue_advance)
    blk = qa_mod.agent_block(a, RING, HIST, K)
    assert pallas_grids(fn, *avals) == [(-(-a // blk),)]
    assert (a == 40) == (blk == a)
    txt = compiled_text(fn, *avals)
    assert txt.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("codec", kref.DELTA_CODECS)
def test_delta_codec_compiles(one_chip, codec):
    avals = shapes(one_chip, ((A, PARAMS_L), F32), ((A, PARAMS_L), F32))
    k = max(1, round(0.05 * PARAMS_L))
    txt = compiled_text(lambda d, r: dc_mod.delta_codec(d, r, codec=codec,
                                                        k=k), *avals)
    assert "tpu_custom_call" in txt


def test_diversity_insert_compiles(one_chip):
    n, dim, na, t = 64, 8, 15, 10
    avals = shapes(one_chip, ((A, n, dim), F32), ((A, n, na), F32),
                   ((A, n), F32), ((A, n), jnp.bool_), ((A, dim), F32),
                   ((A, dim, dim), F32), ((A, na), F32), ((A,), I32),
                   ((A, t, dim), F32), ((A, t, na), F32))
    txt = compiled_text(lambda *xs: div_mod.diversity_insert(
        *xs, alpha=0.5, beta=0.5), *avals)
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_at_qwen2_head_shape(one_chip):
    # qwen2-0.5b: 14 query heads, 2 KV heads (GQA), head_dim 64
    q, kv = (1, 256, 14, 64), (1, 256, 2, 64)
    avals = shapes(one_chip, (q, jnp.bfloat16), (kv, jnp.bfloat16),
                   (kv, jnp.bfloat16))
    txt = compiled_text(fa_mod.flash_attention, *avals)
    assert "tpu_custom_call" in txt


def test_pack_compiles(one_chip):
    avals = shapes(one_chip, ((64, 896), F32), ((32,), I32))
    assert "tpu_custom_call" in compiled_text(pack_mod.pack, *avals)


def test_fleet_kernel_runs_per_device_under_a_mesh(topo, compiled_kernels):
    """Mosaic kernels cannot be partitioned by SPMD: under a mesh the ops
    dispatch runs them in shard_map, each chip on its own agents."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    agents = NamedSharding(mesh, P(("pod", "data")))
    avals = shapes(agents, ((A, PARAMS_L), F32), ((A, PARAMS_L), F32))
    with jax.set_mesh(mesh):
        txt = compiled_text(lambda d, r: kops.delta_codec(
            d, r, codec="int8"), *avals)
    assert "tpu_custom_call" in txt
    assert f"f32[{A // 4},1,{PARAMS_L}]" in txt   # a quarter per chip


def test_twin_kernel_runs_per_device_under_a_mesh(topo, compiled_kernels):
    """The fleet's vmap over one agent's twin call, with its agent axis
    named on the mesh: each chip runs the kernel once on its own quarter
    of the agents."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    avals = _twin_avals(NamedSharding(mesh, P(("pod", "data"))))
    fn = jax.vmap(kops.queue_advance, spmd_axis_name=("pod", "data"))
    with jax.set_mesh(mesh):
        txt = compiled_text(fn, *avals)
    assert txt.count('custom_call_target="tpu_custom_call"') == 1
    assert f"s32[{A // 4},{RING}]" in txt   # a quarter per chip
