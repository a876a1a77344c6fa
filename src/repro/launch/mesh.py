"""Production mesh builders (functions, not module constants — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip, FLOP/s
HBM_BW = 819e9                # per chip, B/s
ICI_BW = 50e9                 # per link, B/s


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding is placed by the hints and
    XLA's partitioner, as every mesh of this repo expects (the default,
    Explicit, makes sharding part of each array's type)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (possibly fake) devices exist — for tests."""
    return make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(n_devices: int = None, n_pods: int = 1):
    """The fleet-training (pod, data) mesh over ``n_devices`` (default: all
    visible — e.g. 8 under ``XLA_FLAGS=--xla_force_host_platform_device_count
    =8``). The ``pod`` axis mirrors the FL hierarchy: it takes ``n_pods``
    devices when that divides the device count (per-pod base networks then
    live one-pod-per-shard and the cloud merge is a cross-pod all-reduce);
    otherwise pods replicate and agents shard over ``data`` alone —
    ``greedy_spec`` falls through safely either way."""
    n = jax.device_count() if n_devices is None else n_devices
    pod = n_pods if n_pods > 0 and n % n_pods == 0 else 1
    return make_mesh((pod, n // pod), ("pod", "data"))


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)
