import os

# Smoke tests and benches must see the single real CPU device (the 512-device
# override is ONLY for launch/dryrun.py, per the multi-pod dry-run contract).
# Exception: the mesh suite (tests/test_mesh.py) opts in explicitly with
# REPRO_MULTIDEVICE=1 + an 8-device override, as the CI `mesh` job does.
if os.environ.get("REPRO_MULTIDEVICE") != "1":
    assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""), \
        "dry-run device-count override must not leak into tests (set REPRO_MULTIDEVICE=1 to opt in)"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


def pytest_collection_modifyitems(config, items):
    # tier-1 stays fast: @pytest.mark.slow tests (full leaderboard grids,
    # long horizons) only run when explicitly requested with RUN_SLOW=1.
    if os.environ.get("RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow: set RUN_SLOW=1 to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


def _grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"].grid
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _grids(sub)


@pytest.fixture
def pallas_grids():
    """fn(*args) -> the grid of every pallas_call its jaxpr holds,
    sub-jaxprs included."""
    return lambda fn, *args: list(_grids(jax.make_jaxpr(fn)(*args).jaxpr))
