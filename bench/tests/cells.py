"""Shared helpers of the per-cell CPU tests: a run of a cell at a small
fleet, with the harness's look for a chip skipped."""
from bench import faults, run

AGENTS = 8
SECONDS = 0.5


def run_small(cell, seed):
    return run.run_cell(cell, seed, SECONDS, False, require_tpu=False,
                        n_agents=AGENTS)


def control_numbers(cell, seed):
    """The cell's numbers with the bfloat16 reference in the program's
    place, and the cell's limits."""
    _, _, _, limits, _, entry = run.prepare(cell, seed, require_tpu=False,
                                       n_agents=AGENTS)
    entry.setup()
    entry.free()
    return entry.numbers(control=True), limits


def faulted_run(cell, fault, seed):
    with faults.FAULTS[cell.split(".")[0]][fault]():
        return run_small(cell, seed)


def assert_result_shape(res):
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    for c in res["checks"].values():
        assert set(c) == {"value", "limit", "detail"}
