"""CPU tests of the cell ``sim.reid-twin.burst`` at a small fleet (4
cameras x detector, re-ID): a sound run is correct, the bfloat16 control
is refused, each planted fault (``bench/faults_sim.py``) is refused; and
the cell's own per-layer readers and byte count, on a hand-built trace."""
import os

import pytest

from bench import costs, costs_sim, faults_sim, program_spans_sim, run
from bench import trace_reduce as tr
from bench.tests import cells
from bench.tests.test_trace_reduce import _meta, _x, hand_trace

CELL = "sim.reid-twin.burst"
TWIN = {"dt": 0.05, "k_ticks": 20, "ring": 512, "hist_n": 64}
IAGENT = {"state_dim": 8, "hidden_dim": 64, "feat_dim": 48, "n_res": 4,
          "n_bs": 7, "n_mt": 4}
CONFIG = {"twin": TWIN, "iagent": IAGENT}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_sound_run_is_correct(jax_config_restored):
    res = cells.run_small(CELL, 2**31 + 4242)
    cells.assert_result_shape(res)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "intervals_per_s.twin",
                                   "peak_hbm_mb"}
    assert set(res["checks"]) == {"arrived", "dropped", "completed",
                                  "effective", "routed", "lat_sum", "logp"}


def test_control_is_refused(jax_config_restored):
    numbers, limits = cells.control_numbers(CELL, 88)
    _, ok = run.judge(numbers, limits)
    assert not ok, numbers


@pytest.mark.parametrize("fault", sorted(faults_sim.FAULTS["sim"]))
def test_fault_is_refused(fault, jax_config_restored):
    with faults_sim.FAULTS["sim"][fault]():
        res = cells.run_small(CELL, 41)
    assert res["correct"] is False, res["checks"]


# -- the cell's readers --------------------------------------------------
SIM = "jit(_simulate)/while/body"


def reader(quantity):
    return run.load_module(os.path.join(run.BENCH, "layers",
                                        f"{quantity}.py"),
                           f"bench_layer_{quantity}")


def sim_trace():
    """Two dispatches of 3 intervals each (6 fleet intervals); on the
    host, per dispatch, the spans ``sim.prep`` (0.5 us), ``sim.call`` (10),
    ``sim.fetch`` (80) and ``sim.summary`` (6); on the device, per
    dispatch: 6 us of policy (two ops, one under a vmap), 2 us
    of spread, 40 us under ``sim_advance`` holding three 10 us kernel
    calls, and 3 us outside any scope."""
    ev = _meta(3, "/device:TPU:0", {2: "XLA Modules", 3: "XLA Ops"})
    ev += _meta(9, "/host:CPU", {1: "main"})
    for t0 in (0.0, 100.0):
        ev.append(_x(9, 1, "bench.dispatch", t0, 100.0))
        ev.append(_x(9, 1, "sim.prep", t0 + 0.5, 0.5))
        ev.append(_x(9, 1, "sim.call", t0 + 1, 10.0))
        ev.append(_x(9, 1, "sim.fetch", t0 + 12, 80.0))
        ev.append(_x(9, 1, "sim.summary", t0 + 92, 6.0))
        ev.append(_x(3, 2, "jit__simulate(1)", t0 + 20, 60.0))
        ev.append(_x(3, 3, "fusion.1", t0 + 20, 4.0,
                     f"{SIM}/sim_policy/vmap(jit(_linear))/dot_general:"))
        ev.append(_x(3, 3, "fusion.2", t0 + 24, 2.0,
                     f"{SIM}/sim_policy/add:"))
        ev.append(_x(3, 3, "fusion.3", t0 + 26, 2.0,
                     f"{SIM}/sim_fanout/floor:"))
        ev.append(_x(3, 3, "while.1", t0 + 28, 40.0,
                     f"{SIM}/sim_advance/while:"))
        for k in (0, 12, 24):
            ev.append(_x(3, 3, "_pipeline_advance_impl.3", t0 + 30 + k,
                         10.0, f"{SIM}/sim_advance/"
                         f"jit(_pipeline_advance_impl)/pallas_call:"))
        ev.append(_x(3, 3, "fusion.4", t0 + 70, 3.0, f"{SIM}/add:"))
    return tr.Trace(ev, chips=1)


def ctx_of(trace, agents=8, intervals_per_dispatch=3):
    n = trace.main_runs()
    return {"trace": trace, "config": CONFIG, "chips": 1, "dispatches": n,
            "episodes": 0, "rounds": 0, "agents": agents,
            "intervals": n * agents * intervals_per_dispatch,
            "peaks": PEAKS}


@pytest.mark.parametrize("quantity, want", [
    ("policy_ms", 2 * 6e-3 / 6), ("fanout_ms", 2 * 2e-3 / 6),
    ("advance_ms", 2 * 40e-3 / 6)])
def test_scope_reader_on_hand_built_trace(quantity, want):
    assert reader(quantity).read(ctx_of(sim_trace())) == pytest.approx(want)


@pytest.mark.parametrize("quantity, want", [
    ("eval_prep_ms", 0.5e-3), ("eval_call_ms", 10e-3),
    ("eval_fetch_ms", 80e-3), ("eval_summary_ms", 6e-3)])
def test_host_span_reader_on_hand_built_trace(quantity, want):
    assert reader(quantity).read(ctx_of(sim_trace())) == pytest.approx(want)


def test_pipeline_roofline_counts_every_agent_once_a_call():
    """Six calls, each moving all 8 agents (both stages), in 60 us."""
    got = reader("pipeline_advance_roofline").read(ctx_of(sim_trace()))
    moved = 6 * costs_sim.pipeline_advance_bytes(8, TWIN)
    assert got == pytest.approx(100.0 * moved / 60e-6 / 819e9)


def test_eval_mfu_is_forward_flops_at_the_window_rate():
    t = sim_trace()
    got = reader("eval_mfu").read(ctx_of(t))
    rate = 2 * 8 * 3 / t.window_s
    assert got == pytest.approx(
        100.0 * costs.iagent_forward_flops(IAGENT) * rate / 197e12)


@pytest.mark.parametrize("quantity", ["policy_ms", "advance_ms", "fanout_ms",
                                      "pipeline_advance_roofline",
                                      "eval_prep_ms", "eval_call_ms",
                                      "eval_fetch_ms", "eval_summary_ms"])
def test_reader_finds_nothing_without_the_spans(quantity):
    """A program older than the pipeline scopes, host spans and kernel
    (its trace holds the training's ``fleet.*`` spans and the single-model
    ``queue_advance``) reads None."""
    assert reader(quantity).read(ctx_of(hand_trace())) is None


def test_scope_reader_needs_intervals():
    ctx = ctx_of(sim_trace())
    ctx["intervals"] = 0
    assert program_spans_sim.scope_ms_per_interval(ctx, "sim_policy") is None


def test_pipeline_bytes():
    state = 4 * (512 + 12 + 2 + 1 + 64)
    per_agent = 2 * state + 4 * (20 + 6) + 4 * 3
    assert costs_sim.pipeline_advance_bytes(80, TWIN) == 80 * per_agent
