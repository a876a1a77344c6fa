"""The readers of the program's own spans (``bench/program_spans.py`` and
``bench/layers/{prep,call,rollout,buffer,update}_ms.py``), on a small
hand-built trace and on a twin trace recorded on the chip
(``bench/tests/testdata``)."""
import os

import pytest

from bench import program_spans, run
from bench import trace_reduce as tr
from bench.tests.test_trace_reduce import _meta, _x, hand_trace

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
CHIP_TRACE = os.path.join(TESTDATA,
                          "train.twin-int8.nominal.spans.trace.json.gz")
QUANTITIES = ("prep_ms", "call_ms", "rollout_ms", "buffer_ms", "update_ms")
EP = "jit(_scan_driver)/while/body/closed_call/jit(fleet_episode)"


def reader(quantity):
    return run.load_module(os.path.join(run.BENCH, "layers",
                                        f"{quantity}.py"),
                           f"bench_layer_{quantity}")


def spans_trace():
    """Two dispatches of 100 us, each with the host stages prep (10 us),
    call (15 us) and fetch (70 us) inside ``bench.dispatch``; on the
    device, one 60 us program per dispatch: a 30 us rollout loop holding a
    20 us kernel, 8 us of buffer insert, 12 us of update (two ops, one
    fused under a transformation), 4 us of the episode outside any scope
    and 6 us of FL. A host span after the window does not count."""
    ev = _meta(3, "/device:TPU:0", {2: "XLA Modules", 3: "XLA Ops"})
    ev += _meta(9, "/host:CPU", {1: "main"})
    for t0 in (0.0, 100.0):
        ev.append(_x(9, 1, "bench.dispatch", t0, 100.0))
        ev.append(_x(9, 1, "fleet.prep", t0 + 1, 10.0))
        ev.append(_x(9, 1, "DevicePut", t0 + 2, 3.0))
        ev.append(_x(9, 1, "fleet.call", t0 + 12, 15.0))
        ev.append(_x(9, 1, "fleet.fetch", t0 + 28, 70.0))
        ev.append(_x(3, 2, "jit__scan_driver(1)", t0 + 30, 60.0))
        ev.append(_x(3, 3, "while.3", t0 + 30, 30.0,
                     f"{EP}/vmap(fcpo_rollout)/while:"))
        ev.append(_x(3, 3, "_queue_advance_impl.9", t0 + 35, 20.0,
                     f"{EP}/vmap(fcpo_rollout)/while/body/closed_call/"
                     f"jit(_queue_advance_impl)/pallas_call:"))
        ev.append(_x(3, 3, "fusion.1", t0 + 60, 8.0,
                     f"{EP}/vmap(fcpo_buffer)/while/body/dot_general:"))
        ev.append(_x(3, 3, "fusion.2", t0 + 68, 7.0,
                     f"{EP}/vmap(fcpo_update)/transpose(jvp())/"
                     f"dot_general:"))
        ev.append(_x(3, 3, "fusion.3", t0 + 75, 5.0,
                     f"{EP}/vmap(fcpo_update)/jit(fcpo_loss)/mul:"))
        ev.append(_x(3, 3, "fusion.4", t0 + 80, 4.0,
                     f"{EP}/vmap(fcpo_rollout_tail)/add:"))
        ev.append(_x(3, 3, "fusion.5", t0 + 84, 6.0,
                     "jit(_scan_driver)/while/body/cond/jit(fl_round)/"
                     "fl_aggregate/add:"))
    ev.append(_x(9, 1, "fleet.prep", 250.0, 500.0))
    return tr.Trace(ev, chips=1)


def ctx_of(trace, dispatches, episodes_per_dispatch=2):
    return {"trace": trace, "dispatches": dispatches,
            "episodes": dispatches * episodes_per_dispatch}


@pytest.mark.parametrize("quantity, want", [
    ("prep_ms", 10e-3), ("call_ms", 15e-3),
    # device readings per episode: 2 dispatches x 2 episodes
    ("rollout_ms", 2 * 30e-3 / 4), ("buffer_ms", 2 * 8e-3 / 4),
    ("update_ms", 2 * 12e-3 / 4)])
def test_reader_on_hand_built_trace(quantity, want):
    got = reader(quantity).read(ctx_of(spans_trace(), 2))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_reader_finds_nothing_without_the_spans(quantity):
    """A program older than the spans (the trace of the harness tests)
    reads None, and the harness leaves the metric out."""
    assert reader(quantity).read(ctx_of(hand_trace(), 2)) is None


@pytest.mark.parametrize("tf_op, scope, hit", [
    ("a/vmap(fcpo_update)/transpose(jvp())/dot_general:", "fcpo_update",
     True),
    ("a/jit(fl_round)/fl_encode/add:", "fl_encode", True),
    ("a/fcpo_rollout:", "fcpo_rollout", True),
    ("a/vmap(fcpo_rollout_tail)/add:", "fcpo_rollout", False),
    ("a/vmap(fcpo_update)/jit(fcpo_loss)/mul:", "fcpo_update", True),
    ("a/fcpo_updates/mul:", "fcpo_update", False),
    ("a/my_fcpo_buffer/add:", "fcpo_buffer", False)])
def test_scope_is_matched_as_a_whole_segment(tf_op, scope, hit):
    ev = _meta(3, "/device:TPU:0", {3: "XLA Ops"})
    ev.append(_x(3, 3, "fusion", 0.0, 5.0, tf_op))
    got = program_spans.scope_seconds(tr.Trace(ev, chips=1), scope)
    assert got == (pytest.approx(5e-6) if hit else 0)


def test_scopes_sum_within_the_episode():
    t = spans_trace()
    parts = sum(program_spans.scope_seconds(t, s) for s in
                ("fcpo_rollout", "fcpo_buffer", "fcpo_update"))
    assert parts == pytest.approx(2 * 50e-6)
    assert t.frame_time("fleet_episode") == pytest.approx(2 * 54e-6)


def test_idle_time_falls_in_the_host_stages():
    """Each device-idle stretch goes to the innermost host event at its
    middle: 0-30 us to the first call, 90-130 us (the first fetch's tail
    and the second prep) to that prep, 190-200 us to the last fetch; none
    to ``bench.dispatch``'s own time."""
    gaps = dict(spans_trace().breakdown()["idle_gaps"])
    assert gaps == {"fleet.call": pytest.approx(30e-6),
                    "fleet.prep": pytest.approx(40e-6),
                    "fleet.fetch": pytest.approx(10e-6)}


@pytest.fixture(scope="module")
def chip_ctx():
    """Five dispatches of ``train.twin-int8.nominal`` (40 agents, one v5e,
    the harness's traced window), trimmed to the fields the reduction
    reads."""
    t = tr.Trace(tr.load_events(CHIP_TRACE), chips=1)
    n = t.main_runs()
    return {"trace": t, "dispatches": n, "episodes": 2 * n}


@pytest.mark.parametrize("quantity, want", [
    ("prep_ms", 4.383484), ("call_ms", 2.1325798),
    ("rollout_ms", 13.227307521), ("buffer_ms", 0.3263672694),
    ("update_ms", 0.1267780294)])
def test_reader_on_recorded_chip_trace(chip_ctx, quantity, want):
    assert chip_ctx["dispatches"] == 5
    assert reader(quantity).read(chip_ctx) == pytest.approx(want, rel=1e-9)


def test_recorded_scopes_split_the_episode(chip_ctx):
    """The three scopes hold all but a sliver of the episode's device time,
    and the twin kernel runs inside the rollout."""
    t = chip_ctx["trace"]
    parts = {s: program_spans.scope_seconds(t, s) for s in
             ("fcpo_rollout", "fcpo_buffer", "fcpo_update")}
    episode = t.frame_time("fleet_episode")
    assert 0.99 * episode < sum(parts.values()) <= episode
    _, kernel_s = t.kernel("queue_advance")
    assert kernel_s < parts["fcpo_rollout"]


def test_recorded_idle_time_falls_in_the_host_stages(chip_ctx):
    """Device-idle time that the dispatch span's own time held before the
    host stages had spans now goes to them."""
    gaps = dict(chip_ctx["trace"].breakdown()["idle_gaps"])
    assert gaps["fleet.prep"] > 10 * gaps["bench.dispatch"]
    assert "fleet.fetch" in gaps
