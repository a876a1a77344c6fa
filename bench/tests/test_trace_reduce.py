"""The reduction from a profiler trace to per-layer numbers, on a small
hand-built trace and on a recorded chip trace (``bench/testdata``)."""
import os

import pytest

from bench import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def _meta(pid, name, threads):
    evs = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}]
    evs += [{"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": t}} for tid, t in threads.items()]
    return evs


def _x(pid, tid, name, ts, dur, tf_op=""):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": {"tf_op": tf_op}}


EP = "jit(_scan_driver)/while/body/jit(fleet_episode)/vmap()"
FL = "jit(_scan_driver)/while/body/cond/jit(fl_round)/add"


def hand_trace():
    """Two dispatches of 100 us host spans; on the device, a 60 us program
    each, holding a 50 us loop (a 30 us kernel, a 10 us op inside) and a
    5 us FL op; a 2 us eager op sits between the programs."""
    ev = _meta(3, "/device:TPU:0", {2: "XLA Modules", 3: "XLA Ops"})
    ev += _meta(9, "/host:CPU", {1: "main"})
    for k, t0 in enumerate((0.0, 100.0)):
        ev.append(_x(9, 1, "bench.dispatch", t0, 100.0))
        ev.append(_x(9, 1, "PjitFunction(_scan_driver)", t0, 20.0))
        ev.append(_x(3, 2, "jit__scan_driver(1)", t0 + 20, 60.0))
        ev.append(_x(3, 3, "while.1", t0 + 20, 50.0, EP))
        ev.append(_x(3, 3, "_queue_advance_impl.9", t0 + 25, 30.0, EP))
        ev.append(_x(3, 3, "fusion.4", t0 + 56, 10.0, EP))
        ev.append(_x(3, 3, "fusion.7", t0 + 72, 5.0, FL))
        ev.append(_x(3, 2, "jit_reshape(2)", t0 + 90, 2.0))
        ev.append(_x(3, 3, "reshape.1", t0 + 90, 2.0))
    return tr.Trace(ev, chips=1)


def test_union_and_idle():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.idle_intervals([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                          (5, 6)]


def test_self_times_subtract_nested_ops():
    ops = [{"ts": 0, "dur": 50}, {"ts": 5, "dur": 30}, {"ts": 36, "dur": 10},
           {"ts": 52, "dur": 5}]
    assert tr.self_times(ops) == [10, 30, 10, 5]


def test_window_busy_and_idle():
    t = hand_trace()
    assert t.window_s == pytest.approx(200e-6)
    # per dispatch: 50 (loop) + 5 (FL) + 2 (eager) us busy
    assert t.busy_s == pytest.approx(2 * 57e-6)


def test_frame_attribution_uses_self_time():
    t = hand_trace()
    assert t.frame_time("fleet_episode") == pytest.approx(2 * 50e-6)
    assert t.frame_time("fl_round") == pytest.approx(2 * 5e-6)
    assert t.frame_time("pod_merge") == 0


def test_kernel_calls_and_time():
    t = hand_trace()
    assert t.kernel("queue_advance") == (2, pytest.approx(60e-6))
    assert t.kernel("delta_codec") == (0, 0)


def test_dispatch_gap_counts_idle_between_programs():
    t = hand_trace()
    # program 1 ends at 80, program 2 starts at 120; 2 us busy in between
    assert t.dispatch_gaps() == [pytest.approx(38e-6)]


def test_idle_gaps_attributed_to_host_activity():
    b = hand_trace().breakdown()
    gaps = dict(b["idle_gaps"])
    # idle 0-20 and 92-120 (its middle falls in the second dispatch call):
    # the host was dispatching the program
    assert gaps["PjitFunction(_scan_driver)"] == pytest.approx(48e-6)
    assert sum(gaps.values()) == pytest.approx(200e-6 - 2 * 57e-6)
    ops = dict(b["device_ops"])
    assert ops["_queue_advance_impl"] == pytest.approx(60e-6)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        tr.peaks_for("TPU v99")
    assert tr.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_chip_trace_reduces_as_on_the_chip():
    """A traced window of ``train.twin-int8.nominal`` at 2048 agents on one
    v5e (3 dispatches, trimmed to the fields the reduction reads) gives the
    numbers that run reported."""
    import json

    from bench import run

    t = tr.Trace(tr.load_events(os.path.join(
        TESTDATA, "train.twin-int8.nominal.trace.json.gz")), chips=1)
    assert t.window_s == pytest.approx(4.183016941)
    assert t.busy_s == pytest.approx(4.151549574954)
    assert t.kernel("queue_advance") == (60, pytest.approx(3.993779458858))
    assert t.kernel("delta_codec")[0] == 36      # 12 leaves x 3 rounds
    assert t.main_runs() == 3
    with open(os.path.join(run.BENCH, "configs", "fleet-twin.json")) as f:
        config = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    ctx = {"trace": t, "config": config, "chips": 1, "dispatches": 3,
           "episodes": 6, "rounds": 3, "agents": 2048,
           "intervals": 3 * 40960, "peaks": tr.peaks_for("TPU v5 lite")}
    got = {k: v["value"] for k, v in
           run.read_layers(spec, "train.twin-int8.nominal", ctx).items()}
    want = {"dispatch_gap_ms.twin": 10.548155625000014,
            "episode_ms.twin": 682.5968590286667,
            "fl_round_ms.twin": 11.693353072666667,
            "queue_advance_roofline": 0.01815265474234022,
            "delta_codec_roofline": 4.708797528415706,
            "idle_share.twin": 0.7522648483101135,
            "step_mfu.twin": 0.001145178013143893}
    assert got == pytest.approx(want, rel=1e-9)
    ops = t.breakdown()["device_ops"]
    assert ops[0][0] == "_queue_advance_impl"
