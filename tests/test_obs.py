"""Flight-recorder tests: profiler spans, request attribution, live-metrics
resilience.

Four invariant families:

  * the profiler sees the program's stages and changes nothing — the
    lowered scan carries every ``fcpo_*``/``fl_*`` named scope (the names
    the benchmark's per-layer readers match), the host stages of
    ``train_fleet_scan`` land as ``fleet.prep`` < ``fleet.call`` <
    ``fleet.fetch`` annotations, the kernel wrappers keep their jit names,
    and a run under ``jax.profiler.trace`` is bit-identical to one without
    and reuses its executable;
  * the exported request timeline is well-formed — Chrome trace-event
    schema round-trips through JSON;
  * request attribution is a lossless decomposition — per-request stage
    stamps reconstructed from the twin's monotone counters conserve the
    twin's own aggregate counts/latency-sum/histogram EXACTLY (including a
    hypothesis sweep over random workloads), and the per-segment delays
    telescope to the total latency;
  * the live-metrics tap survives kills — ``MetricsSink(resume=True)``
    validates the meta header and appends (torn tails healed), and
    ``launch/watch.py`` degrades gracefully on meta-only files and unknown
    metric keys.
"""
import glob
import gzip
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fcpo import FCPOConfig
from repro.core.backends import get_backend
from repro.core.fleet import (_scan_fn, fleet_init, lower_fleet_scan,
                              train_fleet_scan)
from repro.eval.stream import MetricsSink, read_metrics
from repro.fl import TransportConfig
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import (CAP_BATCH, CAP_POST, CAP_PRE, CAP_QCAP,
                               CAP_SLO, CAP_TBATCH)
from repro.launch import watch
from repro.obs import Tracer, validate_chrome_trace
from repro.obs.requests import SEGMENTS, attribute_agent, attribute_run, \
    conservation_report, records_to_chrome, stage_decomposition
from repro.sim import SimParams, make_scenario, simulate_fleet
from repro.sim.state import sim_init
from repro.sim.step import sim_interval_recorded

A, EPISODES, SEED = 4, 4, 0


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("fcpo_rollout", "fcpo_buffer", "fcpo_update", "fl_uplink",
          "fl_encode", "fl_aggregate", "fl_finetune")
HOST_SPANS = ("fleet.prep", "fleet.call", "fleet.fetch")


def _profile_events(log_dir):
    """The complete events of the profiler trace written under
    ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.trace.json.gz"))
    with gzip.open(path, "rt") as f:
        return [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]


# ---------------------------------------------------------------------------
# Profiler spans: named scopes in the compiled scan, host annotations
# ---------------------------------------------------------------------------
def _lowered_scan_text(backend_name):
    """The scan a benchmark cell dispatches, lowered with its debug info:
    fluid with the lossless transport, the twin with both kernels and the
    int8 codec (interpret-mode Pallas on the CPU)."""
    cfg = FCPOConfig()
    twin = backend_name == "twin"
    backend = get_backend(backend_name, use_pallas=twin)
    fleet = fleet_init(cfg, A, jax.random.PRNGKey(SEED), n_pods=2,
                       env_backend=backend)
    traces = make_scenario("nominal", jax.random.PRNGKey(SEED + 1), A,
                           2 * cfg.n_steps)
    transport = (TransportConfig(codec="int8", use_pallas=True) if twin
                 else None)
    return lower_fleet_scan(cfg, fleet, traces, donate=False,
                            env_backend=backend,
                            transport=transport).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered_scans():
    """Each backend's lowered scan text, made once on first use."""
    texts = {}

    def get(backend_name):
        if backend_name not in texts:
            texts[backend_name] = _lowered_scan_text(backend_name)
        return texts[backend_name]
    return get


@pytest.fixture(scope="module")
def profiled_runs(tmp_path_factory):
    """The same two-call run without and then under the profiler."""
    cfg = FCPOConfig()
    fleet = fleet_init(cfg, A, jax.random.PRNGKey(SEED))
    traces = make_scenario("nominal", jax.random.PRNGKey(SEED + 1), A,
                           EPISODES * cfg.n_steps)
    kw = dict(seed=SEED, donate=False)
    off = [train_fleet_scan(cfg, fleet, traces, **kw) for _ in range(2)]
    size = _scan_fn(False)._cache_size()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        on = [train_fleet_scan(cfg, fleet, traces, **kw) for _ in range(2)]
    return {"off": off, "on": on, "cache_sizes": (size, _scan_fn(
        False)._cache_size()), "events": _profile_events(log_dir)}


class TestProfilerSpans:
    @pytest.mark.parametrize("backend", ["fluid", "twin"])
    @pytest.mark.parametrize("scope", SCOPES)
    def test_lowered_scan_carries_scope(self, lowered_scans, backend,
                                        scope):
        """Every stage the benchmark's readers split the device time by is
        a named scope in the op name stacks of the compiled scan."""
        text = lowered_scans(backend)
        assert f"{scope}/" in text or f"({scope})" in text

    def test_profiled_run_bit_identical(self, profiled_runs):
        """The profiler records; it never changes the numerics."""
        for a, b in zip(jax.tree.leaves(profiled_runs["off"]),
                        jax.tree.leaves(profiled_runs["on"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_profiled_run_does_not_recompile(self, profiled_runs):
        before, after = profiled_runs["cache_sizes"]
        assert after == before

    def test_host_spans_in_order_once_per_call(self, profiled_runs):
        spans = sorted((e for e in profiled_runs["events"]
                        if e["name"] in HOST_SPANS), key=lambda e: e["ts"])
        assert [e["name"] for e in spans] == list(HOST_SPANS) * 2
        assert len({e["tid"] for e in spans}) == 1
        for prev, nxt in zip(spans, spans[1:]):
            assert prev["ts"] + prev["dur"] <= nxt["ts"]

    @pytest.mark.parametrize("kernel", ["queue_advance", "delta_codec"])
    def test_kernel_wrappers_keep_their_jit_names(self, lowered_scans,
                                                  kernel):
        """A chip trace names a kernel's op after its jitted wrapper
        (``_<kernel>_impl.<n>``); the benchmark's rooflines match it."""
        assert kernel_ops.KERNEL_JITS[kernel].__name__ == f"_{kernel}_impl"
        assert f"jit(_{kernel}_impl)" in lowered_scans("twin")

    def test_launcher_trace_out_writes_profiler_trace(self, tmp_path):
        out = tmp_path / "prof"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                   PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.train_fleet", "--agents",
             "2", "--pods", "1", "--episodes", "2", "--trace-out", str(out)],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        names = [e["name"] for e in _profile_events(str(out))]
        for span in HOST_SPANS:
            assert names.count(span) == 1
        assert glob.glob(str(out / "plugins" / "profile" / "*" /
                             "perfetto_trace.json.gz"))


class TestChromeTraceSchema:
    def test_export_roundtrip(self, tmp_path):
        tr = Tracer(pid=7)
        tr.add_complete("req0/infer", ts_us=10.0, dur_us=5.0, pid=1000,
                        tid=2, args={"agent": 0})
        tr.add_complete("req0/pre", ts_us=2.0, dur_us=8.0, tid=1)
        path = tr.export(str(tmp_path / "trace.json"))
        with open(path) as f:
            trace = json.load(f)
        assert validate_chrome_trace(trace) == []
        ev = trace["traceEvents"]
        assert [e["name"] for e in ev] == ["req0/pre", "req0/infer"]
        assert ev[0]["pid"] == 7 and ev[1]["pid"] == 1000
        assert ev[1]["args"] == {"agent": 0}
        assert (ev[1]["ts"], ev[1]["dur"]) == (10.0, 5.0)

    def test_validator_catches_malformed(self):
        assert validate_chrome_trace([1, 2]) != []
        assert validate_chrome_trace({"nope": []}) != []
        assert validate_chrome_trace({"traceEvents": "x"}) != []
        ok = {"name": "a", "ph": "X", "ts": 0.0, "dur": 1.0,
              "pid": 1, "tid": 0}
        assert validate_chrome_trace({"traceEvents": [ok]}) == []
        for bad in (
            {k: v for k, v in ok.items() if k != "pid"},   # missing key
            dict(ok, ph="Z"),                               # unknown phase
            dict(ok, ts=-1.0),                              # negative ts
            {k: v for k, v in ok.items() if k != "dur"},   # X without dur
            "not-an-object",
        ):
            assert validate_chrome_trace({"traceEvents": [bad]}) != []


# ---------------------------------------------------------------------------
# Request-grade latency attribution
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded_run():
    cfg = FCPOConfig()
    sp = SimParams()
    a, t = 2, 8
    fleet = fleet_init(cfg, a, jax.random.PRNGKey(SEED))
    traces = make_scenario("steady", jax.random.PRNGKey(SEED + 2), a, t)
    args = (cfg, sp, fleet.astate.params, fleet.masks, fleet.env_params,
            traces, jax.random.PRNGKey(SEED + 3))
    state_plain, _, summ_plain = simulate_fleet(*args)
    state, history, summ = simulate_fleet(*args, record_ticks=True)
    return {"sp": sp, "state_plain": state_plain, "state": state,
            "history": history, "summ": summ, "summ_plain": summ_plain}


class TestRequestAttribution:
    def test_recording_is_bit_identical(self, recorded_run):
        for a, b in zip(jax.tree.leaves(recorded_run["state_plain"]),
                        jax.tree.leaves(recorded_run["state"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_conservation_against_twin_aggregates(self, recorded_run):
        out = attribute_run(recorded_run["history"], recorded_run["state"])
        for rep in out["conservation"]:
            assert rep["ok"], rep

    def test_segments_telescope_to_latency(self, recorded_run):
        out = attribute_run(recorded_run["history"], recorded_run["state"])
        for attr in out["agents"]:
            done = attr["completed"]
            total = sum(attr[s + "_ticks"][done] for s in SEGMENTS)
            assert np.array_equal(total, attr["latency_ticks"][done])

    def test_stage_decomposition_shape(self, recorded_run):
        out = attribute_run(recorded_run["history"], recorded_run["state"])
        dec = stage_decomposition(out["agents"], recorded_run["sp"].dt)
        assert set(dec) == set(SEGMENTS)
        for stats in dec.values():
            assert set(stats) == {"mean_s", "p50_s", "p99_s",
                                  "p99_tail_mean_s"}
            assert all(v >= 0.0 for v in stats.values())

    def test_records_export_to_valid_chrome_slices(self, recorded_run):
        out = attribute_run(recorded_run["history"], recorded_run["state"],
                            sample_every=4)
        tr = Tracer()
        n = records_to_chrome(tr, out["records"], recorded_run["sp"].dt)
        trace = tr.chrome_trace()
        assert n > 0 and validate_chrome_trace(trace) == []
        assert sum(1 for e in trace["traceEvents"] if e["ph"] == "X") == n

    def test_sampling_thins_records_not_conservation(self, recorded_run):
        full = attribute_run(recorded_run["history"], recorded_run["state"],
                             sample_every=1)
        thin = attribute_run(recorded_run["history"], recorded_run["state"],
                             sample_every=8)
        assert 0 < len(thin["records"]) < len(full["records"])
        for rep in thin["conservation"]:
            assert rep["ok"]


class TestAttributionProperty:
    """Conservation holds on arbitrary workloads, not just policy-driven
    ones: random arrivals and caps through the real microtick kernel."""

    def _caps(self, rng):
        caps = np.zeros(6, np.float32)
        caps[CAP_PRE] = rng.uniform(0.2, 4.0)
        caps[CAP_POST] = rng.uniform(0.2, 4.0)
        caps[CAP_BATCH] = rng.integers(1, 7)
        caps[CAP_TBATCH] = rng.integers(1, 7)
        caps[CAP_QCAP] = rng.integers(2, 13)
        caps[CAP_SLO] = rng.integers(1, 15)
        return caps

    def _check(self, seed, n_intervals, k_ticks=8):
        rng = np.random.default_rng(seed)
        sp = SimParams(dt=0.05, k_ticks=k_ticks, ring=64, hist_n=16)
        step = jax.jit(sim_interval_recorded)
        state = sim_init(sp)
        seqs, caps_seq = [], []
        for _ in range(n_intervals):
            caps = self._caps(rng)
            arrivals = rng.integers(0, 7, size=k_ticks)
            state, ticks = step(state, jnp.asarray(arrivals, jnp.int32),
                                jnp.asarray(caps))
            seqs.append(np.asarray(ticks))
            caps_seq.append(caps)
        seq = np.concatenate(seqs)
        attr = attribute_agent(seq, np.asarray(caps_seq), k_ticks)
        rep = conservation_report(attr, seq[-1],
                                  float(np.asarray(state.lat_sum)),
                                  np.asarray(state.hist))
        assert rep["ok"], (seed, rep)

    def test_random_workloads_conserve(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.settings(max_examples=20, deadline=None)
        @hyp.given(seed=st.integers(0, 2**32 - 1),
                   n_intervals=st.integers(1, 6))
        def prop(seed, n_intervals):
            self._check(seed, n_intervals)

        prop()

    def test_deterministic_slice(self):
        """Hypothesis-free slice of the property (runs even without the
        optional dependency)."""
        for seed in (0, 1, 2, 3):
            self._check(seed, n_intervals=4)


# ---------------------------------------------------------------------------
# Live-metrics resilience: sink resume + watcher degradation
# ---------------------------------------------------------------------------
META = {"agents": 4, "episodes": 8, "seed": 0}


class TestSinkResume:
    def test_resume_appends_after_kill(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with MetricsSink(path, meta=META) as sink:
            for e in range(3):
                sink.append({"episode": e, "reward": 0.1 * e})
        with MetricsSink(path, meta=META, resume=True) as sink:
            assert sink.n_records == 3
            for e in range(3, 5):
                sink.append({"episode": e, "reward": 0.1 * e})
        meta, records = read_metrics(path)
        assert meta == META
        assert [r["episode"] for r in records] == [0, 1, 2, 3, 4]

    def test_resume_heals_torn_tail(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with MetricsSink(path, meta=META) as sink:
            sink.append({"episode": 0, "reward": 0.5})
        with open(path, "a") as f:
            f.write('{"episode": 1, "rew')  # killed mid-write, no newline
        with MetricsSink(path, meta=META, resume=True) as sink:
            assert sink.n_records == 1  # torn line dropped, not counted
            sink.append({"episode": 1, "reward": 0.6})
        _, records = read_metrics(path)
        # the resumed record must not merge into the torn line
        assert [r["episode"] for r in records] == [0, 1]

    def test_resume_meta_mismatch_raises(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        MetricsSink(path, meta=META).close()
        with pytest.raises(ValueError, match="meta mismatch"):
            MetricsSink(path, meta=dict(META, agents=8), resume=True)

    def test_resume_headerless_file_raises(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with open(path, "w") as f:
            f.write('{"episode": 0, "reward": 0.5}\n')
        with pytest.raises(ValueError, match="header"):
            MetricsSink(path, meta=META, resume=True)

    def test_resume_missing_file_is_fresh_start(self, tmp_path):
        path = str(tmp_path / "new.jsonl")
        with MetricsSink(path, meta=META, resume=True) as sink:
            assert sink.n_records == 0
            sink.append({"episode": 0, "reward": 0.1})
        meta, records = read_metrics(path)
        assert meta == META and len(records) == 1


class TestWatchDegradation:
    def test_meta_only_file_renders_no_records_line(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        MetricsSink(path, meta=META).close()  # killed before episode 0
        text = watch.render(path, tail_k=5)
        assert "no records yet" in text
        assert "run:" in text

    def test_unknown_and_non_numeric_keys_skipped(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with MetricsSink(path, meta=META) as sink:
            sink.append({"episode": 0, "reward": 1.0,
                         "brand_new_metric": 2.0, "note": "hello"})
            sink.append({"episode": 1, "reward": "oops-a-string"})
        text = watch.render(path, tail_k=5)
        assert "reward" in text
        assert "brand_new_metric" not in text
        assert "note" not in text
