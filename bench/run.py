"""Run one benchmark cell on the chips this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), the entry that drives it
(``bench/entries/<first part of the cell name>.py``), its comparison limits
(``bench/limits/<cell>.json``) and its per-layer readers
(``bench/layers/<metric>.py``) are all found by name. A metric named
``<quantity>.<part>`` (``intervals_per_s.twin``) is that quantity in the
cells its entry lists, under a bound or reader of its own: the end-to-end
quantity, or the reader ``bench/layers/<quantity>.py``, is the one before
the first dot.

A run needs a TPU and exits non-zero without one (there is no CPU
fallback). It builds the cell's fleet and traffic on the device from the
seed, warms up every shape the window uses (set-up), then dispatches for
``--seconds`` and fails if anything compiled in that window. With ``--trace
0`` it reports the cell's end-to-end metrics; with ``--trace 1`` the same
window runs under the profiler and the run reports the per-layer metrics
read from the device trace. After the window, the program's state is freed
and its answers are compared with the plain reference; every number
compared is printed beside its limit, last on standard error and last in
the result line, which is the last line on standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "artifacts", "bench_trace")
# the traced window, whichever ends first: traces are large and slow to read
TRACE_SECONDS = 4.0
TRACE_DISPATCHES = 24
# configuration keys that describe and do not set (see PERF.md section 4)
PROSE_KEYS = {"name", "source", "deployment", "assumed", "reduced"}


class RunError(Exception):
    """A run that cannot report a result."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec, name):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return cell, config


def seed_key(seed):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    import jax
    import numpy as np

    word = int(np.random.SeedSequence(seed).generate_state(1)[0]) >> 1
    return jax.random.PRNGKey(word)


def check_devices(chips):
    """The device facts every result names; no TPU, or too few chips, is an
    error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX sees {devs[0].platform!r} devices and "
                       f"the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Counts the programs JAX traces and compiles (or fetches from the
    persistent cache) while ``armed``."""

    def __init__(self):
        import jax

        from jax._src import dispatch

        self.names = {"compile": dispatch.BACKEND_COMPILE_EVENT,
                      "trace": dispatch.JAXPR_TRACE_EVENT}
        self.counts = {k: 0 for k in self.names}
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed:
            for k, name in self.names.items():
                if event == name:
                    self.counts[k] += 1


def run_window(entry, seconds, span=None, max_dispatches=None):
    """Dispatch until ``seconds`` have passed (or ``max_dispatches`` have
    run); the dispatch in flight when time runs out finishes. Returns
    (dispatches, elapsed seconds, each dispatch's seconds)."""
    times = []
    t0 = time.perf_counter()
    while (time.perf_counter() - t0 < seconds
           and len(times) != max_dispatches):
        t = time.perf_counter()
        with span("bench.dispatch") if span else contextlib.nullcontext():
            entry.dispatch()
        times.append(time.perf_counter() - t)
    return len(times), time.perf_counter() - t0, times


def peak_memory(chips):
    import jax

    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def quantity(name):
    """What a metric measures: its name up to the first dot."""
    return name.split(".")[0]


def cell_metrics(metrics, cell_name):
    """The metrics of ``metrics`` that the cell reports."""
    return [m for m in metrics if cell_name in m.get("workloads", [cell_name])]


def read_layers(spec, cell_name, ctx):
    """{metric: {"value", "unit"}} from each per-layer reader that finds
    something to read in this cell."""
    out = {}
    for m in cell_metrics(spec["per_layer"], cell_name):
        q = quantity(m["name"])
        reader = load_module(os.path.join(BENCH, "layers", f"{q}.py"),
                             f"bench_layer_{q}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(numbers, limits):
    """{name: {"value", "limit", "detail"}} and whether all are within."""
    checks, ok = {}, True
    for name, (value, detail) in numbers.items():
        limit = limits[name]
        checks[name] = {"value": value, "limit": limit, "detail": detail}
        ok = ok and value <= limit
    return checks, ok


def prepare(cell_name, seed, require_tpu=True, n_agents=None):
    """Everything a run of the cell needs before set-up: (spec, cell,
    config, limits, device facts, entry). ``n_agents`` is for the tests,
    which drive a run on the CPU at a small size."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # the cache is the checkout's own: no eviction, whose bookkeeping
    # breaks on entries that lack their access-time file
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg_entry = find_cell(spec, cell_name)
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    limits = load_json(os.path.join(BENCH, "limits", f"{cell_name}.json"))

    import jax

    from repro.launch import compile_cache

    from bench import traffic_gen

    chips = cell["chips"]
    if require_tpu:
        device = check_devices(chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": chips}
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = cell_name.split(".")[0]
    entries = load_module(os.path.join(BENCH, "entries", f"{kind}.py"),
                          f"bench_entry_{kind}")
    unread = set(config) - PROSE_KEYS - entries.READS
    if unread:
        raise RunError(f"{cfg_entry['file']} sets {sorted(unread)}, which "
                       f"the {kind} entry does not read")
    entry = entries.Entry(config, traffic_gen.load_mix(cell["traffic"]),
                          seed_key(seed), chips, n_agents=n_agents)
    return spec, cell, config, limits, device, entry


def run_cell(cell_name, seed, seconds, trace, require_tpu=True,
             n_agents=None):
    """One run of a cell; returns the result dict."""
    spec, cell, config, limits, device, entry = prepare(
        cell_name, seed, require_tpu, n_agents)
    chips = cell["chips"]

    import jax

    from bench import trace_reduce

    counter = CompileCounter()
    entry.setup()
    setup_s = time.perf_counter() - T_START

    counter.armed = True
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        attempted, window_s, times = run_window(
            entry, min(seconds, TRACE_SECONDS), jax.profiler.TraceAnnotation,
            TRACE_DISPATCHES)
        jax.profiler.stop_trace()
    else:
        attempted, window_s, times = run_window(entry, seconds)
    counter.armed = False
    if counter.counts["compile"]:
        raise RunError(f"{counter.counts['compile']} program(s) compiled "
                       f"inside the measured window "
                       f"({counter.counts['trace']} traced)")

    device["memory_peak_bytes"] = (peak_memory(chips) if require_tpu
                                   else 0)
    intervals = attempted * entry.intervals_per_dispatch
    # a dispatch that raises ends the run without a result
    result = {"correct": None, "attempted": attempted, "failed": 0}
    if trace:
        red = trace_reduce.reduce_dir(TRACE_DIR, chips)
        # counted from the trace, so that the per-dispatch readings divide
        # the device time the trace holds by the dispatches it holds
        n = red.main_runs()
        ctx = {"trace": red, "config": config, "chips": chips,
               "dispatches": n,
               "episodes": n * entry.episodes_per_dispatch,
               "rounds": n * entry.rounds_per_dispatch,
               "agents": entry.n_agents,
               "intervals": n * entry.intervals_per_dispatch,
               "peaks": trace_reduce.peaks_for(device["kind"],
                                                require_tpu)}
        result["metrics"] = read_layers(spec, cell_name, ctx)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    else:
        values = {"setup_s": setup_s,
                  "intervals_per_s": intervals / window_s,
                  "peak_hbm_mb": device["memory_peak_bytes"] / 1e6}
        result["metrics"] = {
            m["name"]: {"value": values[quantity(m["name"])],
                        "unit": m["unit"]}
            for m in cell_metrics(spec["end_to_end"], cell_name)}
    result["device"] = device
    # each dispatch's time, so that a stalled one shows in the run's output
    slowest = max(range(len(times)), key=times.__getitem__)
    result["dispatch_ms"] = {"median": 1e3 * statistics.median(times),
                             "max": 1e3 * times[slowest],
                             "slowest": slowest}
    entry.free()
    checks, ok = judge(entry.numbers(), limits)
    result["correct"] = ok
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (RunError, FileNotFoundError, ModuleNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    d = result["dispatch_ms"]
    print(f"dispatch ms: median {d['median']!r}, slowest {d['max']!r} "
          f"(dispatch {d['slowest']})", file=sys.stderr, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}; "
              f"{c['detail']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
