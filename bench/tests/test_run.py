"""CPU tests of the harness itself: no chip, no result; a compile inside
the window fails the run; a checkout without the program fails; seeds wider
than 32 bits work."""
import itertools
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import cells

ROOT = run.ROOT


def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.fluid-f32.dynamic", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _bench(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "No module named 'repro'" in proc.stderr


def test_config_key_the_entry_does_not_read_is_refused(monkeypatch):
    load = run.load_json

    def with_extra_key(path):
        data = load(path)
        if os.sep + "configs" + os.sep in path:
            data["state_policy"] = "lean"
        return data

    monkeypatch.setattr(run, "load_json", with_extra_key)
    with pytest.raises(run.RunError, match="state_policy"):
        run.prepare("train.fluid-f32.dynamic", 1, require_tpu=False)


def test_compile_inside_the_window_fails(monkeypatch, jax_config_restored):
    orig = run.run_window
    sizes = itertools.count(1)

    def window(entry, seconds, span=None):
        dispatch = entry.dispatch

        def compiling_dispatch():
            out = dispatch()
            jax.jit(lambda x: x * 2)(jnp.ones(next(sizes))).block_until_ready()
            return out

        entry.dispatch = compiling_dispatch
        return orig(entry, seconds, span)

    monkeypatch.setattr(run, "run_window", window)
    with pytest.raises(run.RunError, match="compiled inside"):
        cells.run_small("train.fluid-f32.dynamic", 3)


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2**31 + 7, 2**40 + 3])
def test_seed_key_takes_wide_seeds(seed):
    a, b = run.seed_key(seed), run.seed_key(seed)
    assert (a == b).all()
    assert not (run.seed_key(seed) == run.seed_key(seed + 1)).all()


def test_benchmark_json_names_files_that_exist():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "limits", f"{w['name']}.json"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "entries", f"{w['name'].split('.')[0]}.py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "layers", f"{run.quantity(m['name'])}.py"))
