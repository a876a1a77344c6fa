"""Faults planted under the timed path of the twin-evaluation (``sim``)
cells, to show their comparison refuses them (``bench/faults.py`` holds
the training cells'). Each is a context manager that replaces one piece of
the program while it is active and clears JAX's caches on entry and exit.
They serve ``bench/tests/test_sim_reid_twin_burst.py`` (at a small size
on the CPU) and ``bench/calibrate_sim.py`` (at the cell's size on the chip).

- ``no_fanout``: a stage after the detector gets one request per completed
  frame (objects per frame taken as 1).
- ``late_fanout``: a stage's fanned-out requests enter the next stage one
  control interval late, all in the first tick of the next interval.
- ``late_handoff``: they enter one microtick late (the last tick's in the
  first tick of the next interval).
- ``altered_answer``: the twin kernels report one extra completed request
  per agent and interval, in every stage.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from bench.faults import COMPLETED, _patched


def _objects_fault(change):
    from repro.sim import harness

    def make(orig):
        def simulate(cfg, sp, pipe, params, masks, env_params, traces,
                     objects, *args, **kw):
            return orig(cfg, sp, pipe, params, masks, env_params, traces,
                        change(objects), *args, **kw)
        return simulate

    return _patched(harness, "_simulate", make)


def no_fanout():
    return _objects_fault(jnp.ones_like)


@contextlib.contextmanager
def _handoff_fault(delayed):
    """Replace the fan-out with ``delayed(fanout_arrivals)`` and run the
    program's jnp route, whose stage-by-stage interval calls it (the
    kernel route equals that route bit for bit, and has the fan-out
    inside the kernel). Requests held over to the next interval ride in
    the whole part of the carried phase."""
    from repro.sim import harness

    def simulate(orig):
        def call(*args, **kw):
            return orig(*args, **dict(kw, use_pallas=False))
        return call

    with _patched(harness, "fanout_arrivals", delayed), \
            _patched(harness, "_simulate", simulate):
        yield


def _late_by_an_interval(fanout):
    def arrivals(objects, completions, phase):
        held = jnp.floor(phase)
        counts = jnp.zeros_like(completions).at[..., 0].set(
            held.astype(completions.dtype))
        return counts, phase - held + objects * jnp.sum(
            completions, axis=-1).astype(jnp.float32)
    return arrivals


def _late_by_a_tick(fanout):
    def arrivals(objects, completions, phase):
        held = jnp.floor(phase)
        shifted = jnp.concatenate([jnp.zeros_like(completions[..., :1]),
                                   completions[..., :-1]], axis=-1)
        counts, rest = fanout(objects, shifted, phase - held)
        counts = counts.at[..., 0].add(held.astype(counts.dtype))
        return counts, rest + objects * completions[..., -1].astype(
            jnp.float32)
    return arrivals


def late_fanout():
    return _handoff_fault(_late_by_an_interval)


def late_handoff():
    return _handoff_fault(_late_by_a_tick)


@contextlib.contextmanager
def altered_answer():
    from repro.kernels import ops

    def make(orig):
        def advance(*args, **kw):
            out = orig(*args, **kw)
            return (out[0], out[1].at[..., COMPLETED].add(1)) + out[2:]
        return advance

    with _patched(ops, "queue_advance", make), \
            _patched(ops, "pipeline_advance", make):
        yield


FAULTS = {"sim": {"no_fanout": no_fanout, "late_fanout": late_fanout,
                  "late_handoff": late_handoff,
                  "altered_answer": altered_answer}}
