"""Faults planted under the timed path, to show the comparison refuses them.

Each fault is a context manager that replaces one piece of the program while
it is active and clears JAX's caches on entry and exit, so the next trace
of the timed call picks the fault up and the one after it does not. They
serve the per-cell tests ``bench/tests/test_<cell>.py`` (at a small size on
the CPU) and ``bench/calibrate.py`` (at the cell's size on the chip).

- ``unchanged_state``: a training round returns the fleet it was given.
- ``half_batch``: the CRL update's loss is the mean over the first half of
  the episode's samples only.
- ``no_merge``: the cloud tier's merge of the pod base networks is left
  out.
- ``altered_answer``: the twin kernel reports one extra completed request
  per agent and interval (cells that run the twin).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

COMPLETED = 9   # the twin's completed-requests counter


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, orig)
        jax.clear_caches()


def unchanged_state():
    from repro.core import fleet

    def make(orig):
        def scan_fn(donate):
            fn = orig(False)

            def call(*args):
                _, history = fn(*args)
                return args[1], history
            return call
        return scan_fn

    return _patched(fleet, "_scan_fn", make)


def half_batch():
    from repro.core import ppo

    def make(orig):
        def loss(cfg, params, rollout, mask):
            half = rollout.states.shape[0] // 2
            return orig(cfg, params, ppo.Rollout(*(x[:half] for x in rollout)),
                        mask)
        return loss

    return _patched(ppo, "fcpo_loss", make)


def altered_answer():
    def alter(out, args):
        counters = out[1].at[..., COMPLETED].add(1)
        return (out[0], counters) + tuple(out[2:])

    return _kernel_fault(alter)


def no_merge():
    from repro.core import fleet

    return _patched(fleet, "pod_merge", lambda orig: (
        lambda cfg, f, *args, **kw: f))


def _kernel_fault(change):
    from repro.kernels import ops

    def make(orig):
        def queue_advance(*args):
            return change(orig(*args), args)
        return queue_advance

    return _patched(ops, "queue_advance", make)


FAULTS = {
    "train": {"unchanged_state": unchanged_state,
              "half_batch": half_batch,
              "altered_answer": altered_answer,
              "no_merge": no_merge},
}
