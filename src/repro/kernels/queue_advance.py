"""Pallas fused queue-advance kernel — the digital-twin data-plane hot path.

One grid step per agent advances that agent's request-level pipeline state
(admit -> pre-process -> batch-form -> inference service -> post-process ->
deadline check) K microticks in a single kernel: the arrival ring, the stage
counters, the service credits, and the latency histogram all stay in VMEM
for the whole control interval, so the only HBM traffic is one load and one
store of the agent's ~(R + H + 20)-word state per K ticks instead of K round
trips. A fleet of A agents is one kernel call over grid (A,).

The per-tick math is imported from ``repro.kernels.ref.sim_microtick`` — the
same function the jnp oracle (``queue_advance_ref``) scans — so kernel and
oracle agree bit-for-bit (equivalence-tested in tests/test_sim.py, including
under ``vmap``). On CPU the kernel executes with ``interpret=True`` (same
body, XLA-CPU execution); on TPU the same call site compiles to Mosaic
(tests/test_tpu_compile.py), each per-agent leaf travelling as a
(1, 1, n) block of an (A, 1, n) view.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as kref


def _queue_kernel(arrive_ref, counters_ref, credits_ref, latsum_ref,
                  hist_ref, arrivals_ref, caps_ref,
                  o_arrive, o_counters, o_credits, o_latsum, o_hist,
                  *, k_ticks):
    caps = caps_ref[0, 0]
    arrivals = arrivals_ref[0, 0]
    tick_ids = kref._iota(k_ticks)

    # The loop carries each leaf as a (1, n) row and the tick works on the
    # (n,) vector: Mosaic cannot carry a 1-D vector through a loop whose
    # body rewrites it.
    def tick(t, rows):
        # a masked sum, not arrivals[t]: Mosaic cannot slice lanes at a
        # dynamic offset
        n_arr = jnp.sum(jnp.where(tick_ids == t, arrivals, 0))
        out = kref.sim_microtick(*(r[0] for r in rows), n_arr, caps)
        return tuple(x[None] for x in out)

    refs = (arrive_ref, counters_ref, credits_ref, latsum_ref, hist_ref)
    outs = (o_arrive, o_counters, o_credits, o_latsum, o_hist)
    rows = jax.lax.fori_loop(0, k_ticks, tick, tuple(r[0] for r in refs))
    for o, row in zip(outs, rows):
        o[0] = row


def queue_advance(arrive, counters, credits, lat_sum, hist, arrivals, caps,
                  *, interpret=False):
    """Fused K-microtick advance over the agent axis.

    arrive: (A, R) int32 [or unbatched (R,) — a singleton agent axis is
    added and squeezed]; counters: (A, SIM_NCOUNTERS) int32; credits: (A, 2)
    float32; lat_sum: (A,) float32; hist: (A, H) int32; arrivals: (A, K)
    int32; caps: (A, SIM_NCAPS) float32. Returns the updated state tuple
    (arrive, counters, credits, lat_sum, hist), identical to
    ``vmap(ref.queue_advance_ref)``."""
    unbatched = arrive.ndim == 1
    if unbatched:
        (arrive, counters, credits, lat_sum, hist, arrivals, caps) = \
            jax.tree.map(lambda x: x[None],
                         (arrive, counters, credits, lat_sum, hist,
                          arrivals, caps))
    a, ring = arrive.shape
    assert ring > 0 and ring & (ring - 1) == 0, \
        "ring capacity must be a positive power of two"
    k_ticks, hist_n = arrivals.shape[1], hist.shape[1]
    f32, i32 = jnp.float32, jnp.int32

    kernel = functools.partial(_queue_kernel, k_ticks=k_ticks)
    # every per-agent leaf is viewed as (A, 1, n) and blocked (1, 1, n):
    # the block's last two dims then equal the array's, which Mosaic needs
    widths = (ring, kref.SIM_NCOUNTERS, 2, 1, hist_n, k_ticks,
              kref.SIM_NCAPS)
    dtypes = (i32, i32, f32, f32, i32, i32, f32)
    specs = [pl.BlockSpec((1, 1, w), lambda a_: (a_, 0, 0)) for w in widths]
    out = pl.pallas_call(
        kernel,
        grid=(a,),
        in_specs=specs,
        out_specs=specs[:5],
        out_shape=[jax.ShapeDtypeStruct((a, 1, w), d)
                   for w, d in zip(widths[:5], dtypes[:5])],
        interpret=interpret,
    )(*(x.astype(d).reshape(a, 1, w) for x, d, w in zip(
        (arrive, counters, credits, lat_sum, hist, arrivals, caps),
        dtypes, widths)))
    out = [x.reshape(x.shape[0], -1) for x in out]
    out[3] = out[3][:, 0]

    if unbatched:
        out = jax.tree.map(lambda x: x[0], out)
    return tuple(out)
