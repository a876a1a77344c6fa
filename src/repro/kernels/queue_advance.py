"""Pallas fused queue-advance kernel — the digital-twin data-plane hot path.

One grid step advances a block of agents together through the K microticks
of a control interval (admit -> pre-process -> batch-form -> inference
service -> post-process -> deadline check). Operands keep their ``(A, n)``
layout in HBM and travel as ``(A_blk, n)`` blocks: agents on sublanes, the
ring, histogram and tick axes on lanes. Inside the kernel every per-agent
scalar (the counters, the two credits, ``lat_sum``, the caps, the tick's
arrivals) is an ``(A_blk, 1)`` column and the rings an ``(A_blk, R)`` tile,
so each tick is one chain of vector ops for the whole block, and the only
HBM traffic is one load and one store of the block's state per K ticks.

The block is the whole agent axis when its operands fit ``_VMEM_BUDGET``,
else the fewest blocks that do, each a multiple of 8 agents (the last one
padded). The per-tick math is ``repro.kernels.ref.sim_microtick_rows`` —
the same function the jnp oracle (``queue_advance_ref``) scans — so kernel
and oracle agree bit for bit (tests/test_sim.py). Only the histogram is
binned differently: its counts are integers, so the kernel keeps each
ring slot's latency bucket from the tick it completed and bins them once
per call (and before a slot completes a second time within one call,
which needs R completions).

On CPU the kernel executes with ``interpret=True`` (same body, XLA-CPU
execution); on TPU the same call site compiles to Mosaic
(tests/test_tpu_compile.py). ``kernels.ops.queue_advance`` carries the
batching rule that brings one agent's call under the fleet's ``vmap`` to
one call of this kernel over the whole batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as kref

# bytes of one block's operands (inputs and outputs, before Pallas doubles
# them for pipelining); sets how many agents one grid step takes
_VMEM_BUDGET = 1 << 20


def agent_block(n_agents, ring, hist_n, k_ticks, rows_per_agent=1):
    """Agents per grid step: all of them when their operands fit
    ``_VMEM_BUDGET``, else the fewest near-equal blocks that fit, rounded
    up to a multiple of 8 (Mosaic's sublane tile). ``rows_per_agent``:
    the rows each takes (a pipeline's camera: one per stage)."""
    words = (2 * (ring + kref.SIM_NCOUNTERS + 2 + 1 + hist_n) + k_ticks
             + kref.SIM_NCAPS) * rows_per_agent
    most = max(8, _VMEM_BUDGET // (4 * words) // 8 * 8)
    if n_agents <= most:
        return n_agents
    n_blocks = pl.cdiv(n_agents, most)
    return pl.cdiv(pl.cdiv(n_agents, n_blocks), 8) * 8


def _columns(ref):
    return tuple(ref[:, j:j + 1] for j in range(ref.shape[1]))


def _tile(cols):
    """(B, 1) columns -> (B, n) tile, by selects (no lane concatenate)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], len(cols)),
                                    1)
    out = jnp.zeros(lane.shape, cols[0].dtype)
    for j, col in enumerate(cols):
        out = jnp.where(lane == j, col, out)
    return out


def _bin(hist, bucket):
    """hist (B, H) plus the count of each bucket 0..H-1 in bucket (B, R)
    (the sentinel H counts nowhere)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, hist.shape, 1)

    def one(h, hist):
        n = jnp.sum(jnp.where(bucket == h, 1, 0), axis=1, keepdims=True)
        return jnp.where(lane == h, hist + n, hist)

    return jax.lax.fori_loop(0, hist.shape[1], one, hist)


def _queue_kernel(arrive_ref, counters_ref, credits_ref, latsum_ref,
                  hist_ref, arrivals_ref, caps_ref,
                  o_arrive, o_counters, o_credits, o_latsum, o_hist):
    hist_n = hist_ref.shape[1]
    caps = _columns(caps_ref)
    arrivals = arrivals_ref[...]
    tick_ids = jax.lax.broadcasted_iota(jnp.int32, arrivals.shape, 1)
    none = jnp.full(arrive_ref.shape, hist_n, jnp.int32)

    def tick(t, carry):
        arrive, c, credits, lat_sum, hist, slots = carry
        # a masked sum, not arrivals[:, t]: Mosaic cannot slice lanes at a
        # dynamic offset
        n_arr = jnp.sum(jnp.where(tick_ids == t, arrivals, 0), axis=1,
                        keepdims=True)
        arrive, c, credits, lat_sum, bucket = kref.sim_microtick_rows(
            arrive, c, credits, lat_sum, n_arr, caps, hist_n)
        done = bucket < hist_n
        # a slot completing twice in one call: bin what the block holds
        # first (counts add in any order, so this changes no count)
        again = jnp.max(jnp.where(done & (slots < hist_n), 1, 0)) > 0
        hist, slots = jax.lax.cond(again, lambda: (_bin(hist, slots), none),
                                   lambda: (hist, slots))
        slots = jnp.where(done, bucket, slots)
        return arrive, c, credits, lat_sum, hist, slots

    carry = (arrive_ref[...], _columns(counters_ref), _columns(credits_ref),
             latsum_ref[...], hist_ref[...], none)
    arrive, c, credits, lat_sum, hist, slots = jax.lax.fori_loop(
        0, arrivals.shape[1], tick, carry)
    o_arrive[...] = arrive
    o_counters[...] = _tile(c)
    o_credits[...] = _tile(credits)
    o_latsum[...] = lat_sum
    o_hist[...] = _bin(hist, slots)


def queue_advance(arrive, counters, credits, lat_sum, hist, arrivals, caps,
                  *, interpret=False):
    """Fused K-microtick advance over the agent axis.

    arrive: (..., R) int32; counters: (..., SIM_NCOUNTERS) int32; credits:
    (..., 2) float32; lat_sum: (...) float32; hist: (..., H) int32;
    arrivals: (..., K) int32; caps: (..., SIM_NCAPS) float32. The leading
    dims (none for one agent) are the agents, flattened for the call.
    Returns the updated state tuple (arrive, counters, credits, lat_sum,
    hist), identical to ``ref.queue_advance_ref`` vmapped over them."""
    lead = lat_sum.shape
    ring = arrive.shape[-1]
    assert ring > 0 and ring & (ring - 1) == 0, \
        "ring capacity must be a positive power of two"
    k_ticks, hist_n = arrivals.shape[-1], hist.shape[-1]
    f32, i32 = jnp.float32, jnp.int32
    # lat_sum travels as an (A, 1) column, like every per-agent scalar
    widths = (ring, kref.SIM_NCOUNTERS, 2, 1, hist_n, k_ticks,
              kref.SIM_NCAPS)
    dtypes = (i32, i32, f32, f32, i32, i32, f32)
    ops = [x.astype(d).reshape(-1, w) for x, d, w in zip(
        (arrive, counters, credits, lat_sum, hist, arrivals, caps),
        dtypes, widths)]
    a = ops[0].shape[0]
    blk = agent_block(a, ring, hist_n, k_ticks)
    specs = [pl.BlockSpec((blk, w), lambda i: (i, 0)) for w in widths]
    out = pl.pallas_call(
        _queue_kernel,
        grid=(pl.cdiv(a, blk),),
        in_specs=specs,
        out_specs=specs[:5],
        out_shape=[jax.ShapeDtypeStruct((a, w), d)
                   for w, d in zip(widths[:5], dtypes[:5])],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*ops)
    out = [x.reshape(lead + x.shape[1:]) for x in out]
    out[3] = out[3][..., 0]
    return tuple(out)


def _pipeline_kernel(arrive_ref, counters_ref, credits_ref, latsum_ref,
                     hist_ref, arrivals_ref, caps_ref, fan_ref,
                     o_arrive, o_counters, o_credits, o_latsum, o_hist,
                     o_phase):
    stages, blk = arrive_ref.shape[:2]
    rows = stages * blk
    flat = lambda ref: ref[...].reshape(rows, ref.shape[2])
    hist_n = hist_ref.shape[2]
    caps = tuple(flat(caps_ref)[:, j:j + 1] for j in range(kref.SIM_NCAPS))
    arrivals = flat(arrivals_ref)
    fan = flat(fan_ref)
    objects, phase = fan[:, 0:1], fan[:, 1:2]
    tick_ids = jax.lax.broadcasted_iota(jnp.int32, arrivals.shape, 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < blk
    none = jnp.full((rows, arrive_ref.shape[2]), hist_n, jnp.int32)

    def tick(t, carry):
        arrive, c, credits, lat_sum, hist, slots, sent, floor_prev = carry
        c, credits, lat_sum, bucket, n_post = kref.sim_serve_rows(
            arrive, c, credits, lat_sum, caps, hist_n)
        # a later stage's row takes the completions of the row one stage
        # up (the same camera), fanned out, in the same tick
        sent = sent + jnp.where(first, 0, jnp.roll(n_post, blk, axis=0))
        floors = jnp.floor(phase + objects * sent.astype(jnp.float32))
        n_arr = jnp.where(
            first, jnp.sum(jnp.where(tick_ids == t, arrivals, 0), axis=1,
                           keepdims=True),
            (floors - floor_prev).astype(jnp.int32))
        arrive, c = kref.sim_admit_rows(arrive, c, n_arr, caps)
        done = bucket < hist_n
        again = jnp.max(jnp.where(done & (slots < hist_n), 1, 0)) > 0
        hist, slots = jax.lax.cond(again, lambda: (_bin(hist, slots), none),
                                   lambda: (hist, slots))
        slots = jnp.where(done, bucket, slots)
        return arrive, c, credits, lat_sum, hist, slots, sent, floors

    col = lambda v: tuple(v[:, j:j + 1] for j in range(v.shape[1]))
    carry = (flat(arrive_ref), col(flat(counters_ref)),
             col(flat(credits_ref)), flat(latsum_ref), flat(hist_ref), none,
             jnp.zeros((rows, 1), jnp.int32), jnp.floor(phase))
    arrive, c, credits, lat_sum, hist, slots, sent, _ = jax.lax.fori_loop(
        0, arrivals.shape[1], tick, carry)
    cum = phase + objects * sent.astype(jnp.float32)
    back = lambda v, ref: v.reshape(ref.shape)
    o_arrive[...] = back(arrive, o_arrive)
    o_counters[...] = back(_tile(c), o_counters)
    o_credits[...] = back(_tile(credits), o_credits)
    o_latsum[...] = back(lat_sum, o_latsum)
    o_hist[...] = back(_bin(hist, slots), o_hist)
    o_phase[...] = back(jnp.where(first, phase, cum - jnp.floor(cum)),
                        o_phase)


def pipeline_advance(arrive, counters, credits, lat_sum, hist, arrivals,
                     caps, objects, phase, *, stages, interpret=False):
    """Every stage of a pipeline fleet through one control interval in ONE
    call: per microtick, every agent's service steps, then each later
    stage's fan-out of the completions one stage up (``objects`` per
    completion, ``phase`` carried), then every agent's admission — the
    arrivals of ``fanout_arrivals``, entering in the tick they were sent.

    Operands lead with the A = stages x cameras agents, stage-major, as in
    ``queue_advance``; ``arrivals`` are stage 0's (the other rows' are
    ignored); objects, phase: (A,) float32 (stage 0's phase passes through
    unchanged). Returns (arrive, counters, credits, lat_sum, hist, phase).
    A grid step takes whole cameras: the block of cameras of every
    stage."""
    a, ring = arrive.shape
    cams = a // stages
    k_ticks, hist_n = arrivals.shape[-1], hist.shape[-1]
    f32, i32 = jnp.float32, jnp.int32
    widths = (ring, kref.SIM_NCOUNTERS, 2, 1, hist_n, k_ticks,
              kref.SIM_NCAPS, 2)
    dtypes = (i32, i32, f32, f32, i32, i32, f32, f32)
    ops = [x.astype(d).reshape(stages, cams, w) for x, d, w in zip(
        (arrive, counters, credits, lat_sum, hist, arrivals, caps,
         jnp.stack([objects, phase], axis=-1)), dtypes, widths)]
    # a camera is a row per stage; the objects, phase in and phase out are
    # three words more than a single model's operands
    blk = agent_block(cams, ring, hist_n, k_ticks + 3, rows_per_agent=stages)
    specs = [pl.BlockSpec((stages, blk, w), lambda i: (0, i, 0))
             for w in widths]
    out_widths = widths[:5] + (1,)
    out = pl.pallas_call(
        _pipeline_kernel,
        grid=(pl.cdiv(cams, blk),),
        in_specs=specs,
        out_specs=specs[:5] + [pl.BlockSpec((stages, blk, 1),
                                            lambda i: (0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((stages, cams, w), d)
                   for w, d in zip(out_widths, dtypes[:5] + (f32,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*ops)
    out = [x.reshape(a, x.shape[2]) for x in out]
    out[3] = out[3][:, 0]
    out[5] = out[5][:, 0]
    return tuple(out)
