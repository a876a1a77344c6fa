"""Pallas fused diversity-insert kernel — the CRL buffer hot path (Eq. 6).

One grid step per agent ingests a whole episode of T candidate experiences
into that agent's diversity buffer: score from the streaming moments ->
argmin-evict slot choice -> scatter + rank-1 moment update, fused into a
single kernel so the per-candidate sequential chain never leaves on-chip
memory. The buffer slots (N, D), the moments, and the T candidates all live
in VMEM for the duration of the episode — the only HBM traffic is one load
and one store of the agent's buffer state (≈ N·(D+NA) floats) per episode
instead of T round trips.

The scoring math is imported from ``repro.kernels.ref`` — the same unrolled
LAPACK-free Cholesky the jnp oracle uses, in its ``masked`` form (selects
instead of scatters, which Mosaic lacks) — so kernel and oracle agree to
float32 roundoff (equivalence-tested in tests/test_buffer.py). On CPU the
kernel executes with ``interpret=True`` (same body, XLA-CPU execution); on
TPU the same call site compiles to Mosaic (tests/test_tpu_compile.py).

Booleans cross the kernel boundary as int32 (0/1) masks — TPU vector memory
has no i1 lanes; the ops wrapper converts at the edges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as kref


def _diversity_kernel(states_ref, probs_ref, score_ref, filled_ref, ssum_ref,
                      souter_ref, psum_ref, nfill_ref, cs_ref, cp_ref,
                      o_states, o_probs, o_score, o_filled, o_ssum, o_souter,
                      o_psum, o_nfill, o_slot, o_do, o_d,
                      *, alpha, beta, ridge, t_steps):
    # Seed the in-place slot state once; the candidate loop mutates it.
    o_states[...] = states_ref[...]
    o_probs[...] = probs_ref[...]
    n = score_ref.shape[-1]
    slots = kref._iota(n)
    steps = kref._iota(t_steps)

    # Per-slot and per-step metadata rides the loop as (1, n) rows and is
    # updated by masked selects: Mosaic can neither index lanes at a
    # dynamic offset nor carry a 1-D vector through a loop.
    def body(t, carry):
        s_sum, s_outer, p_sum, n_filled, score, filled, slot, do_t, d_t = \
            carry
        s_row = cs_ref[0, pl.ds(t, 1), :]           # (1, D)
        p_row = cp_ref[0, pl.ds(t, 1), :]           # (1, NA)
        s, p = s_row[0], p_row[0]                   # (D,), (NA,)
        score = score[0]                             # (N,)

        d = kref.diversity_score_from_moments(
            s, p, s_sum[0], s_outer, p_sum[0], n_filled[0, 0],
            alpha=alpha, beta=beta, ridge=ridge, masked=True)

        # Score invariant (see diversity_insert_ref): empty slots hold -inf,
        # so one argmin picks first-empty-else-min-filled and d > min(score)
        # is the insert test in both regimes.
        minval = jnp.min(score)
        idx = jnp.argmin(score).astype(jnp.int32)
        do = d > minval
        evict = do & (minval != -jnp.inf)

        old_s_row = o_states[0, pl.ds(idx, 1), :]
        old_p_row = o_probs[0, pl.ds(idx, 1), :]
        old_s = old_s_row[0]
        add = do.astype(s_sum.dtype)
        sub = evict.astype(s_sum.dtype)

        @pl.when(do)
        def _scatter():
            o_states[0, pl.ds(idx, 1), :] = s_row
            o_probs[0, pl.ds(idx, 1), :] = p_row

        hit = do & (slots == idx)
        at_t = steps == t
        return (
            s_sum + add * s_row - sub * old_s_row,
            s_outer + add * jnp.outer(s, s) - sub * jnp.outer(old_s, old_s),
            p_sum + add * p_row - sub * old_p_row,
            n_filled + do.astype(n_filled.dtype)
            - evict.astype(n_filled.dtype),
            jnp.where(hit, d, score)[None],
            jnp.where(hit, 1, filled[0])[None],
            jnp.where(at_t, idx, slot[0])[None],
            jnp.where(at_t, do.astype(jnp.int32), do_t[0])[None],
            jnp.where(at_t, d, d_t[0])[None],
        )

    zeros = lambda dt: jnp.zeros((1, t_steps), dt)
    init = (ssum_ref[0], souter_ref[0], psum_ref[0], nfill_ref[0],
            score_ref[0], filled_ref[0], zeros(jnp.int32), zeros(jnp.int32),
            zeros(jnp.float32))
    outs = (o_ssum, o_souter, o_psum, o_nfill, o_score, o_filled, o_slot,
            o_do, o_d)
    for o, v in zip(outs, jax.lax.fori_loop(0, t_steps, body, init)):
        o[0] = v


def diversity_insert(states, probs, score, filled, s_sum, s_outer, p_sum,
                     n_filled, cand_states, cand_probs, *, alpha, beta,
                     ridge=0.1, interpret=False):
    """Fused batch insert over the agent axis.

    states: (A, N, D) [or unbatched (N, D) — a singleton agent axis is added
    and squeezed]; cand_states: (A, T, D); filled: bool. Returns the same
    tuple as ``ref.diversity_insert_ref`` batched over A: updated
    (states, probs, score, filled, s_sum, s_outer, p_sum, n_filled) plus the
    per-candidate decision trace (slot, do_insert, d)."""
    unbatched = states.ndim == 2
    if unbatched:
        (states, probs, score, filled, s_sum, s_outer, p_sum, n_filled,
         cand_states, cand_probs) = jax.tree.map(
            lambda x: x[None], (states, probs, score, filled, s_sum, s_outer,
                                p_sum, n_filled, cand_states, cand_probs))
    a, n, dim = states.shape
    t_steps, na = cand_probs.shape[1], cand_probs.shape[2]
    f32, i32 = jnp.float32, jnp.int32

    kernel = functools.partial(_diversity_kernel, alpha=alpha, beta=beta,
                               ridge=ridge, t_steps=t_steps)
    # per-agent leaves are (A, rows, width) blocks of (1, rows, width); 1-D
    # ones are viewed as (A, 1, width) so the block's last two dims equal
    # the array's, which Mosaic needs
    shapes = ((n, dim), (n, na), (1, n), (1, n), (1, dim), (dim, dim),
              (1, na), (1, 1), (t_steps, dim), (t_steps, na), (1, t_steps),
              (1, t_steps), (1, t_steps))
    viewed = (False, False, True, True, True, False, True, True, False,
              False, True, True, True)
    dtypes = (f32, f32, f32, i32, f32, f32, f32, i32, f32, f32, i32, i32,
              f32)
    specs = [pl.BlockSpec((1,) + sh, lambda a_: (a_, 0, 0)) for sh in shapes]
    out_ids = (0, 1, 2, 3, 4, 5, 6, 7, 10, 11, 12)
    args = (states, probs, score, filled, s_sum, s_outer, p_sum, n_filled,
            cand_states, cand_probs)
    out = pl.pallas_call(
        kernel,
        grid=(a,),
        in_specs=specs[:10],
        out_specs=[specs[i] for i in out_ids],
        out_shape=[jax.ShapeDtypeStruct((a,) + shapes[i], dtypes[i])
                   for i in out_ids],
        interpret=interpret,
    )(*(x.astype(dt).reshape((a,) + sh)
        for x, dt, sh in zip(args, dtypes, shapes)))
    # back to the caller's layout: drop the singleton row of 1-D leaves
    out = [x[:, 0] if viewed[i] else x for x, i in zip(out, out_ids)]
    out[7] = out[7][:, 0]

    (n_states, n_probs, n_score, n_filled_i, n_ssum, n_souter, n_psum,
     n_nfill, slot, do, d) = out
    result = (n_states, n_probs, n_score, n_filled_i.astype(bool), n_ssum,
              n_souter, n_psum, n_nfill, slot, do.astype(bool), d)
    if unbatched:
        result = jax.tree.map(lambda x: x[0], result)
    return result
