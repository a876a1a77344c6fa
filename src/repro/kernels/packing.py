"""Pallas TPU token/frame packing kernel — the paper's RES action data path.

Frame packing combines small inputs into one fixed compiled shape (§II-B
"Resolution Adjustments"); for the LM data plane that is a gather of
variable-length request segments into a padded bucket. The index vector
arrives via scalar prefetch, so each grid step's input block index is
computed *before* its DMA — the gather happens at the BlockSpec level (one
HBM->VMEM row copy per step), not as an in-kernel load loop.

Rows with index < 0 are padding: the copy is skipped under ``pl.when`` and
the slot is zeroed, so a bucket's cost scales with its *real* payload.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(idx_ref, tok_ref, o_ref):
    i = pl.program_id(0)
    idx = idx_ref[i]

    @pl.when(idx >= 0)
    def _copy():
        o_ref[...] = tok_ref[...]

    @pl.when(idx < 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


def pack(tokens, indices, *, interpret=False):
    """tokens: (T, D); indices: (N,) int32, negative = padding.

    Returns (N, D) with out[i] = tokens[indices[i]] (0 for padding). Rows
    travel as (1, 1, D) blocks of a (T, 1, D) view, whose last two dims
    equal the array's, as Mosaic needs."""
    t, d = tokens.shape
    n = indices.shape[0]
    out = pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda i, idx_ref: (
                    jnp.maximum(idx_ref[i], 0), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), tokens.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), tokens.reshape(t, 1, d))
    return out.reshape(n, d)
