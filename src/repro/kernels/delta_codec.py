"""Pallas fused delta-codec kernel — the FL transport hot path.

One grid step per agent runs that agent's whole error-feedback encode/decode
chain in a single kernel: the flat parameter delta and the carried residual
are pulled into VMEM once, the error-compensated delta ``xf = delta + r`` is
encoded (per-tensor int8 round trip or exact top-k sparsification, jit-static
choice) and decoded in place, and the new residual ``xf - decoded`` is
written back — one load and one store of the agent's 2·L-word codec state
per FL round instead of separate quantize/dequantize/residual passes. A
fleet of A agents is one kernel call over grid (A,).

The per-coordinate math is imported from ``repro.kernels.ref``
(``delta_codec_step`` — the same function the jnp oracle ``delta_codec_ref``
calls), so kernel and oracle agree bit-for-bit (equivalence-tested in
tests/test_fl.py, including under ``vmap``). On CPU the kernel executes
with ``interpret=True`` (same body, XLA-CPU execution); on TPU all three
codecs compile to Mosaic (tests/test_tpu_compile.py) — topk's exact-k
selection is a bitwise binary search over the float bit patterns
(``ref.topk_mask``), not a sort, which Mosaic lacks. Each agent's
(L,) vector travels as a (1, 1, L) block of an (A, 1, L) view: a block's
last two dims must equal the array's (or tile (8, 128)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as kref


def _codec_kernel(delta_ref, res_ref, o_dec, o_res, *, codec, k):
    xf = delta_ref[0, 0] + res_ref[0, 0]
    dec, new_res = kref.delta_codec_step(xf, codec=codec, k=k)
    o_dec[0, 0] = dec
    o_res[0, 0] = new_res


def delta_codec(delta, residual, *, codec: str, k: int = 1, interpret=False):
    """Fused error-feedback encode/decode over the agent axis.

    delta, residual: (A, L) float32 flat per-agent parameter deltas [or
    unbatched (L,) — a singleton agent axis is added and squeezed]. ``codec``
    in ``ref.DELTA_CODECS`` and ``k`` (top-k budget) are jit-static. Returns
    (decoded, new_residual), identical to ``vmap(ref.delta_codec_ref)``."""
    if codec not in kref.DELTA_CODECS:
        raise ValueError(f"unknown codec {codec!r}; expected one of "
                         f"{kref.DELTA_CODECS}")
    unbatched = delta.ndim == 1
    if unbatched:
        delta, residual = delta[None], residual[None]
    a, l = delta.shape
    f32 = jnp.float32

    kernel = functools.partial(_codec_kernel, codec=codec, k=k)
    spec = pl.BlockSpec((1, 1, l), lambda a_: (a_, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(a,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((a, 1, l), f32)] * 2,
        interpret=interpret,
    )(delta.astype(f32).reshape(a, 1, l), residual.astype(f32).reshape(a, 1, l))
    out = [x.reshape(a, l) for x in out]

    if unbatched:
        out = jax.tree.map(lambda x: x[0], out)
    return tuple(out)
