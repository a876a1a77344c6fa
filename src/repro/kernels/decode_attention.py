"""Pallas TPU decode attention: one query token vs. a long KV cache.

This is the memory-bound hot spot of the ``decode_32k`` / ``long_500k``
shapes: arithmetic intensity ≈ 1 FLOP/byte, so the kernel is designed so the
ONLY HBM traffic is one streaming pass over the (valid prefix of the) cache.

Grid: (batch, kv_heads, kv_blocks). Each step loads a (bk, D) k/v tile and
the (group, D) query-head group that shares this kv head, updating the
online-softmax state in VMEM scratch. Blocks entirely beyond ``kv_len`` are
skipped with ``pl.when`` (no wasted bandwidth on the invalid cache tail —
this is what makes the 512k-cache cell stream only ``kv_len`` bytes).

The valid length arrives via scalar prefetch (PrefetchScalarGridSpec) so the
skip decision is available before the DMA is issued.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, bk, n_kv):
    ik = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ik * bk < kv_len)
    def _work():
        q = q_ref[0, 0].astype(jnp.float32)       # (group, D)
        k = k_ref[0, :, 0].astype(jnp.float32)    # (bk, D)
        v = v_ref[0, :, 0].astype(jnp.float32)    # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < kv_len
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def decode_attention_bhd(q, k_cache, v_cache, kv_len, *, bk=512,
                         interpret=False):
    """q: (B, Hq, D); caches: (B, S_max, Hkv, D); kv_len scalar int32.

    Returns (B, Hq, D)."""
    b, hq, d = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    bk = min(bk, s_max)
    assert s_max % bk == 0
    nk = s_max // bk
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, group, d)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, group, d),
                             lambda b_, h, ik, len_ref: (b_, h, 0, 0)),
                pl.BlockSpec((1, bk, 1, d),
                             lambda b_, h, ik, len_ref: (b_, ik, h, 0)),
                pl.BlockSpec((1, bk, 1, d),
                             lambda b_, h, ik, len_ref: (b_, ik, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d),
                                   lambda b_, h, ik, len_ref: (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), qg, k_cache, v_cache)
    return out.reshape(b, hq, d)


def decode_attention(q, k_cache, v_cache, kv_len, *, bk=512, interpret=False):
    """Model-layout adapter: q (B, 1, Hq, D) -> (B, 1, Hq, D)."""
    out = decode_attention_bhd(q[:, 0], k_cache, v_cache, kv_len, bk=bk,
                               interpret=interpret)
    return out[:, None]
