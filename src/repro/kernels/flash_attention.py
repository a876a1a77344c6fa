"""Pallas TPU flash attention (prefill / training), causal + GQA.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv axis innermost so the
(m, l, acc) online-softmax state lives in VMEM scratch across kv iterations
(the classic TPU revisiting pattern — the output block index is independent
of the kv grid index).

BlockSpecs pull (bq, D) query tiles and (bk, D) key/value tiles into VMEM;
with bq = bk = 128 and D ∈ {64, 80, 128, 192, 256} both matmuls hit the MXU
with 128-aligned contraction/output dims. VMEM footprint per step ≈
(bq·D + 2·bk·D + bq·bk + 2·bq·D) · 4 B ≈ 0.6 MB at D=128 — well inside the
~16 MB/core budget, leaving room for double buffering.

GQA is handled in the k/v index_map (query head h reads kv head
h // group) — no repeated kv materialization in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, bq, bk, n_kv):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = qpos >= kpos
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)[:, None]           # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                        # masked lanes: exp(NEG_INF - m) ≈ 0
    if causal:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)[:, None]
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, bq=128, bk=128,
                         interpret=False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_kv=nk)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128, interpret=False):
    """Layout adapter: q (B, Sq, Hq, D), k/v (B, Sk, Hkv, D) — model layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)
    return out.transpose(0, 2, 1, 3)
