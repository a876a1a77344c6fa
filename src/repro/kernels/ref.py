"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, causal=True):
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D). Returns (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(k.shape[1])[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, kv_len):
    """q: (B, 1, Hq, D); caches: (B, S_max, Hkv, D); kv_len: () or (B,).

    Single-query attention over the valid prefix of the cache."""
    b, _, hq, d = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    k = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    v = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    valid = jnp.arange(s_max)[None, :] < jnp.asarray(kv_len).reshape(-1, 1)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def pack_ref(tokens, indices):
    """tokens: (T, D); indices: (N,) int32 (negative = padding slot -> 0).

    The frame/token-packing gather: out[i] = tokens[indices[i]] or 0."""
    safe = jnp.clip(indices, 0, tokens.shape[0] - 1)
    out = tokens[safe]
    return jnp.where((indices >= 0)[:, None], out, 0).astype(tokens.dtype)


# ---------------------------------------------------------------------------
# Streaming-moment diversity insert (Eq. 6 engine) — shared math + jnp oracle
# ---------------------------------------------------------------------------
# The helpers below are the single source of truth for the streaming buffer
# math: the jnp batch path (``diversity_insert_ref``), the single-insert path
# in ``repro.core.buffer``, and the Pallas kernel body all call them, so the
# three implementations cannot drift. Everything is unrolled over the static
# state dimension D (= 8), which keeps the math LAPACK-free: it compiles to a
# fixed chain of vector ops that is legal inside jit, vmap, lax.scan, and a
# Pallas kernel alike (``jnp.linalg`` custom calls are none of those).

def chol_small(cov, eps=1e-12, *, masked=False):
    """Cholesky factor of a small static-D SPD matrix, unrolled over D.

    ``masked`` writes each entry by a select over the whole tile instead of
    an indexed update, for Mosaic, which has no scatter; the arithmetic of
    every entry is the same."""
    d = cov.shape[0]
    l = jnp.zeros_like(cov)
    if masked:
        rows = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    for j in range(d):
        acc = jnp.sum(l[j, :j] * l[j, :j]) if j else 0.0
        ljj = jnp.sqrt(jnp.maximum(cov[j, j] - acc, eps))
        if not masked:
            l = l.at[j, j].set(ljj)
            if j + 1 < d:
                dots = (jnp.sum(l[j + 1:, :j] * l[j, :j][None, :], -1)
                        if j else 0.0)
                l = l.at[j + 1:, j].set((cov[j + 1:, j] - dots) / ljj)
            continue
        l = jnp.where((rows == j) & (cols == j), ljj, l)
        if j + 1 < d:
            dots = jnp.sum(l[:, :j] * l[j, :j][None, :], -1) if j else 0.0
            col = (cov[:, j] - dots) / ljj
            l = jnp.where((rows > j) & (cols == j), col[:, None], l)
    return l


def tri_solve_small(l, b, *, masked=False):
    """Solve L y = b (L lower-triangular) by unrolled forward substitution.

    ``masked`` (for Mosaic, as in ``chol_small``) keeps y as a (1, D) row
    and reads L through masked tile sums instead of row slices; the sums
    then add in another order, so it agrees with the indexed path to
    float32 roundoff, not bit for bit."""
    d = l.shape[0]
    if masked:
        rows = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        y = jnp.zeros((1, d), b.dtype)
        for i in range(d):
            acc = jnp.sum(jnp.where((rows == i) & (cols < i), l * y, 0.0))
            lii = jnp.sum(jnp.where((rows == i) & (cols == i), l, 0.0))
            y = jnp.where(cols[:1] == i, (b[i] - acc) / lii, y)
        return y[0]
    y = jnp.zeros_like(b)
    for i in range(d):
        acc = jnp.sum(l[i, :i] * y[:i]) if i else 0.0
        y = y.at[i].set((b[i] - acc) / l[i, i])
    return y


def diversity_score_from_moments(state, probs, s_sum, s_outer, p_sum,
                                 n_filled, *, alpha, beta, ridge=0.1,
                                 eps=1e-8, masked=False):
    """Eq. 6 score of one candidate from running sufficient statistics only.

    Mahalanobis: cov = E[ssᵀ] − μμᵀ + ridge·I from (s_sum, s_outer), then
    d_M² = ‖L⁻¹(s−μ)‖² with L the Cholesky factor — O(D²) and never touches
    the N stored slots. KL uses the running probs sum the same way.
    Mathematically identical to the recompute-everything oracle
    (``repro.core.buffer.diversity``). ``masked``: the Mosaic-legal form of
    the small solves (``chol_small``), for the Pallas kernel body."""
    dim = state.shape[-1]
    n = jnp.maximum(n_filled.astype(jnp.float32), 1.0)
    mu = s_sum / n
    cov = (s_outer / n - jnp.outer(mu, mu)
           + ridge * jnp.eye(dim, dtype=s_sum.dtype))
    y = tri_solve_small(chol_small(cov, masked=masked), state - mu,
                        masked=masked)
    d_m = jnp.sqrt(jnp.maximum(jnp.sum(y * y), 0.0))
    mean_p = jnp.where(n_filled > 0, p_sum / n, probs)
    pc = jnp.clip(probs, eps, 1.0)
    qc = jnp.clip(mean_p, eps, 1.0)
    d_kl = jnp.sum(pc * jnp.log(pc / qc))
    return alpha * d_m + beta * d_kl


def diversity_insert_step(states, probs, score, filled, s_sum, s_outer,
                          p_sum, n_filled, cand_state, cand_probs, *,
                          alpha, beta, ridge=0.1):
    """One streaming insert: score -> slot choice -> rank-1 moment update.

    Eviction semantics match the recompute oracle exactly: first empty slot
    if any, else the min-score filled slot iff the candidate scores higher.
    On insert the moments gain the candidate's rank-1 contribution; on
    eviction of a filled slot they lose the old occupant's.

    Returns ((states, probs, score, filled, s_sum, s_outer, p_sum,
    n_filled), (slot, do_insert, score_of_candidate))."""
    d = diversity_score_from_moments(cand_state, cand_probs, s_sum, s_outer,
                                     p_sum, n_filled, alpha=alpha, beta=beta,
                                     ridge=ridge)
    has_empty = ~jnp.all(filled)
    empty_idx = jnp.argmin(filled)                # first unfilled slot
    min_idx = jnp.argmin(jnp.where(filled, score, jnp.inf))
    idx = jnp.where(has_empty, empty_idx, min_idx)
    do = has_empty | (d > score[min_idx])

    old_s, old_p = states[idx], probs[idx]
    evict = do & filled[idx]
    add = do.astype(s_sum.dtype)
    sub = evict.astype(s_sum.dtype)
    s_sum = s_sum + add * cand_state - sub * old_s
    s_outer = (s_outer + add * jnp.outer(cand_state, cand_state)
               - sub * jnp.outer(old_s, old_s))
    p_sum = p_sum + add * cand_probs - sub * old_p
    n_filled = (n_filled + do.astype(n_filled.dtype)
                - evict.astype(n_filled.dtype))

    states = jnp.where(do, states.at[idx].set(cand_state), states)
    probs = jnp.where(do, probs.at[idx].set(cand_probs), probs)
    score = jnp.where(do, score.at[idx].set(d), score)
    filled = jnp.where(do, filled.at[idx].set(True), filled)
    return (states, probs, score, filled, s_sum, s_outer, p_sum, n_filled), \
        (idx, do, d)


# ---------------------------------------------------------------------------
# Federated delta codec (fl transport) — shared math + jnp oracle
# ---------------------------------------------------------------------------
# Single source of truth for every int8/top-k encode/decode in the repo: the
# FL transport subsystem (``repro.fl.codec``), the DP gradient compression
# (``repro.training.compression`` re-exports ``quantize_int8`` /
# ``dequantize_int8`` from here), the jnp oracle (``delta_codec_ref``), and
# the fused Pallas ``delta_codec`` kernel body all call these helpers, so the
# implementations cannot drift. Everything is plain vector ops (no gather-
# heavy argsort) so the same code is legal inside jit, vmap, lax.scan, and a
# Pallas kernel.

DELTA_CODECS = ("float32", "int8", "topk")


def int8_scale(xf):
    """Per-tensor symmetric int8 scale: max|x|/127, floored away from 0.

    Written as an explicit multiply by the reciprocal constant: XLA applies
    the div-by-constant -> mul-by-reciprocal rewrite in some compilation
    contexts (e.g. inside a Pallas kernel) but not others, which would put
    the kernel and the op-by-op oracle one ulp apart on the scale and break
    bit-identity everywhere downstream."""
    return jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) * (1.0 / 127.0)


def quantize_int8(x):
    """Per-tensor symmetric int8 quantization. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = int8_scale(xf)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def int8_roundtrip(xf):
    """quantize -> dequantize without materializing the int8 array (the
    values stay integer-valued float32, bit-identical to casting through
    int8 — asserted in tests/test_fl.py). Returns (decoded, scale)."""
    scale = int8_scale(xf)
    return jnp.clip(jnp.round(xf / scale), -127.0, 127.0) * scale, scale


def topk_mask(mag, k: int):
    """(n,) bool mask selecting EXACTLY the k largest entries of the
    non-negative ``mag``, ties broken by lowest index.

    No sort (Mosaic has none): for non-negative float32 the int32 bit
    pattern orders like the value, so the k-th largest value is the largest
    pattern t with count(bits >= t) >= k, built one bit at a time (31 count
    passes); the ties at t that still fit the budget are the lowest-index
    ones, found the same way over the index (log2 n passes). Compares and
    sums only, all in the integer domain, so the same code runs inside the
    Pallas kernel body and selects the same set on every backend."""
    n = mag.shape[0]
    if k >= n:
        return jnp.ones((n,), bool)
    bits = jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)
    count = lambda m: jnp.sum(m.astype(jnp.int32))
    t = jnp.int32(0)
    for b in range(30, -1, -1):
        cand = t | (1 << b)
        t = jnp.where(count(bits >= cand) >= k, cand, t)
    above = bits > t
    eq = bits == t
    need = k - count(above)               # >= 1 ties at t still selected
    idx = _iota(n)
    j = jnp.int32(0)                      # largest j with count(eq & idx<j) < need
    for b in range(max(n - 1, 1).bit_length() - 1, -1, -1):
        cand = j | (1 << b)
        j = jnp.where(count(eq & (idx < cand)) < need, cand, j)
    return above | (eq & (idx <= j))


def delta_codec_step(xf, *, codec: str, k: int = 1):
    """Encode->decode one flat error-compensated delta ``xf = delta + r``.

    Returns (decoded, new_residual) with ``decoded + new_residual == xf``
    — the telescoping identity error feedback relies on; bit-exact for
    float32/topk, within one ulp of the quantization scale for int8:
      * ``float32`` — lossless: decoded = xf, residual 0.
      * ``int8``    — per-tensor symmetric quantization round trip.
      * ``topk``    — keep the k largest-|.| coordinates exactly, zero the
        rest; the untransmitted mass is the residual.
    """
    if codec == "float32":
        return xf, jnp.zeros_like(xf)
    if codec == "int8":
        # The residual is (frac - q) * scale, NOT xf - q*scale: the latter
        # is an FMA-contractible a*b-c pattern that XLA fuses inside the
        # Pallas kernel but not in the op-by-op oracle, breaking
        # kernel==oracle bit-identity. (frac - q)*scale has the subtract
        # before the multiply — no contraction applies — and equals
        # xf - dec to one ulp of xf (frac*scale == xf up to two roundings).
        scale = int8_scale(xf)
        frac = xf / scale
        q = jnp.clip(jnp.round(frac), -127.0, 127.0)
        return q * scale, (frac - q) * scale
    if codec == "topk":
        mask = topk_mask(jnp.abs(xf), k)
        # residual via select, not subtraction: exact in both regimes
        return jnp.where(mask, xf, 0.0), jnp.where(mask, 0.0, xf)
    raise ValueError(f"unknown codec {codec!r}; expected one of {DELTA_CODECS}")


def delta_codec_ref(delta, residual, *, codec: str, k: int = 1):
    """jnp oracle for the fused Pallas ``delta_codec`` kernel: one agent's
    flat (L,) parameter delta through error feedback + encode + decode
    (vmap for a fleet). Returns (decoded, new_residual)."""
    return delta_codec_step(delta + residual, codec=codec, k=k)


# ---------------------------------------------------------------------------
# Request-level data-plane microtick (digital twin) — shared math + jnp oracle
# ---------------------------------------------------------------------------
# The twin keeps each agent's in-flight requests in a power-of-two ring whose
# occupancy is described by MONOTONE int32 request counters rather than mod-R
# pointers: because every request passes admit -> pre -> batch-form ->
# inference -> post in order and every stage serves FIFO, each stage's
# occupants are a CONTIGUOUS ring segment and the whole per-agent queue state
# is five counters (head <= p_inf <= launch <= p_pre <= tail). Stage
# membership is positional, a request's deadline is arrive + slo_ticks, and
# ring slot ``i`` holds request number ``q`` iff q ≡ i (mod R) — so admission
# and completion are mask writes/reads over ((i - ptr) & (R-1)) < n, never a
# sort or a scatter. ``sim_microtick_rows`` below is the single source of
# truth: ``sim_microtick`` (one agent; the jnp oracle ``queue_advance_ref``
# and the harness scan it) and the Pallas ``queue_advance`` kernel body (a
# block of agents) both call it, so the implementations cannot drift.

# counters vector layout (int32): five stage pointers (monotone request
# counts), the inference-server occupancy flag + completion tick, four
# request accumulators, and the global microtick counter.
(SIM_TAIL, SIM_PPRE, SIM_LAUNCH, SIM_PINF, SIM_HEAD, SIM_BUSY, SIM_DONE_AT,
 SIM_ARRIVED, SIM_DROPPED, SIM_COMPLETED, SIM_EFFECTIVE, SIM_TICK) = range(12)
SIM_NCOUNTERS = 12

# caps vector layout (float32; integer-valued entries cast inside the tick):
# pre/post service capacity per tick, requests per inference batch, batch
# service time in ticks, per-stage queue capacity, SLO deadline in ticks.
CAP_PRE, CAP_POST, CAP_BATCH, CAP_TBATCH, CAP_QCAP, CAP_SLO = range(6)
SIM_NCAPS = 6


def _iota(n):
    # 1D iota via broadcasted_iota — a plain 1D ``jax.lax.iota`` fails to
    # lower inside a Pallas TPU kernel (vector lanes want >= 2D).
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)[:, 0]


def sim_microtick_rows(arrive, c, credits, lat_sum, n_arrive, caps, hist_n):
    """The math of one microtick, for one agent or for a block of agents.

    One agent: arrive is its (R,) ring and every per-agent value a scalar.
    A block of B agents: arrive is (B, R), one ring per row, and every
    per-agent value a (B, 1) column. The per-agent values are ``c`` (the
    SIM_NCOUNTERS counters, a sequence), ``credits`` (2, a sequence),
    ``lat_sum``, ``n_arrive`` (arrivals this tick) and ``caps`` (SIM_NCAPS,
    a sequence). ``sim_microtick`` calls it for one agent and the Pallas
    ``queue_advance`` kernel for a block, so the two share every operation.

    Returns (arrive, c, credits, lat_sum, bucket): ``bucket`` is shaped
    like ``arrive`` and holds, for each slot completed this tick, its
    latency in ticks clipped to [0, hist_n - 1], and hist_n elsewhere.

    Stage order is a backward sweep (complete -> post -> launch -> pre ->
    admit) so a request spends >= 1 tick per stage; pre/post are token-bucket
    servers (bucket depth = capacity + 1 so idle periods cannot bank
    unbounded service); the inference server runs ONE batch at a time and
    launches work-conserving (whatever is ready, up to the batch size and
    the post-queue room — backpressure instead of post drops, which keeps
    the ring segments contiguous); admission drops overflow beyond the
    bounded pre queue. Deadline check: a completion at end-of-tick m has
    latency m + 1 - arrive ticks and counts as effective iff it is within
    slo_ticks. Python mirror: ``repro.sim.oracle`` (built on serving/slo.py).
    """
    c, credits, lat_sum, bucket, _ = sim_serve_rows(arrive, c, credits,
                                                    lat_sum, caps, hist_n)
    arrive, c = sim_admit_rows(arrive, c, n_arrive, caps)
    return arrive, c, credits, lat_sum, bucket


def _ring_idx(arrive):
    ring = arrive.shape[-1]
    assert ring > 0 and ring & (ring - 1) == 0, \
        "ring capacity must be a positive power of two"
    return jax.lax.broadcasted_iota(jnp.int32, arrive.shape, arrive.ndim - 1)


def sim_serve_rows(arrive, c, credits, lat_sum, caps, hist_n):
    """Steps (1)-(4) of ``sim_microtick_rows``: everything but admission.
    Returns (c, credits, lat_sum, bucket, n_post): ``c`` with the service
    pointers and the completion accumulators advanced, and ``n_post`` the
    requests completed this tick (a per-agent value)."""
    ring = arrive.shape[-1]
    idx = _ring_idx(arrive)

    def ring_sum(x):   # per agent: a scalar, or a (B, 1) column
        return jnp.sum(x, axis=-1, keepdims=x.ndim > 1, dtype=jnp.int32)

    m = c[SIM_TICK]
    c_pre, c_post = caps[CAP_PRE], caps[CAP_POST]
    batch_slots = caps[CAP_BATCH].astype(jnp.int32)
    t_batch = caps[CAP_TBATCH].astype(jnp.int32)
    qcap = caps[CAP_QCAP].astype(jnp.int32)
    slo_ticks = caps[CAP_SLO].astype(jnp.int32)

    # (1) inference completion: the in-flight batch lands in the post queue.
    done = (c[SIM_BUSY] > 0) & (m >= c[SIM_DONE_AT])
    p_inf = jnp.where(done, c[SIM_LAUNCH], c[SIM_PINF])
    busy = jnp.where(done, 0, c[SIM_BUSY])

    # (2) post-processing serves the n oldest post-queue requests; their
    # latencies feed the accumulators and the histogram buckets.
    # (credits stay >= 0, so the int32 cast truncates == floor)
    post_credit = jnp.minimum(credits[1] + c_post, c_post + 1.0)
    n_post = jnp.minimum(post_credit.astype(jnp.int32),
                         p_inf - c[SIM_HEAD])
    post_credit = post_credit - n_post.astype(jnp.float32)
    comp = ((idx - c[SIM_HEAD]) & (ring - 1)) < n_post
    lat = m + 1 - arrive
    lat_sum = lat_sum + ring_sum(jnp.where(comp, lat, 0)).astype(jnp.float32)
    n_eff = ring_sum(comp & (lat <= slo_ticks))
    # non-completed slots bucket to the out-of-range sentinel hist_n
    bucket = jnp.where(comp, jnp.clip(lat, 0, hist_n - 1), hist_n)
    head = c[SIM_HEAD] + n_post

    # (3) batch launch: work-conserving, backpressured by post-queue room
    # (room counts everything at/after inference not yet post-completed, so
    # the post queue can never exceed qcap and never needs to drop).
    ready = c[SIM_PPRE] - c[SIM_LAUNCH]
    room = qcap - (c[SIM_LAUNCH] - head)
    n_launch = jnp.maximum(
        jnp.minimum(jnp.minimum(ready, batch_slots), room), 0)
    do_launch = (busy == 0) & (n_launch > 0)
    launch = jnp.where(do_launch, c[SIM_LAUNCH] + n_launch, c[SIM_LAUNCH])
    done_at = jnp.where(do_launch, m + t_batch, c[SIM_DONE_AT])
    busy = jnp.where(do_launch, 1, busy)

    # (4) pre-processing, backpressured by batch-formation queue room.
    pre_credit = jnp.minimum(credits[0] + c_pre, c_pre + 1.0)
    n_pre = jnp.minimum(
        pre_credit.astype(jnp.int32),
        jnp.minimum(c[SIM_TAIL] - c[SIM_PPRE],
                    jnp.maximum(qcap - (c[SIM_PPRE] - launch), 0)))
    n_pre = jnp.maximum(n_pre, 0)
    pre_credit = pre_credit - n_pre.astype(jnp.float32)
    p_pre = c[SIM_PPRE] + n_pre

    c = (c[SIM_TAIL], p_pre, launch, p_inf, head, busy, done_at,
         c[SIM_ARRIVED], c[SIM_DROPPED], c[SIM_COMPLETED] + n_post,
         c[SIM_EFFECTIVE] + n_eff, m)
    return c, (pre_credit, post_credit), lat_sum, bucket, n_post


def sim_admit_rows(arrive, c, n_arrive, caps):
    """Step (5) of ``sim_microtick_rows``, after ``sim_serve_rows``:
    admission into the bounded pre queue, overflow dropped, and the tick
    clock advanced. Returns (arrive, c)."""
    ring = arrive.shape[-1]
    idx = _ring_idx(arrive)
    m = c[SIM_TICK]
    qcap = caps[CAP_QCAP].astype(jnp.int32)
    # Each stage queue is <= qcap, so with ring >= 3*qcap the ring bound
    # never binds.
    free = jnp.minimum(qcap - (c[SIM_TAIL] - c[SIM_PPRE]),
                       ring - (c[SIM_TAIL] - c[SIM_HEAD]))
    admit = jnp.clip(jnp.minimum(n_arrive, free), 0, n_arrive)
    adm = ((idx - c[SIM_TAIL]) & (ring - 1)) < admit
    arrive = jnp.where(adm, m, arrive)
    c = (c[SIM_TAIL] + admit,) + tuple(c[1:SIM_ARRIVED]) + (
        c[SIM_ARRIVED] + n_arrive, c[SIM_DROPPED] + (n_arrive - admit),
        c[SIM_COMPLETED], c[SIM_EFFECTIVE], m + 1)
    return arrive, c


def sim_microtick(arrive, counters, credits, lat_sum, hist, n_arrive, caps):
    """One microtick of ONE agent's request-level pipeline, pure array ops
    (the math is ``sim_microtick_rows``).

    arrive: (R,) int32 ring of arrival ticks; counters: (SIM_NCOUNTERS,)
    int32; credits: (2,) float32 fractional pre/post service tokens;
    lat_sum: () float32; hist: (H,) int32 completed-latency histogram in
    ticks, binned here every tick; n_arrive: () int32 arrivals this tick;
    caps: (SIM_NCAPS,) float32. Returns the updated
    (arrive, counters, credits, lat_sum, hist)."""
    hist_n = hist.shape[0]
    arrive, c, credits, lat_sum, bucket = sim_microtick_rows(
        arrive, tuple(counters[j] for j in range(SIM_NCOUNTERS)),
        (credits[0], credits[1]), lat_sum, n_arrive,
        tuple(caps[j] for j in range(SIM_NCAPS)), hist_n)
    hist = hist + jnp.sum(bucket[:, None] == _iota(hist_n)[None, :],
                          axis=0, dtype=jnp.int32)
    return arrive, jnp.stack(c), jnp.stack(credits), lat_sum, hist


def queue_advance_ref(arrive, counters, credits, lat_sum, hist, arrivals,
                      caps):
    """jnp oracle for the fused Pallas ``queue_advance`` kernel: advance ONE
    agent's data plane K microticks (vmap for a fleet).

    arrivals: (K,) int32 per-tick arrival counts; caps: (SIM_NCAPS,) float32
    (one action decode, held for the whole control interval). Returns the
    updated (arrive, counters, credits, lat_sum, hist)."""

    def tick(carry, n_arr):
        return sim_microtick(*carry, n_arr, caps), None

    carry, _ = jax.lax.scan(
        tick, (arrive, counters, credits, lat_sum, hist), arrivals)
    return carry


def diversity_insert_ref(states, probs, score, filled, s_sum, s_outer, p_sum,
                         n_filled, cand_states, cand_probs, *, alpha, beta,
                         ridge=0.1):
    """jnp oracle for the fused Pallas ``diversity_insert`` kernel: ingest T
    candidates sequentially (single agent; vmap for a fleet).

    cand_states: (T, D); cand_probs: (T, NA). Returns the updated
    (states, probs, score, filled, s_sum, s_outer, p_sum, n_filled) plus the
    per-candidate decision trace (slot (T,), do_insert (T,), d (T,)) the
    caller uses to scatter the non-scored payload (actions/rewards/...).

    The sequential scan carries only O(N) metadata — score and a per-slot
    *source map* (``-1`` = original occupant, ``t`` = candidate t) — plus
    the O(D²) moments. A slot's current occupant is gathered from the source
    map when its rank-1 contribution must be subtracted on eviction, and the
    (N, D)/(N, NA) slot arrays are materialized ONCE after the scan from the
    final map, instead of being copied through every scan step.

    The slot choice exploits the score invariant — empty slots hold −inf,
    filled slots a finite Eq. 6 value — so ``argmin(score)`` alone picks the
    first empty slot if any (all −inf ties resolve to the lowest index,
    matching ``argmin(filled)``) else the min-score filled slot, and
    ``d > min(score)`` is the insert test in both regimes (−inf accepts
    everything). ``filled`` therefore never enters the scan at all.
    Decision-for-decision identical to ``diversity_insert_step`` chained T
    times (tests/test_buffer.py)."""
    n = score.shape[0]

    def step(carry, x):
        score, src, s_sum, s_outer, p_sum, n_filled = carry
        s, p, t = x
        d = diversity_score_from_moments(s, p, s_sum, s_outer, p_sum,
                                         n_filled, alpha=alpha, beta=beta,
                                         ridge=ridge)
        minval = jnp.min(score)
        idx = jnp.argmin(score)
        do = d > minval                  # -inf (empty slot) accepts always
        evict = do & (minval != -jnp.inf)

        si = src[idx]
        old_s = jnp.where(si < 0, states[idx], cand_states[jnp.maximum(si, 0)])
        old_p = jnp.where(si < 0, probs[idx], cand_probs[jnp.maximum(si, 0)])
        add = do.astype(s_sum.dtype)
        sub = evict.astype(s_sum.dtype)
        carry = (
            score.at[idx].set(jnp.where(do, d, minval)),
            src.at[idx].set(jnp.where(do, t, si)),
            s_sum + add * s - sub * old_s,
            s_outer + add * jnp.outer(s, s) - sub * jnp.outer(old_s, old_s),
            p_sum + add * p - sub * old_p,
            n_filled + do.astype(n_filled.dtype)
            - evict.astype(n_filled.dtype),
        )
        return carry, (idx, do, d)

    init = (score, jnp.full((n,), -1, jnp.int32), s_sum, s_outer, p_sum,
            n_filled)
    xs = (cand_states, cand_probs, jnp.arange(cand_states.shape[0]))
    (score, src, s_sum, s_outer, p_sum, n_filled), (slot, do, d) = \
        jax.lax.scan(step, init, xs)

    written = src >= 0
    keep = (~written)[:, None]
    states = jnp.where(keep, states, cand_states[jnp.maximum(src, 0)])
    probs = jnp.where(keep, probs, cand_probs[jnp.maximum(src, 0)])
    filled = filled | written
    return states, probs, score, filled, s_sum, s_outer, p_sum, n_filled, \
        slot, do, d
