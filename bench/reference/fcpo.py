"""Plain reference of the FCPO fleet's training rounds.

A straightforward, option-free implementation of what the benchmark's cells
run, written for the comparison that decides ``correct``. It imports nothing
of the program and takes nothing the program has made: it builds its own
fleet from the seed, draws its own traces' consumers, and runs episode by
episode with plain ``jax.numpy`` (no Pallas kernel, no scanned driver, no
mesh). The equations follow the program's documented semantics (FCPO,
arXiv:2507.18047, Eqs. 1-7 and Algorithms 1-2 as the program reads them);
where the program fixes an order of operations that decides a discrete
choice (buffer eviction, the int8 scale), the same order is used here.

``Ref.dtype`` is the float type everything is computed and stored in:
float32 for the reference, bfloat16 for the control that a sound comparison
must refuse. Integer state (actions, twin counters) stays int32.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

RIDGE = 0.1
BACKBONE = ("l1", "l2", "value")
HEADS = ("head_res", "head_bs", "head_mt")
LAYERS = BACKBONE + HEADS
# twin counters (int32): five stage pointers, server busy flag and finish
# tick, four request accumulators, the microtick clock
(TAIL, PPRE, LAUNCH, PINF, HEAD, BUSY, DONE_AT, ARRIVED, DROPPED, COMPLETED,
 EFFECTIVE, TICK) = range(12)


@dataclass(frozen=True)
class Ref:
    """Everything the reference needs, from the configuration file."""
    n_agents: int
    n_pods: int
    iagent: Any
    rl: Any
    fl: Any
    env: str                       # "fluid" | "twin"
    twin: Optional[Any] = None     # dt, k_ticks, ring, hist_n
    codec: str = "float32"         # "float32" | "int8"
    dtype: Any = jnp.float32

    @staticmethod
    def from_config(c, dtype=jnp.float32, n_agents=None):
        t = c.get("twin")
        return Ref(n_agents=n_agents or c["agents"], n_pods=c["pods"],
                   iagent=tuple(sorted(c["iagent"].items())),
                   rl=tuple(sorted(c["rl"].items())),
                   fl=tuple(sorted(c["fl"].items())), env=c["env_backend"],
                   twin=tuple(sorted(t.items())) if t else None,
                   codec=c["fl_codec"], dtype=dtype)

    def __getattr__(self, name):
        for group in ("iagent", "rl", "fl", "twin"):
            d = object.__getattribute__(self, group)
            if d is not None and name in dict(d):
                return dict(d)[name]
        raise AttributeError(name)

    @property
    def n_act(self):
        return self.n_res + self.n_bs + self.n_mt


# ---------------------------------------------------------------------------
# iAgent: 8 -> 64 -> 48 backbone, value head, cascaded action heads (Fig. 4)
# ---------------------------------------------------------------------------
def linear_init(key, d_in, d_out):
    k1, k2 = jax.random.split(key)
    lim = 1.0 / math.sqrt(d_in)
    return {"w": jax.random.uniform(k1, (d_in, d_out), jnp.float32, -lim, lim),
            "b": jax.random.uniform(k2, (d_out,), jnp.float32, -lim, lim)}


def agent_init(r: Ref, key):
    ks = jax.random.split(key, 6)
    f = r.feat_dim
    return {"l1": linear_init(ks[0], r.state_dim, r.hidden_dim),
            "l2": linear_init(ks[1], r.hidden_dim, f),
            "value": linear_init(ks[2], f, 1),
            "head_res": linear_init(ks[3], f, r.n_res),
            "head_bs": linear_init(ks[4], f + r.n_res, r.n_bs),
            "head_mt": linear_init(ks[5], f + r.n_res, r.n_mt)}


def dense(p, x):
    return jnp.dot(x, p["w"], precision="highest") + p["b"]


def forward(r: Ref, p, x):
    """Log-probabilities of the three heads and the value of states x."""
    h = jax.nn.relu(dense(p["l1"], x))
    feat = jax.nn.relu(dense(p["l2"], h))
    value = dense(p["value"], feat)[..., 0]
    lres = jax.nn.log_softmax(dense(p["head_res"], feat), axis=-1)
    feat_c = jnp.concatenate([feat, jnp.exp(lres)], axis=-1)
    lbs = jax.nn.log_softmax(dense(p["head_bs"], feat_c), axis=-1)
    lmt = jax.nn.log_softmax(dense(p["head_mt"], feat_c), axis=-1)
    return lres, lbs, lmt, value


def sample(r: Ref, p, obs, key):
    """Gumbel-max draw of each head: (actions (3,), logp, probs, value)."""
    lres, lbs, lmt, value = forward(r, p, obs)
    kr, kb, km = jax.random.split(key, 3)
    scores = [l + jax.random.gumbel(k, l.shape, jnp.float32).astype(l.dtype)
              for l, k in zip((lres, lbs, lmt), (kr, kb, km))]
    acts = [jnp.argmax(s) for s in scores]
    logp = sum(l[a] for l, a in zip((lres, lbs, lmt), acts))
    probs = jnp.concatenate([jnp.exp(lres), jnp.exp(lbs), jnp.exp(lmt)])
    return jnp.stack(acts).astype(jnp.int32), logp, probs, value


# ---------------------------------------------------------------------------
# environments: the fluid MDP and the request-level twin (one agent)
# ---------------------------------------------------------------------------
def env_params(r: Ref, speed):
    F = r.dtype
    speed = speed.astype(F)
    return {"t0": 0.012 / speed, "t1": 0.0022 / speed,
            "pre_rate": 220.0 * speed, "post_rate": 260.0 * speed,
            "contention": 0.18 / jnp.maximum(speed, 0.25),
            "queue_cap": jnp.asarray(128.0, F),
            "slo_s": jnp.asarray(r.slo_s, F), "net_lat": jnp.asarray(0.015, F)}


def observation(r: Ref, rate, cur_action, drops, pre_q, post_q, queue_cap,
                slo_s):
    F = r.dtype
    return jnp.stack([
        rate / 100.0,
        cur_action[0].astype(F) / (r.n_res - 1),
        cur_action[1].astype(F) / (r.n_bs - 1),
        cur_action[2].astype(F) / (r.n_mt - 1),
        jnp.asarray(drops, F) / 50.0,
        jnp.asarray(pre_q, F) / queue_cap,
        jnp.asarray(post_q, F) / queue_cap,
        slo_s / 0.5]).astype(F)


def decode(r: Ref, action):
    F = r.dtype
    res = jnp.asarray((1.0, 0.75, 0.5, 0.25), F)[action[0]]
    bs = jnp.asarray((1, 2, 4, 8, 16, 32, 64), F)[action[1]]
    mt = jnp.asarray((1, 2, 3, 4), F)[action[2]]
    return res, bs, mt


def fluid_init(r: Ref):
    z = jnp.zeros((), r.dtype)
    return {"pre_q": z, "post_q": z, "drops": z,
            "cur_action": jnp.zeros((3,), jnp.int32), "ema_lat": z}


def fluid_observe(r: Ref, ep, s, rate):
    return observation(r, rate, s["cur_action"], s["drops"], s["pre_q"],
                       s["post_q"], ep["queue_cap"], ep["slo_s"])


def fluid_step(r: Ref, ep, s, action, rate):
    """One control interval of the fluid pipeline: Little's-law queues,
    batched inference, bounded queues that drop, Eq. 1 reward."""
    res, bs, mt = decode(r, action)
    area = res ** 2
    pack = 1.0 / area
    mt_eff = mt * jnp.maximum(1.0 - ep["contention"] * (mt - 1.0), 0.3)
    rate_pre = ep["pre_rate"] * mt_eff / jnp.maximum(area, 0.05)
    pre_in = s["pre_q"] + rate
    pre_done = jnp.minimum(pre_in, rate_pre)
    pre_q = pre_in - pre_done
    drops_pre = jnp.maximum(pre_q - ep["queue_cap"], 0.0)
    pre_q = jnp.minimum(pre_q, ep["queue_cap"])
    t_batch = ep["t0"] + ep["t1"] * bs * area
    inf_done = jnp.minimum(pre_done + 0.0, (bs * pack) / t_batch)
    pre_q = jnp.minimum(pre_q + (pre_done - inf_done), ep["queue_cap"])
    rate_post = ep["post_rate"] * mt_eff
    post_in = s["post_q"] + inf_done
    post_done = jnp.minimum(post_in, rate_post)
    post_q = post_in - post_done
    drops_post = jnp.maximum(post_q - ep["queue_cap"], 0.0)
    post_q = jnp.minimum(post_q, ep["queue_cap"])
    lat = (ep["net_lat"] + pre_q / jnp.maximum(rate_pre, 1.0)
           + 0.5 * bs * pack / jnp.maximum(rate, 1.0) + t_batch
           + post_q / jnp.maximum(rate_post, 1.0))
    ema_lat = 0.7 * s["ema_lat"] + 0.3 * lat
    viol = jnp.where(lat > ep["slo_s"], post_done, 0.0)
    safe = jnp.maximum(rate, 1.0)
    reward = jnp.tanh(0.5 * (r.theta * post_done / safe - r.sigma * ema_lat
                             - r.phi * (bs + viol) / safe))
    s2 = {"pre_q": pre_q, "post_q": post_q, "drops": drops_pre + drops_post,
          "cur_action": action, "ema_lat": ema_lat}
    return s2, reward


def twin_init(r: Ref):
    return {"arrive": jnp.zeros((r.ring,), jnp.int32),
            "counters": jnp.zeros((12,), jnp.int32),
            "credits": jnp.zeros((2,), r.dtype),
            "lat_sum": jnp.zeros((), r.dtype),
            "hist": jnp.zeros((r.hist_n,), jnp.int32),
            "cur_action": jnp.zeros((3,), jnp.int32),
            "drops_prev": jnp.zeros((), jnp.int32),
            "phase": jnp.zeros((), r.dtype),
            "ema_lat": jnp.zeros((), r.dtype)}


def twin_qcap(r: Ref, ep):
    return jnp.minimum(ep["queue_cap"], float(r.ring // 3))


def twin_observe(r: Ref, ep, s, rate):
    c = s["counters"]
    return observation(r, rate, s["cur_action"], s["drops_prev"],
                       c[TAIL] - c[PPRE], c[PINF] - c[HEAD], twin_qcap(r, ep),
                       ep["slo_s"])


def twin_caps(r: Ref, ep, action):
    """The action's per-microtick service: pre/post requests per tick,
    requests per batch, batch time and queue capacity in ticks... and the
    SLO in ticks."""
    res, bs, mt = decode(r, action)
    area = res ** 2
    mt_eff = mt * jnp.maximum(1.0 - ep["contention"] * (mt - 1.0), 0.3)
    rate_pre = ep["pre_rate"] * mt_eff / jnp.maximum(area, 0.05)
    rate_post = ep["post_rate"] * mt_eff
    t_batch = ep["t0"] + ep["t1"] * bs * area
    return (rate_pre * r.dt, rate_post * r.dt,
            jnp.maximum(jnp.round(bs / area), 1.0).astype(jnp.int32),
            jnp.maximum(jnp.ceil(t_batch / r.dt), 1.0).astype(jnp.int32),
            jnp.round(twin_qcap(r, ep)).astype(jnp.int32),
            jnp.maximum(jnp.round(ep["slo_s"] / r.dt), 1.0).astype(jnp.int32))


def spread(r: Ref, rate, phase):
    """Arrivals per microtick: cumulative floors of rate * dt, with the
    fractional request carried in ``phase``."""
    j = jnp.arange(1 + r.k_ticks, dtype=r.dtype)
    cum = jnp.floor(phase + rate * r.dt * j)
    end = phase + rate * r.dt * r.k_ticks
    return (cum[1:] - cum[:-1]).astype(jnp.int32), end - jnp.floor(end)


def microtick(r: Ref, s, n_arrive, caps):
    """One microtick of the pipeline: inference completion, post-processing
    of the oldest requests (their latencies counted), a work-conserving
    batch launch backpressured by post-queue room, pre-processing
    backpressured by batch-queue room, then admission with drops. Each
    stage serves FIFO, so its occupants are a contiguous ring segment."""
    c_pre, c_post, batch, t_batch, qcap, slo = caps
    ring = r.ring
    idx = jnp.arange(ring, dtype=jnp.int32)
    arrive, c, credits = s["arrive"], s["counters"], s["credits"]
    m = c[TICK]
    done = (c[BUSY] > 0) & (m >= c[DONE_AT])
    p_inf = jnp.where(done, c[LAUNCH], c[PINF])
    busy = jnp.where(done, 0, c[BUSY])
    post_credit = jnp.minimum(credits[1] + c_post, c_post + 1.0)
    n_post = jnp.minimum(post_credit.astype(jnp.int32), p_inf - c[HEAD])
    post_credit = post_credit - n_post.astype(credits.dtype)
    served = ((idx - c[HEAD]) & (ring - 1)) < n_post
    lat = m + 1 - arrive
    lat_sum = s["lat_sum"] + jnp.sum(jnp.where(served, lat, 0)).astype(
        s["lat_sum"].dtype)
    n_eff = jnp.sum(served & (lat <= slo), dtype=jnp.int32)
    hist = s["hist"] + jnp.zeros_like(s["hist"]).at[
        jnp.clip(lat, 0, r.hist_n - 1)].add(served.astype(jnp.int32))
    head = c[HEAD] + n_post
    ready = c[PPRE] - c[LAUNCH]
    room = qcap - (c[LAUNCH] - head)
    n_launch = jnp.maximum(jnp.minimum(jnp.minimum(ready, batch), room), 0)
    go = (busy == 0) & (n_launch > 0)
    launch = jnp.where(go, c[LAUNCH] + n_launch, c[LAUNCH])
    done_at = jnp.where(go, m + t_batch, c[DONE_AT])
    busy = jnp.where(go, 1, busy)
    pre_credit = jnp.minimum(credits[0] + c_pre, c_pre + 1.0)
    n_pre = jnp.minimum(pre_credit.astype(jnp.int32),
                        jnp.minimum(c[TAIL] - c[PPRE],
                                    jnp.maximum(qcap - (c[PPRE] - launch), 0)))
    n_pre = jnp.maximum(n_pre, 0)
    pre_credit = pre_credit - n_pre.astype(credits.dtype)
    p_pre = c[PPRE] + n_pre
    free = jnp.minimum(qcap - (c[TAIL] - p_pre), ring - (c[TAIL] - head))
    admit = jnp.clip(jnp.minimum(n_arrive, free), 0, n_arrive)
    adm = ((idx - c[TAIL]) & (ring - 1)) < admit
    counters = jnp.stack([
        c[TAIL] + admit, p_pre, launch, p_inf, head, busy, done_at,
        c[ARRIVED] + n_arrive, c[DROPPED] + (n_arrive - admit),
        c[COMPLETED] + n_post, c[EFFECTIVE] + n_eff, m + 1])
    return dict(s, arrive=jnp.where(adm, m, arrive), counters=counters,
                credits=jnp.stack([pre_credit, post_credit]),
                lat_sum=lat_sum, hist=hist)


def twin_interval(r: Ref, ep, s, action, rate):
    """One control interval of the twin: K microticks under one action."""
    caps = twin_caps(r, ep, action)
    arrivals, phase = spread(r, rate, s["phase"])
    s2 = jax.lax.fori_loop(0, r.k_ticks,
                           lambda t, st: microtick(r, st, arrivals[t], caps),
                           s)
    return dict(s2, phase=phase)


def twin_step(r: Ref, ep, s, action, rate):
    """A twin interval as a training step: Eq. 1 on request-grade
    completions, deadline misses and admission drops."""
    F = r.dtype
    s2 = twin_interval(r, ep, s, action, rate)
    c0, c1 = s["counters"], s2["counters"]
    d_comp = (c1[COMPLETED] - c0[COMPLETED]).astype(F)
    d_eff = (c1[EFFECTIVE] - c0[EFFECTIVE]).astype(F)
    d_drop = c1[DROPPED] - c0[DROPPED]
    mean_lat = (s2["lat_sum"] - s["lat_sum"]) / jnp.maximum(d_comp, 1.0) * r.dt
    ema_lat = jnp.where(d_comp > 0, 0.7 * s["ema_lat"] + 0.3 * mean_lat,
                        s["ema_lat"])
    interval = r.k_ticks * r.dt
    thr = d_comp / interval
    miss = (d_comp - d_eff) / interval
    drop = d_drop.astype(F) / interval
    _, bs, _ = decode(r, action)
    safe = jnp.maximum(rate, 1.0)
    reward = jnp.tanh(0.5 * (r.theta * thr / safe - r.sigma * ema_lat
                             - r.phi * (bs + miss + drop) / safe))
    return dict(s2, cur_action=action, drops_prev=d_drop,
                ema_lat=ema_lat), reward


# ---------------------------------------------------------------------------
# diversity buffer (Eq. 6) from running moments, one agent
# ---------------------------------------------------------------------------
def buffer_init(r: Ref):
    n, d, na, F = r.buffer_size, r.state_dim, r.n_act, r.dtype
    return {"states": jnp.zeros((n, d), F),
            "probs": jnp.full((n, na), 1.0 / na, F),
            "score": jnp.full((n,), -jnp.inf, F),
            "filled": jnp.zeros((n,), bool),
            "s_sum": jnp.zeros((d,), F), "s_outer": jnp.zeros((d, d), F),
            "p_sum": jnp.zeros((na,), F), "n_filled": jnp.zeros((), jnp.int32)}


def diversity(r: Ref, x, p, s_sum, s_outer, p_sum, n_filled):
    """alpha * Mahalanobis distance of x from the stored states (ridge-
    regularized covariance from the moments) + beta * KL of p from the mean
    stored policy."""
    n = jnp.maximum(n_filled.astype(x.dtype), 1.0)
    mu = s_sum / n
    cov = s_outer / n - jnp.outer(mu, mu) + RIDGE * jnp.eye(x.shape[0],
                                                            dtype=x.dtype)
    y = forward_substitute(cholesky(cov), x - mu)
    d_m = jnp.sqrt(jnp.maximum(jnp.sum(y * y), 0.0))
    mean_p = jnp.where(n_filled > 0, p_sum / n, p)
    pc, qc = jnp.clip(p, 1e-8, 1.0), jnp.clip(mean_p, 1e-8, 1.0)
    return r.alpha * d_m + r.beta * jnp.sum(pc * jnp.log(pc / qc))


def cholesky(a, eps=1e-12):
    """Cholesky factor with each pivot floored at eps, column by column."""
    d = a.shape[0]
    l = jnp.zeros_like(a)
    for j in range(d):
        acc = jnp.sum(l[j, :j] * l[j, :j]) if j else 0.0
        ljj = jnp.sqrt(jnp.maximum(a[j, j] - acc, eps))
        l = l.at[j, j].set(ljj)
        if j + 1 < d:
            dots = jnp.sum(l[j + 1:, :j] * l[j, :j][None, :], -1) if j else 0.0
            l = l.at[j + 1:, j].set((a[j + 1:, j] - dots) / ljj)
    return l


def forward_substitute(l, b):
    """Solve L y = b for lower-triangular L, row by row."""
    y = jnp.zeros_like(b)
    for i in range(b.shape[0]):
        acc = jnp.sum(l[i, :i] * y[:i]) if i else 0.0
        y = y.at[i].set((b[i] - acc) / l[i, i])
    return y


def buffer_insert(r: Ref, buf, xs, ps):
    """Offer an episode's experiences in order: each takes the first empty
    slot, or evicts the lowest-scored one if it scores higher."""
    t_steps = xs.shape[0]

    def step(b, inp):
        x, p, t = inp
        d = diversity(r, x, p, b["s_sum"], b["s_outer"], b["p_sum"],
                      b["n_filled"])
        minval = jnp.min(b["score"])
        idx = jnp.argmin(b["score"])
        do = d > minval
        evict = do & (minval != -jnp.inf)
        old_x, old_p = b["states"][idx], b["probs"][idx]
        add, sub = do.astype(x.dtype), evict.astype(x.dtype)
        b = dict(b,
                 states=jnp.where(do, b["states"].at[idx].set(x), b["states"]),
                 probs=jnp.where(do, b["probs"].at[idx].set(p), b["probs"]),
                 score=b["score"].at[idx].set(jnp.where(do, d, minval)),
                 filled=b["filled"] | (do & (jnp.arange(b["filled"].shape[0])
                                             == idx)),
                 s_sum=b["s_sum"] + add * x - sub * old_x,
                 s_outer=(b["s_outer"] + add * jnp.outer(x, x)
                          - sub * jnp.outer(old_x, old_x)),
                 p_sum=b["p_sum"] + add * p - sub * old_p,
                 n_filled=b["n_filled"] + do.astype(jnp.int32)
                 - evict.astype(jnp.int32))
        return b, None

    buf, _ = jax.lax.scan(step, buf, (xs, ps, jnp.arange(t_steps)))
    return buf


def buffer_resync(buf):
    w = buf["filled"].astype(buf["s_sum"].dtype)
    xs = buf["states"] * w[:, None]
    return dict(buf, s_sum=xs.sum(0),
                s_outer=jnp.einsum("nd,ne->de", xs, buf["states"],
                                   precision="highest"),
                p_sum=(buf["probs"] * w[:, None]).sum(0),
                n_filled=buf["filled"].sum().astype(jnp.int32))


# ---------------------------------------------------------------------------
# losses (Eqs. 3-5), Adam, the CRL update, Alg. 2 fine-tuning (one agent)
# ---------------------------------------------------------------------------
def gae(r: Ref, rewards, values):
    v_next = jnp.concatenate([values[1:], jnp.zeros((1,), values.dtype)])
    deltas = rewards + r.gamma * v_next - values
    adv, out = jnp.zeros((), rewards.dtype), []
    for t in range(rewards.shape[0] - 1, -1, -1):
        adv = deltas[t] + r.gamma * r.lam * adv
        out.append(adv)
    return jnp.stack(out[::-1])


def discounted(r: Ref, rewards):
    ret, out = jnp.zeros((), rewards.dtype), []
    for t in range(rewards.shape[0] - 1, -1, -1):
        ret = rewards[t] + r.gamma * ret
        out.append(ret)
    return jnp.stack(out[::-1])


def action_logp(r: Ref, p, roll):
    lres, lbs, lmt, value = forward(r, p, roll["obs"])
    a = roll["actions"]
    logp = (jnp.take_along_axis(lres, a[:, 0:1], -1)[:, 0]
            + jnp.take_along_axis(lbs, a[:, 1:2], -1)[:, 0]
            + jnp.take_along_axis(lmt, a[:, 2:3], -1)[:, 0])
    return logp, value, (lres, lbs, lmt)


def policy_factor(r: Ref, roll):
    adv = gae(r, roll["rewards"], roll["values"])
    adv = (adv - adv.mean()) / (adv.std() + 1e-6)
    return -adv + jnp.exp(-roll["rewards"])


def loss(r: Ref, p, roll):
    """Eq. 3: Eq. 4's policy term (GAE read as the advantage deficit), Eq.
    5's value term against discounted returns, and the resolution and
    threading penalty."""
    logp, values, _ = action_logp(r, p, roll)
    ratio = jnp.exp(logp - roll["logp"])
    l_p = jnp.mean(jnp.minimum(r.eps_clip * ratio, ratio)
                   * policy_factor(r, roll))
    l_v = jnp.mean(jnp.square(values - discounted(r, roll["rewards"])))
    a = roll["actions"].astype(r.dtype)
    l_pen = r.omega * jnp.mean(a[:, 0] / (r.n_res - 1)
                               + a[:, 2] / (r.n_mt - 1))
    return l_p + l_v + l_pen


def adam(r: Ref, p, g, opt, frozen=()):
    t = opt["t"] + 1
    b1, b2 = 0.9, 0.999
    # the bias corrections are step-count scalars, kept in float32 whatever
    # the dtype (1 - 0.999 ** t is 0 in bfloat16)
    tf = t.astype(jnp.float32)
    c1 = (1 - b1 ** tf).astype(r.dtype)
    c2 = (1 - b2 ** tf).astype(r.dtype)
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, opt["m"], g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, opt["v"], g)
    step = jax.tree.map(
        lambda m_, v_: r.lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + 1e-8), m, v)
    new_p = {k: (p[k] if k in frozen else
                 jax.tree.map(lambda a, s: a - s, p[k], step[k])) for k in p}
    return new_p, {"m": m, "v": v, "t": t}


def crl_update(r: Ref, p, opt, roll):
    """The gated online update: backprop only when |loss| >= loss_gate,
    and nothing kept from a step that is not finite."""
    l = loss(r, p, roll)
    g = jax.grad(lambda q: loss(r, q, roll))(p)
    p2, opt2 = adam(r, p, g, opt)
    ok = jnp.isfinite(l) & jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(p2)]))
    take = (jnp.abs(l) >= r.loss_gate) & ok
    pick = lambda new, old: jnp.where(take, new, old)
    return jax.tree.map(pick, p2, p), jax.tree.map(pick, opt2, opt), l


def finetune(r: Ref, p, opt, roll):
    """Alg. 2: a few policy-loss steps on the action heads only."""
    factor = policy_factor(r, roll)

    def policy_loss(q):
        logp, _, _ = action_logp(r, q, roll)
        ratio = jnp.exp(logp - roll["logp"])
        return jnp.mean(jnp.minimum(r.eps_clip * ratio, ratio) * factor)

    for _ in range(r.finetune_steps):
        g = jax.grad(policy_loss)(p)
        p, opt = adam(r, p, g, opt, frozen=BACKBONE)
    return p, opt


def head_losses(r: Ref, p, roll):
    """Alg. 1's per-head loss: each head's Eq. 4 term at the current params
    (the ratio is 1 there)."""
    _, _, lps = action_logp(r, p, roll)
    factor = policy_factor(r, roll)
    return jnp.stack([jnp.mean(jnp.minimum(r.eps_clip, 1.0) * factor)
                      for _ in lps])


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------
def fleet_init(r: Ref, key):
    """The fleet a seed gives: per-agent iAgents, zero Adam state, empty
    buffers and pipelines, the heterogeneous device mix, per-pod base
    networks."""
    F, a = r.dtype, r.n_agents
    kp, kb, _, kr = jax.random.split(key, 4)
    params = jax.vmap(lambda k: agent_init(r, k))(jax.random.split(kp, a))
    speeds = jnp.asarray(np.random.default_rng(0).choice(
        [0.5, 0.75, 1.0, 2.0], a), jnp.float32)
    bandwidth = jnp.asarray(np.random.default_rng(1).uniform(2.0, 40.0, a),
                            jnp.float32)
    base = agent_init(r, kb)
    cast = lambda t: jax.tree.map(lambda x: x.astype(F), t)
    params = cast(params)
    env_init = twin_init if r.env == "twin" else fluid_init
    return {
        "params": params,
        "opt": {"m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params),
                "t": jnp.zeros((a,), jnp.int32)},
        "buffer": jax.vmap(lambda _: buffer_init(r))(jnp.arange(a)),
        "env": jax.vmap(lambda _: env_init(r))(jnp.arange(a)),
        "rng": jax.random.split(kr, a),
        "ep": jax.vmap(lambda s: env_params(r, s))(speeds),
        "speeds": speeds.astype(F), "bandwidth": bandwidth.astype(F),
        "base": cast(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (r.n_pods,) + x.shape), base)),
        "residuals": jax.tree.map(lambda x: jnp.zeros(x.shape, F), params),
        "pod": jnp.asarray(np.arange(a) % r.n_pods, jnp.int32),
    }


def agent_episode(r: Ref, p, opt, buf, env, rng, ep, rates):
    """One agent's episode: act, step the environment, offer the
    experiences to the buffer, then the gated update."""
    env_obs = twin_observe if r.env == "twin" else fluid_observe
    env_step = twin_step if r.env == "twin" else fluid_step

    def step(carry, rate):
        est, key = carry
        key, k = jax.random.split(key)
        obs = env_obs(r, ep, est, rate)
        acts, logp, probs, value = sample(r, p, obs, k)
        est2, reward = env_step(r, ep, est, acts, rate)
        return (est2, key), (obs, acts, logp, reward.astype(r.dtype), value,
                             probs)

    (env, rng), (obs, acts, logp, rewards, values, probs) = jax.lax.scan(
        step, (env, rng), rates.astype(r.dtype))
    buf = buffer_insert(r, buf, obs, probs)
    roll = {"obs": obs, "actions": acts, "logp": logp, "rewards": rewards,
            "values": values}
    p2, opt2, l = crl_update(r, p, opt, roll)
    return p2, opt2, buf, env, rng, roll, l, rewards.mean()


def episode(r: Ref, st, rates):
    """Every agent's episode: (state, rollouts, mean loss, mean reward)."""
    p, opt, buf, env, rng, roll, l, rew = jax.vmap(
        lambda *a: agent_episode(r, *a))(st["params"], st["opt"],
                                         st["buffer"], st["env"], st["rng"],
                                         st["ep"], rates)
    st = dict(st, params=p, opt=opt, buffer=buf, env=env, rng=rng)
    return st, roll, l.astype(jnp.float32).mean(), rew.astype(
        jnp.float32).mean()


def select(r: Ref, st):
    """Eq. 7: the top half of agents by utility (memory and compute
    availability, squashed buffer diversity) times sqrt(bandwidth / 10)."""
    b = st["buffer"]
    div = jnp.where(b["filled"], b["score"], 0.0).mean(-1)
    if r.env == "twin":
        c = st["env"]["counters"]
        pre_q = (c[:, TAIL] - c[:, PPRE]).astype(r.dtype)
    else:
        pre_q = st["env"]["pre_q"]
    mem = jnp.clip(1.0 - pre_q / st["ep"]["queue_cap"], 0, 1)
    comp = jnp.clip(st["speeds"] / 2.0, 0, 1)
    util = (mem + comp + div / (1.0 + jnp.abs(div))) / 3.0
    util = util * jnp.sqrt(jnp.maximum(st["bandwidth"], 1e-3) / 10.0)
    k = max(1, int(round(r.clients_per_round * r.n_agents)))
    order = jnp.argsort(-util.astype(jnp.float32), stable=True)
    return jnp.zeros((r.n_agents,), bool).at[order[:k]].set(True)


def int8_roundtrip(x):
    """Per-tensor symmetric int8 quantization of one agent's flat delta:
    (decoded, what is left over for error feedback)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) * (1.0 / 127.0)
    frac = x / scale
    q = jnp.clip(jnp.round(frac), -127.0, 127.0)
    return q * scale, (frac - q) * scale


def pod_mean(r: Ref, x, base, w, cnt, pod):
    """(base + sum of w-weighted members) / (members + 1), per pod."""
    shape = (-1,) + (1,) * (x.ndim - 1)
    tot = jax.ops.segment_sum(x * w.reshape(shape), pod, r.n_pods)
    return (base + tot) / (cnt + 1.0).reshape((r.n_pods,) + shape[1:])


def fl_round(r: Ref, st, roll):
    """One federated round: Eq. 7 selection, the selected clients' deltas
    over the wire (int8 with error feedback, or lossless), Alg. 1 per pod
    (equal weights for the backbone and value head, loss weights within
    each action head), Alg. 2 fine-tuning, the buffers' moments rebuilt."""
    sel = select(r, st)
    pod = st["pod"]
    params = st["params"]
    hl = jax.vmap(lambda p, ro: head_losses(r, p, ro))(params, roll)
    residuals = st["residuals"]
    if r.codec == "int8":
        recon, residuals = {}, {}
        for k in LAYERS:
            recon[k], residuals[k] = {}, {}
            for leaf in ("w", "b"):
                p = params[k][leaf]
                b = st["base"][k][leaf][pod]
                x = (p - b).reshape(r.n_agents, -1) + \
                    st["residuals"][k][leaf].reshape(r.n_agents, -1)
                dec, res = jax.vmap(int8_roundtrip)(x)
                ok = jnp.all(jnp.isfinite(dec), axis=1)
                sel = sel & ok
                recon[k][leaf] = b + dec.reshape(p.shape)
                residuals[k][leaf] = res.reshape(p.shape)
        keep = lambda new, old: jnp.where(
            sel.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
        recon = jax.tree.map(keep, recon, params)
        residuals = jax.tree.map(keep, residuals, st["residuals"])
    else:
        recon = params
    w_eq = sel.astype(r.dtype)
    cnt = jax.ops.segment_sum(w_eq, pod, r.n_pods)
    new_p, new_base = {}, {}
    for k in LAYERS:
        new_p[k], new_base[k] = {}, {}
        if k in BACKBONE:
            w = w_eq
        else:
            lh = hl[:, HEADS.index(k)]
            mean_l = jax.ops.segment_sum(lh * w_eq, pod, r.n_pods) / \
                jnp.maximum(cnt, 1.0)
            raw = jnp.exp(-(lh - mean_l[pod])) * w_eq
            rsum = jax.ops.segment_sum(raw, pod, r.n_pods)
            w = raw * (cnt / jnp.maximum(rsum, 1e-9))[pod]
        for leaf in ("w", "b"):
            x = recon[k][leaf]
            agg = pod_mean(r, x, st["base"][k][leaf], w, cnt, pod)
            mine = agg[pod]
            if k in HEADS:
                has = (cnt[pod] > 0).reshape((-1,) + (1,) * (x.ndim - 1))
                mine = jnp.where(has, mine, x)
            new_p[k][leaf], new_base[k][leaf] = mine, agg
    p, opt = jax.vmap(lambda p_, o, ro: finetune(r, p_, o, ro))(
        new_p, st["opt"], roll)
    return dict(st, params=p, opt=opt, base=new_base, residuals=residuals,
                buffer=jax.vmap(buffer_resync)(st["buffer"]))


def pod_merge(st):
    """The cloud tier: every pod's base network becomes their mean."""
    return dict(st, base=jax.tree.map(
        lambda b: jnp.broadcast_to(b.mean(0, keepdims=True), b.shape),
        st["base"]))


@functools.lru_cache(maxsize=None)
def make_round(r: Ref):
    """One training round (a benchmark dispatch): ``fl_every`` episodes,
    then the FL round, then the pod merge on every ``hierarchical_period``
    -th round. Returns a jitted ``(state, rates (A, steps), merge) ->
    (state, per-episode losses, per-episode rewards)``."""
    n = r.n_steps

    def run(st, rates, merge):
        losses, rewards = [], []
        for e in range(r.fl_every):
            st, roll, l, rew = episode(r, st, rates[:, e * n:(e + 1) * n])
            losses.append(l)
            rewards.append(rew)
        st = fl_round(r, st, roll)
        st = jax.lax.cond(merge, pod_merge, lambda s: s, st)
        return st, jnp.stack(losses), jnp.stack(rewards)

    return jax.jit(run)
