"""XLA cost & memory accounting: the flight recorder's static layer.

Where the profiler's trace records when phases ran, this module records what
the compiled programs *are*: FLOPs and bytes accessed from
``Compiled.cost_analysis()``, argument/output/temp/alias sizes from
``Compiled.memory_analysis()``, and a donation audit that checks the fleet
pytree's donated buffers are actually aliased to outputs in the lowered
program (``tf.aliasing_output`` annotations — present in the stablehlo text
even on CPU, where the runtime itself cannot reuse donated buffers and
``alias_size_in_bytes`` reads 0).

Everything here analyzes the EXACT objects the training path runs:
``core.fleet.lower_fleet_scan`` lowers the same ``_scan_fn`` the driver
dispatches, and the kernel table is ``kernels.ops.KERNEL_JITS`` — the same
jit wrappers the dispatchers call. ``benchmarks/fig_profile.py`` persists
these stats via ``save_bench`` as the ``BENCH_profile`` envelope and gates
regressions on them.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")

_ALIAS_RE = re.compile(r"tf\.aliasing_output")


def compiled_stats(lowered) -> Dict[str, float]:
    """Cost/memory accounting of one lowered program: compile it and read
    XLA's analyses. Returns a flat float dict (envelope-friendly):
    ``flops``, ``bytes_accessed``, the ``*_size_in_bytes`` memory fields,
    and ``peak_bytes`` (arguments + outputs + temps − aliased: the
    high-water estimate once donation is honored)."""
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # one entry per partition
        cost = cost[0] if cost else {}
    cost = cost or {}
    out: Dict[str, float] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    mem = compiled.memory_analysis()
    for f in _MEM_FIELDS:
        out[f] = float(getattr(mem, f, 0.0) or 0.0)
    out["peak_bytes"] = (out["argument_size_in_bytes"]
                         + out["output_size_in_bytes"]
                         + out["temp_size_in_bytes"]
                         - out["alias_size_in_bytes"])
    return out


def donation_audit(lowered, expected_donated: Optional[int] = None
                   ) -> Dict[str, Any]:
    """Check donated buffers are wired for reuse in the lowered program.

    Counts ``tf.aliasing_output`` argument annotations in the stablehlo
    text — XLA pairs each usable donated input with an output buffer at
    lowering, so the count is the number of donations that will actually
    be honored (the annotation exists on every backend; the *runtime*
    reuse shows up in ``alias_size_in_bytes``, which CPU reports as 0).
    ``expected_donated``: the number of buffers the caller donated (e.g.
    the fleet pytree's leaf count); the audit passes when every one of
    them got an aliased output."""
    text = lowered.as_text()
    aliased = len(_ALIAS_RE.findall(text))
    ok = True if expected_donated is None else aliased >= expected_donated
    return {"aliased_args": aliased,
            "expected_donated": (-1 if expected_donated is None
                                 else int(expected_donated)),
            "ok": bool(ok)}


def profile_fleet_scan(cfg, fleet, traces, donate: bool = True,
                       **lower_kw) -> Dict[str, Any]:
    """Lower the scanned fleet driver exactly as ``train_fleet_scan`` would
    (donation included) and return its cost/memory stats + donation audit.
    ``lower_kw`` forwards to ``core.fleet.lower_fleet_scan``."""
    from repro.core.fleet import lower_fleet_scan
    lowered = lower_fleet_scan(cfg, fleet, traces, donate=donate,
                               **lower_kw)
    stats = compiled_stats(lowered)
    n_leaves = len(jax.tree.leaves(fleet))
    audit = donation_audit(lowered, n_leaves if donate else None)
    stats["donated_leaves"] = float(n_leaves if donate else 0)
    stats["aliased_args"] = float(audit["aliased_args"])
    stats["donation_ok"] = float(audit["ok"])
    return stats


def fleet_memory_report(cfg, n_agents: int, *, n_pods: int = 8,
                        n_episodes: int = 2, state_policies=("float32",
                                                             "lean"),
                        donate: bool = True, seed: int = 0,
                        **lower_kw) -> Dict[str, Dict[str, float]]:
    """Peak-memory accounting of the fleet scan at scale, per state policy.

    For each policy: build an ``n_agents`` fleet (``fleet_init(...,
    state_policy=...)``), lower the exact donated scan, and report XLA's
    ``peak_bytes`` alongside the stored-state byte breakdown
    (``fleet_state_bytes``) and the donation audit — the A=2048-shape
    audit the scaling work gates on. Keys are policy names; each row holds
    ``peak_bytes`` / ``peak_bytes_per_agent`` / ``state_*`` bytes /
    ``donation_ok``. ``lower_kw`` forwards to ``lower_fleet_scan``
    (e.g. ``mesh=...``)."""
    from repro.core.dtypes import get_policy
    from repro.core.fleet import fleet_init, fleet_state_bytes

    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)
    traces = jnp.asarray(
        rng.uniform(10.0, 50.0, (n_agents, n_episodes * cfg.n_steps)),
        jnp.float32)
    out: Dict[str, Dict[str, float]] = {}
    for pol in state_policies:
        name = get_policy(pol).name
        fleet = fleet_init(cfg, n_agents, key, n_pods=n_pods,
                           state_policy=pol)
        stats = profile_fleet_scan(cfg, fleet, traces, donate=donate,
                                   **lower_kw)
        sb = fleet_state_bytes(fleet)
        row = {f"state_{k}": v for k, v in sb.items()}
        row.update(stats)
        row["peak_bytes_per_agent"] = stats["peak_bytes"] / n_agents
        out[name] = row
    return out


# ---------------------------------------------------------------------------
# Canonical kernel workloads: one representative shape per Pallas kernel,
# matching the sizes the fleet actually runs (tests/test_kernels.py cases).
# ---------------------------------------------------------------------------
def _kernel_args(name: str):
    key = jax.random.PRNGKey(0)
    f32 = jnp.float32
    if name == "flash_attention":
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (2, 128, 4, 64), f32)
        k = jax.random.normal(k2, (2, 128, 4, 64), f32)
        v = jax.random.normal(k3, (2, 128, 4, 64), f32)
        return (q, k, v), dict(causal=True, bq=64, bk=64)
    if name == "decode_attention":
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (2, 1, 4, 64), f32)
        kc = jax.random.normal(k2, (2, 256, 4, 64), f32)
        vc = jax.random.normal(k3, (2, 256, 4, 64), f32)
        return (q, kc, vc, jnp.asarray(256, jnp.int32)), dict(bk=128)
    if name == "pack":
        tok = jax.random.normal(key, (64, 128), f32)
        idx = jnp.asarray([0, 63, -1, 5, 5, -1, 17, 2], jnp.int32)
        return (tok, idx), {}
    if name == "diversity_insert":
        from repro.configs.fcpo import FCPOConfig
        from repro.core.buffer import buffer_init
        cfg = FCPOConfig(buffer_size=8)
        na = cfg.n_res + cfg.n_bs + cfg.n_mt
        a, t = 4, 20
        k1, k2 = jax.random.split(key)
        cs = jax.random.normal(k1, (a, t, cfg.state_dim), f32)
        cp = jax.nn.softmax(jax.random.normal(k2, (a, t, na), f32), -1)
        buf = buffer_init(cfg)
        batched = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (a,) + x.shape),
            (buf.states, buf.probs, buf.score, buf.filled, buf.s_sum,
             buf.s_outer, buf.p_sum, buf.n_filled))
        return (*batched, cs, cp), dict(alpha=cfg.alpha, beta=cfg.beta)
    if name == "delta_codec":
        k1, k2 = jax.random.split(key)
        d = jax.random.normal(k1, (8, 3121), f32)
        r = jax.random.normal(k2, (8, 3121), f32) * 0.1
        return (d, r), dict(codec="topk", k=156)
    if name == "queue_advance":
        from repro.sim.state import SimParams, sim_init
        sp = SimParams()
        a = 4
        state = jax.vmap(lambda _: sim_init(sp))(jnp.arange(a))
        k1 = jax.random.fold_in(key, 1)
        arrivals = jax.random.randint(k1, (a, sp.k_ticks), 0, 7)
        caps = jnp.broadcast_to(
            jnp.asarray([2.5, 3.0, 4.0, 2.0, 8.0, 5.0], f32), (a, 6))
        return (*state, arrivals, caps), {}
    raise KeyError(name)


def profile_kernels(names=None) -> Dict[str, Dict[str, float]]:
    """Cost/memory stats for each Pallas kernel's jit wrapper at its
    canonical workload shape. ``names``: subset to profile (default: all of
    ``kernels.ops.KERNEL_JITS``)."""
    from repro.kernels.ops import KERNEL_JITS
    out: Dict[str, Dict[str, float]] = {}
    for name, fn in KERNEL_JITS.items():
        if names is not None and name not in names:
            continue
        args, kw = _kernel_args(name)
        out[name] = compiled_stats(fn.lower(*args, **kw))
    return out
