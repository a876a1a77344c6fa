"""FCPO-controlled serving launcher — the paper's full system, end to end.

One process = one cluster: N replica engines (reduced model configs on CPU;
full configs on real pods), each piggybacked with an iAgent. Every control
interval the iAgent picks (RES bucket, BS bucket, MT in-flight); the engine
serves that configuration; metrics feed the reward; CRL updates run online;
an agent-specific FL round executes every ``fl_every`` episodes.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --replicas 4 --episodes 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.configs.fcpo import FCPOConfig
from repro.core.fleet import fleet_episode, fleet_init, fl_round
from repro.data.workload import fleet_traces
from repro.launch import compile_cache
from repro.models.registry import get_model
from repro.serving.engine import ServingEngine


def calibrate_env_from_engine(engine: ServingEngine, cfg_f: FCPOConfig,
                              seq: int = 32):
    """Measure the engine's real (t0, t1) batching curve on this device and
    return EnvParams matching it — so the MDP the agents learn on IS this
    data plane's latency surface."""
    from repro.core.env import EnvParams

    vocab = engine.model.cfg.vocab_size
    times = {}
    for bs in (1, max(engine.batch_buckets)):
        tokens = jnp.zeros((bs, seq), jnp.int32) % vocab
        engine.prefill(tokens)  # warm compile
        t0 = time.perf_counter()
        for _ in range(3):
            engine.prefill(tokens)
        times[bs] = (time.perf_counter() - t0) / 3
    b_lo, b_hi = sorted(times)
    t1 = max((times[b_hi] - times[b_lo]) / (b_hi - b_lo), 1e-5)
    t0_fixed = max(times[b_lo] - t1 * b_lo, 1e-4)
    f = lambda x: jnp.asarray(x, jnp.float32)
    return EnvParams(t0=f(t0_fixed), t1=f(t1), pre_rate=f(400.0),
                     post_rate=f(500.0), contention=f(0.15),
                     queue_cap=f(128.0), slo_s=f(cfg_f.slo_s), net_lat=f(0.01))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--episodes", type=int, default=30)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(model, params, max_cache_len=256,
                           batch_buckets=(1, 2, 4, 8), seq_buckets=(16, 32))

    cfg_f = FCPOConfig(slo_s=args.slo_ms / 1000.0)
    fleet = fleet_init(cfg_f, args.replicas, jax.random.PRNGKey(args.seed),
                       n_pods=args.pods, slo_s=cfg_f.slo_s)
    env_params = calibrate_env_from_engine(engine, cfg_f)
    fleet = fleet._replace(env_params=jax.tree.map(
        lambda x: jnp.broadcast_to(x, (args.replicas,)), env_params))
    print(f"calibrated latency model: t0={float(env_params.t0)*1e3:.1f}ms "
          f"t1={float(env_params.t1)*1e6:.0f}us/item")

    traces = fleet_traces(jax.random.PRNGKey(1), args.replicas,
                          args.episodes * cfg_f.n_steps)
    served, rewards = [], []
    for e in range(args.episodes):
        rates = traces[:, e * cfg_f.n_steps:(e + 1) * cfg_f.n_steps]
        fleet, rollouts, metrics = fleet_episode(cfg_f, fleet, rates)
        if (e + 1) % cfg_f.fl_every == 0:
            fleet, sel, _ = fl_round(cfg_f, fleet, rollouts)
        # serve one real batch at the fleet's current best configuration
        a = np.asarray(rollouts.actions[0, -1])
        bs = cfg_f.bs_values[int(a[1])]
        bs = min(bs, max(engine.batch_buckets))
        tokens = jnp.zeros((bs, 16), jnp.int32)
        out = engine.generate(tokens, steps=2)
        served.append(out)
        rewards.append(float(metrics["reward"].mean()))
        print(f"ep {e + 1:3d} reward {float(metrics['reward'].mean()):+.3f} "
              f"eff_thr {float(metrics['effective_throughput'].mean()):6.1f} "
              f"lat {float(metrics['latency'].mean()) * 1e3:6.1f}ms "
              f"| served real batch bs={bs} -> {out.shape}", flush=True)
    print("done")
    return dict(engine=engine, fleet=fleet, env_params=env_params,
                served=served, rewards=rewards)


if __name__ == "__main__":
    main()
