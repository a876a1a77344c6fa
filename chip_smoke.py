"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases 0-3
    python chip_smoke.py --chips 4   # the ('pod', 'data') fleet mesh vs one chip

One chip, in one process, through the entry points a user calls:

  0. device: a TPU is required (no CPU fallback) and the Pallas kernels
     must compile, not run in the interpreter;
  1. fleet training: ``repro.launch.train_fleet.main`` at 1024 iAgents in 4
     pods, 4 episodes in the request-level twin with the fused
     ``queue_advance`` microtick and the fused int8 ``delta_codec`` — one
     donated ``train_fleet_scan``; then the codec kernel against its jnp
     oracle on one FL round's real deltas, bit for bit, for every codec;
  2. twin evaluation: ``simulate_fleet`` on the trained fleet with the
     Pallas kernel and with the jnp oracle — identical request totals;
  3. data plane: ``repro.launch.serve.main`` at qwen2-0.5b's full width
     (random weights from the seed), calibrating the twin's latency model
     from real prefill timings; the engine's prefill against a cache-free
     forward of the same model.

``--chips 4`` runs only the multi-chip path: the phase-1 run on
``make_fleet_mesh(4, 4)`` (pods over chips, Alg. 1 / ``merge_pods`` as
collectives, kernels under ``shard_map``) against the same seed on one
chip, at the tolerance of tests/test_mesh.py.

Every phase prints its set-up seconds (compilation included) and its checks.
Any failed check exits non-zero before the last line, which is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FLEET_FLAGS = ["--agents", "1024", "--pods", "4", "--episodes", "4",
               "--env-backend", "twin", "--pallas", "--fl-codec", "int8",
               "--fl-pallas", "--scenario", "nominal"]
SERVE_FLAGS = ["--arch", "qwen2-0.5b", "--replicas", "4", "--episodes", "2"]
TWIN_INTERVALS = 30
MESH_ATOL = 1e-5          # tests/test_mesh.py's meshed == single-device bound


class CheckFailed(Exception):
    pass


def check(ok, what):
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def same_bits(a, b) -> bool:
    """Bitwise equality (NaN- and signed-zero-exact)."""
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def phase_device(n_chips: int):
    import jax

    from repro.kernels import ops as kops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CheckFailed(f"no TPU found: JAX sees {devs[0].platform!r} "
                          f"devices; this script has no CPU fallback")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"phase 0 device: {dev['kind']} x {dev['count']}", flush=True)
    check(len(devs) >= n_chips, f"{n_chips} chip(s) visible")
    check(not kops._interpret_default(),
          "Pallas kernels compile (not interpreted)")
    return dev


def check_history(hist, n_eps, n_rounds):
    reward = np.asarray(hist["reward"])
    check(reward.shape == (n_eps,), f"{n_eps} episode records")
    check(bool(np.isfinite(reward).all()), "rewards finite")
    payload = np.asarray(hist["fl_payload_bytes"])
    check(int(np.count_nonzero(payload)) == n_rounds,
          f"{n_rounds} FL rounds with non-zero payload "
          f"({payload[payload > 0].tolist()} B)")


def check_codec_vs_oracle(fleet, topk_frac):
    """One FL round's delta codec, kernel vs jnp oracle, every codec, on
    the trained fleet's real round input: each agent's params minus its pod
    base plus its carried residual, flattened to the iAgent's (A, L)
    parameter vector."""
    import jax
    import jax.numpy as jnp

    from repro.fl.transport import CODECS, topk_k
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    a = fleet.pod_ids.shape[0]
    flat = lambda leaves: jnp.concatenate(
        [x.astype(jnp.float32).reshape(a, -1) for x in leaves], axis=1)
    d = flat(jax.tree.leaves(jax.tree.map(
        lambda p, b: p.astype(jnp.float32) - b[fleet.pod_ids],
        fleet.astate.params, fleet.base_params)))
    r = flat(jax.tree.leaves(fleet.residuals))
    k = topk_k(d.shape[1], topk_frac)
    for codec in CODECS:
        got = kops.delta_codec(d, r, codec=codec, k=k)
        want = jax.jit(jax.vmap(functools.partial(
            kref.delta_codec_ref, codec=codec, k=k)))(d, r)
        for g, w, name in zip(got, want, ("decoded", "residual")):
            if not same_bits(g, w):
                raise CheckFailed(
                    f"delta_codec {codec} {name} differs from the oracle: "
                    f"max |diff| {float(jnp.max(jnp.abs(g - w)))}")
        check(True, f"delta_codec {codec} kernel == oracle bit for bit on "
                    f"{d.shape} (k={k})")


def phase_fleet():
    import jax

    from repro.core.fleet import lower_fleet_scan
    from repro.launch import train_fleet

    print("phase 1 fleet training: train_fleet " + " ".join(FLEET_FLAGS),
          flush=True)
    t = time.perf_counter()
    fleet, hist = train_fleet.main(FLEET_FLAGS)
    print(f"phase 1 train_fleet.main: {time.perf_counter() - t:.1f} s "
          f"(compilation included)", flush=True)
    args = train_fleet.parse_args(FLEET_FLAGS)
    cfg, fleet0, traces, mesh, kw = train_fleet.build(args)
    check_history(hist, args.episodes, args.episodes // cfg.fl_every)

    t = time.perf_counter()
    hlo = lower_fleet_scan(cfg, fleet0, traces, mesh=mesh,
                           **kw).compile().as_text()
    print(f"phase 1 lower+compile of the same scan: "
          f"{time.perf_counter() - t:.1f} s", flush=True)
    check("tpu_custom_call" in hlo,
          f"the compiled scan holds Mosaic kernels "
          f"({hlo.count('tpu_custom_call')} tpu_custom_call)")
    del fleet0, hlo

    t = time.perf_counter()
    check_codec_vs_oracle(fleet, args.fl_topk_frac)
    print(f"phase 1 codec check: {time.perf_counter() - t:.1f} s",
          flush=True)
    jax.block_until_ready(fleet)
    return cfg, kw["env_backend"].sp, fleet


def phase_twin(cfg, sp, fleet):
    import jax

    from repro.kernels import ref as kref
    from repro.sim import make_scenario, simulate_fleet

    a = fleet.pod_ids.shape[0]
    traces = make_scenario("nominal", jax.random.PRNGKey(7), a,
                           TWIN_INTERVALS)
    key = jax.random.PRNGKey(8)
    out = {}
    for use_pallas in (True, False):
        t = time.perf_counter()
        state, _, _ = simulate_fleet(cfg, sp, fleet.astate.params,
                                     fleet.masks, fleet.env_params, traces,
                                     key, use_pallas=use_pallas)
        state = jax.device_get(state)
        name = "kernel" if use_pallas else "oracle"
        print(f"phase 2 simulate_fleet ({name}): "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        out[name] = state
    c = np.asarray(out["kernel"].counters)
    totals = {n: int(c[:, i].sum()) for n, i in (
        ("arrived", kref.SIM_ARRIVED), ("dropped", kref.SIM_DROPPED),
        ("completed", kref.SIM_COMPLETED),
        ("effective", kref.SIM_EFFECTIVE))}
    print(f"  request totals over {a} agents x {TWIN_INTERVALS} "
          f"intervals: {totals}", flush=True)
    check(totals["arrived"] > 0 and totals["completed"] > 0,
          "the twin served requests")
    for field in out["kernel"]._fields:
        check(same_bits(getattr(out["kernel"], field),
                        getattr(out["oracle"], field)),
              f"twin state {field}: kernel == oracle bit for bit")


def phase_serve():
    import jax
    import jax.numpy as jnp

    from repro.launch import serve
    from repro.serving.engine import make_prefill_step

    print("phase 3 data plane: serve " + " ".join(SERVE_FLAGS), flush=True)
    t = time.perf_counter()
    out = serve.main(SERVE_FLAGS)
    print(f"phase 3 serve.main: {time.perf_counter() - t:.1f} s "
          f"(compilation included)", flush=True)
    engine, ep = out["engine"], out["env_params"]
    cfg = engine.model.cfg
    print(f"  model: {cfg.name}, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}", flush=True)
    t0, t1 = float(ep.t0), float(ep.t1)
    print(f"  calibration (fit, not a benchmark metric): t0 = {t0:.6g} s, "
          f"t1 = {t1:.6g} s/item", flush=True)
    check(np.isfinite([t0, t1]).all() and t0 > 0 and t1 > 0,
          "calibrated t0, t1 finite and positive")
    check(bool(np.isfinite(out["rewards"]).all()), "fleet rewards finite")
    for tokens in out["served"]:
        tokens = np.asarray(tokens)
        check(tokens.ndim == 2 and tokens.shape[1] == 2
              and ((tokens >= 0) & (tokens < cfg.vocab_size)).all(),
              f"served batch {tokens.shape}: token ids in the vocabulary")
    check(engine.stats["prefill_calls"] > 0
          and engine.stats["decode_calls"] > 0, "prefill and decode ran")

    # the engine's cached prefill against a cache-free forward of the same
    # weights on the same tokens
    batch = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                               cfg.vocab_size, jnp.int32)
    got, _, _ = engine.prefill(batch)
    want = jax.jit(make_prefill_step(engine.model, with_cache=False))(
        engine.params, {"tokens": batch})[:, -1]
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    check(got.shape == want.shape == (2, cfg.vocab_size)
          and np.isfinite(got).all() and err <= 0.05 * scale,
          f"prefill logits {got.shape} match the cache-free forward "
          f"(max |diff| {err:.3g}, max |logit| {scale:.3g})")


def phase_mesh():
    """The ('pod', 'data') fleet mesh over four chips against one chip."""
    import jax

    from repro.core.fleet import (fleet_device_bytes, lower_fleet_scan,
                                  train_fleet_scan)
    from repro.launch import compile_cache, train_fleet

    compile_cache.enable()
    runs = {}
    for name, extra in (("mesh", ["--mesh", "fleet"]), ("one", [])):
        args = train_fleet.parse_args(FLEET_FLAGS + extra)
        cfg, fleet, traces, mesh, kw = train_fleet.build(args)
        if mesh is not None:
            check(dict(mesh.shape) == {"pod": 4, "data": 1},
                  f"fleet mesh {dict(mesh.shape)}: pods over chips")
            t = time.perf_counter()
            hlo = lower_fleet_scan(cfg, fleet, traces, mesh=mesh,
                                   **kw).compile().as_text()
            print(f"mesh lower+compile: {time.perf_counter() - t:.1f} s",
                  flush=True)
            check("all-reduce" in hlo,
                  f"the meshed scan holds cross-pod collectives "
                  f"({hlo.count('all-reduce(')} all-reduce, "
                  f"{hlo.count('all-gather(')} all-gather)")
            check("tpu_custom_call" in hlo, "kernels compiled under the mesh")
        t = time.perf_counter()
        out, hist = train_fleet_scan(cfg, fleet, traces, mesh=mesh, **kw)
        jax.block_until_ready(out)
        print(f"train_fleet_scan ({name}): {time.perf_counter() - t:.1f} s "
              f"(compilation included)", flush=True)
        check_history(hist, args.episodes, args.episodes // cfg.fl_every)
        runs[name] = (out, hist)

    (m_out, m_hist), (o_out, o_hist) = runs["mesh"], runs["one"]
    per = fleet_device_bytes(m_out)
    print(f"  fleet bytes per device: {per}", flush=True)
    vals = sorted(per.values())
    check(len(per) == 4 and vals[-1] <= 2.0 * vals[0],
          "the meshed fleet is split across all four chips")
    check(set(fleet_device_bytes(o_out)) == {jax.devices()[0].id},
          "the single-chip fleet lives on device 0")
    for k in sorted(o_hist):
        a, b = np.asarray(m_hist[k]), np.asarray(o_hist[k])
        if not np.allclose(a, b, atol=MESH_ATOL, rtol=0):
            raise CheckFailed(f"history {k}: meshed != single chip, max "
                              f"|diff| {float(np.max(np.abs(a - b)))}")
    check(True, f"{len(o_hist)} history series: meshed == single chip "
                f"(atol {MESH_ATOL})")
    diffs = [float(np.max(np.abs(np.asarray(a, np.float32)
                                 - np.asarray(b, np.float32))))
             for a, b in zip(jax.tree.leaves(m_out.astate.params),
                             jax.tree.leaves(o_out.astate.params))]
    check(max(diffs) <= MESH_ATOL,
          f"final params: meshed == single chip (max |diff| "
          f"{max(diffs):.3g})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the fleet-mesh path against one chip")
    args = ap.parse_args(argv)
    try:
        dev = phase_device(args.chips)
        if args.chips == 4:
            phase_mesh()
        else:
            from repro.launch import compile_cache

            print(f"compile cache: {compile_cache.enable()}", flush=True)
            cfg, sp, fleet = phase_fleet()
            phase_twin(cfg, sp, fleet)
            del fleet
            phase_serve()
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
