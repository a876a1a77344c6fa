"""The comparison that decides ``correct``: the program's readings against
the plain reference (``bench/reference/fcpo.py``) put through the same work.

Training cells compare, over the first rounds that set-up drove through the
timed call: each episode's mean loss; the norm of the Adam first moment
after the first round, leaf by leaf (the first gradients as the optimizer
holds them); the norm of each leaf's change over the rounds; the initial
weights; the per-pod base networks after the rounds (the fourth round ends
with the cloud merge), as the relative difference of each leaf; and, in the
twin, the requests that arrived (exact) and the share that completed. A
leaf's norm gap is |program - reference| over the larger of the
reference's norm of that leaf and of the median leaf. Leaves whose
first-round moment in the reference is under a thousandth of the median
leaf's are left out of the change (they move by round-off alone).

"""
from __future__ import annotations

import numpy as np

F64 = np.float64


def flat_program(tree):
    """The program's params pytree ({'backbone': {'l1', 'l2'}, 'value',
    'head_*'}) as {'layer': {'w', 'b'}} host arrays."""
    out = {}
    for k, v in tree.items():
        if k == "backbone":
            out.update({kk: vv for kk, vv in v.items()})
        else:
            out[k] = v
    return {k: {leaf: np.asarray(x) for leaf, x in v.items()}
            for k, v in out.items()}


def leaf_norms(tree):
    return {f"{k}.{leaf}": float(np.linalg.norm(np.asarray(x, F64).ravel()))
            for k, v in tree.items() for leaf, x in v.items()}


def _gap(prog, ref, keep=None):
    """Worst leaf's |norm gap| over max(ref norm, median ref norm)."""
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def train_numbers(prog, ref):
    """{name: (value, detail)} of a training cell. ``prog`` and ``ref`` are
    readings dicts: params0, m1, params4 ({'layer': {'w', 'b'}}), losses
    (per episode), counters4 ((A, 12) twin counters or None)."""
    out = {}
    init = max(float(np.max(np.abs(np.asarray(prog["params0"][k][l], F64)
                                   - np.asarray(ref["params0"][k][l], F64))))
               for k in ref["params0"] for l in ref["params0"][k])
    out["init"] = (init, "max |weight difference| of the initial fleet")
    lp, lr = np.asarray(prog["losses"], F64), np.asarray(ref["losses"], F64)
    scale = max(float(np.mean(np.abs(lr))), 1e-30)
    e = int(np.argmax(np.abs(lp - lr)))
    out["loss"] = (float(np.abs(lp - lr)[e]) / scale,
                   f"episode {e}: {float(lp[e])!r} vs {float(lr[e])!r}")
    g_ref = leaf_norms(ref["m1"])
    out["grad"] = _gap(leaf_norms(prog["m1"]), g_ref)
    med = float(np.median(list(g_ref.values())))
    keep = {k for k, v in g_ref.items() if v >= 1e-3 * med}

    def change(rd):
        return {k: {l: np.asarray(rd["params4"][k][l], F64)
                    - np.asarray(rd["params0"][k][l], F64) for l in v}
                for k, v in rd["params4"].items()}

    out["change"] = _gap(leaf_norms(change(prog)), leaf_norms(change(ref)),
                         keep)
    merge = {f"{k}.{l}": float(
        np.linalg.norm(np.asarray(prog["base4"][k][l], F64)
                       - np.asarray(ref["base4"][k][l], F64))
        / max(np.linalg.norm(np.asarray(ref["base4"][k][l], F64)), 1e-30))
        for k in ref["base4"] for l in ref["base4"][k]}
    worst = max(merge, key=merge.get)
    out["merge"] = (merge[worst], worst)
    if ref.get("counters4") is not None:
        from bench.reference.fcpo import ARRIVED, COMPLETED

        cp = np.asarray(prog["counters4"], np.int64)
        cr = np.asarray(ref["counters4"], np.int64)
        out["arrived"] = (float(np.sum(cp[:, ARRIVED] != cr[:, ARRIVED])),
                          f"{int(cp[:, ARRIVED].sum())} vs "
                          f"{int(cr[:, ARRIVED].sum())} requests")
        sp, sr = cp[:, COMPLETED].sum(), cr[:, COMPLETED].sum()
        out["served"] = (abs(float(sp - sr)) / max(float(sr), 1.0),
                         f"{int(sp)} vs {int(sr)} completed")
    return out


def ref_train_readings(config, key, n_agents, rates, rounds, dtype):
    """The reference's readings over the first ``rounds`` rounds, from the
    seed's own fleet and the rates the program was given."""
    import jax
    import jax.numpy as jnp

    from bench.reference import fcpo

    r = fcpo.Ref.from_config(config, dtype or jnp.float32, n_agents)
    k_fleet, _ = jax.random.split(key)
    st = fcpo.fleet_init(r, k_fleet)
    host = lambda t: jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32)),
                                  t)
    rd = {"params0": host(st["params"]), "losses": []}
    run = fcpo.make_round(r)
    steps = r.n_steps * r.fl_every
    for i in range(rounds):
        merge = (i + 1) % r.hierarchical_period == 0
        chunk = jnp.asarray(rates[:, i * steps:(i + 1) * steps])
        st, losses, _ = run(st, chunk, jnp.asarray(merge))
        rd["losses"].extend(np.asarray(losses, F64).tolist())
        if i == 0:
            rd["m1"] = host(st["opt"]["m"])
    rd["params4"] = host(st["params"])
    rd["base4"] = host(st["base"])
    rd["counters4"] = (np.asarray(st["env"]["counters"])
                       if r.env == "twin" else None)
    return rd
