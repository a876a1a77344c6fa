"""Roofline analysis (§g): compute / memory / collective terms per
(arch × shape) on the production mesh, from compiled dry-run artifacts.

Methodology — the scan-correction *delta method*: XLA:CPU ``cost_analysis``
counts ``lax.scan`` bodies ONCE (verified in EXPERIMENTS.md §Dry-run), so the
full-L scanned lowering undercounts per-layer FLOPs/bytes/collectives by ~L×.
Fully unrolled lowerings are exact but compile in O(minutes-hours) per 7B
cell on this host. Instead we lower each cell UNROLLED at two (or four) small
layer counts and extrapolate linearly — exact for homogeneous stacks:

    dense/moe/encoder/vlm:  f(L0), f(L0+1);  X(L) = f(L0) + (L - L0)·Δ
    deepseek (1 dense + 26 moe):  f(2), f(3)
    zamba2 (6 groups of 6 + 2 tail):  f(6), f(12), f(8)
    xlstm (sLSTM@{0,8}, mLSTM elsewhere):  f(2), f(3), f(8), f(9)

Known residual undercounts (documented, small): per-chunk/time-step scan
*bodies* that are pure elementwise state updates (mamba2/mLSTM state carry,
sLSTM recurrent core ≈3% of xlstm FLOPs).

Usage:
  python -m benchmarks.roofline --compute   # runs the delta lowerings (512-dev)
  python -m benchmarks.roofline             # prints the table from artifacts
"""
import os
import sys

if "--compute" in sys.argv:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import subprocess

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")
ROOF = os.path.join(ART, "roofline")

PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _lin(f, arch, full_layers):
    """Combine point-measurements into full-L counts per the plan."""
    def mix(coeffs):
        out = {}
        for key in ("flops", "bytes", "coll"):
            out[key] = sum(c * f[n][key] for n, c in coeffs)
        return out

    if arch == "zamba2-1.2b":
        # X(38) = f6 + 5*(f12 - f6) + (f8 - f6) = -5*f6 + 5*f12 + f8
        return mix([(6, -5.0), (12, 5.0), (8, 1.0)])
    if arch == "xlstm-125m":
        # X = f2 + (f9-f8) + 9*(f3-f2)  [one extra sLSTM + 9 extra mLSTM]
        return mix([(2, 1.0 - 9.0), (3, 9.0), (8, -1.0), (9, 1.0)])
    if arch == "deepseek-v2-lite-16b":
        l0 = 2
        return mix([(2, 1.0 - (full_layers - l0)), (3, float(full_layers - l0))])
    l0 = 1
    return mix([(1, 1.0 - (full_layers - l0)), (2, float(full_layers - l0))])


def compute(archs=None, shapes=None):
    """Run the delta lowerings (requires the 512-device override)."""
    from repro.configs.base import SHAPES, get_config, shape_applicable
    from repro.launch.dryrun import build_cell, collective_bytes, model_flops
    from repro.launch.mesh import make_production_mesh
    import jax

    os.makedirs(ROOF, exist_ok=True)
    mesh = make_production_mesh(multi_pod=False)
    archs = archs or sorted(
        __import__("repro.configs", fromlist=["ARCH_IDS"]).ARCH_IDS)
    shapes = shapes or list(SHAPES)

    for arch in archs:
        cfg_full = get_config(arch)
        for shape_name in shapes:
            ok, _ = shape_applicable(cfg_full, shape_name)
            if not ok:
                continue
            out_path = os.path.join(ROOF, f"{arch}__{shape_name}.json")
            if os.path.exists(out_path):
                print(f"cached {arch} {shape_name}", flush=True)
                continue
            if arch == "zamba2-1.2b":
                points = [6, 12, 8]
            elif arch == "xlstm-125m":
                points = [2, 3, 8, 9]
            elif arch == "deepseek-v2-lite-16b":
                points = [2, 3]
            else:
                points = [1, 2]
            f = {}
            try:
                for n in points:
                    fn, args, in_sh, out_sh, cfg, pspecs, shape = build_cell(
                        arch, shape_name, mesh, unroll=True,
                        overrides={"n_layers": n})
                    with jax.set_mesh(mesh):
                        compiled = jax.jit(fn, in_shardings=in_sh,
                                           out_shardings=out_sh).lower(*args).compile()
                    ca = compiled.cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0]
                    f[n] = {
                        "flops": float(ca.get("flops", 0.0)),
                        "bytes": float(ca.get("bytes accessed", 0.0)),
                        "coll": float(collective_bytes(
                            compiled.as_text())["total"]),
                    }
                    print(f"  {arch} {shape_name} L={n}: "
                          f"flops={f[n]['flops']:.3e}", flush=True)
                corrected = _lin(f, arch, cfg_full.n_layers)
                # MODEL_FLOPS for the FULL config
                from repro.models.registry import get_model
                full_model = get_model(cfg_full.replace(
                    param_dtype="float32"
                    if SHAPES[shape_name].kind == "train" else "bfloat16"))
                pspecs_full = jax.eval_shape(full_model.init,
                                             jax.random.PRNGKey(0))
                mflops, n_tot, n_act = model_flops(cfg_full, pspecs_full,
                                                   SHAPES[shape_name])
                rec = {
                    "arch": arch, "shape": shape_name, "points": f,
                    "flops_per_device": corrected["flops"],
                    "bytes_per_device": corrected["bytes"],
                    "collective_bytes_total": corrected["coll"],
                    "model_flops": mflops,
                    "params_total": n_tot, "params_active": n_act,
                    "chips": mesh.size,
                }
                with open(out_path, "w") as fh:
                    json.dump(rec, fh, indent=1)
                print(f"{arch:24s} {shape_name:12s} corrected "
                      f"flops/dev={corrected['flops']:.3e}", flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"{arch} {shape_name} ERROR {e}", flush=True)


def report(emit_rows=False):
    rows = []
    if not os.path.isdir(ROOF):
        return []
    for fn in sorted(os.listdir(ROOF)):
        with open(os.path.join(ROOF, fn)) as fh:
            r = json.load(fh)
        chips = r["chips"]
        t_comp = r["flops_per_device"] / PEAK_FLOPS_BF16
        t_mem = r["bytes_per_device"] / HBM_BW
        t_coll = r["collective_bytes_total"] / (chips * ICI_BW)
        terms = {"compute_s": t_comp, "memory_s": t_mem,
                 "collective_s": t_coll}
        dominant = max(terms, key=terms.get)
        bound = max(terms.values())
        model_t = r["model_flops"] / (chips * PEAK_FLOPS_BF16)
        useful = r["model_flops"] / (r["flops_per_device"] * chips + 1e-30)
        rows.append({
            "name": f"roofline_{r['arch']}_{r['shape']}",
            "arch": r["arch"], "shape": r["shape"],
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "useful_flops_ratio": useful,
            "roofline_fraction": model_t / bound if bound else 0.0,
            "model_flops": r["model_flops"],
        })
    if emit_rows:
        return [{
            "name": r["name"], "us_per_call": f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.0f}",
            "derived": (f"dom={r['dominant']} comp={r['compute_s']:.2e}s "
                        f"mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s "
                        f"roofline_frac={r['roofline_fraction']:.3f}"),
        } for r in rows]
    return rows


def main(quick: bool = True):
    if not os.path.isdir(ROOF) or not os.listdir(ROOF):
        # compute in a subprocess so the 512-device override never leaks
        subprocess.run([sys.executable, "-m", "benchmarks.roofline",
                        "--compute"], check=False,
                       env={**os.environ,
                            "PYTHONPATH": os.environ.get("PYTHONPATH", "src")})
    return report(emit_rows=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--compute", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    a = ap.parse_args()
    if a.compute:
        compute([a.arch] if a.arch else None, [a.shape] if a.shape else None)
    from benchmarks.common import emit_csv
    emit_csv(report(emit_rows=True))
