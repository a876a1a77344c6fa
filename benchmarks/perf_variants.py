"""§Perf hillclimb harness: paper-faithful baseline vs beyond-paper optimized
variants for the three chosen cells, measured with the delta method
(full-config extrapolation from 1/2-layer unrolled lowerings).

Cells (chosen per the §Perf brief):
  * qwen2-0.5b × train_4k   — most collective-bound baseline
  * qwen2-7b  × decode_32k  — most representative of the paper (serving)
  * granite-moe-3b-a800m × prefill_32k — worst roofline fraction among
    inference cells + MoE representative

Variants:
  baseline  — reference sdpa (S² materialization), repeat_kv GQA, gathered
              CE, GSPMD-chosen activation shardings, FSDP params everywhere.
  optimized — chunked (flash-style) attention, grouped GQA, vocab-sharded CE,
              pinned activation/buffer shardings, TP-only params for serving.

Usage: python -m benchmarks.perf_variants   (run under 512-dev override)
"""
import os
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import json

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "perf")

CELLS = {
    ("qwen2-0.5b", "train_4k"): dict(
        layers=(1, 2), full=24, fsdp_opt=True,
        opt=dict(shard_activations=True, ce_impl="sharded",
                 attn_impl="chunked", gqa_impl="grouped")),
    ("qwen2-7b", "decode_32k"): dict(
        layers=(1, 2), full=28, fsdp_opt=False,
        opt=dict(shard_activations=True, gqa_impl="grouped")),
    ("granite-moe-3b-a800m", "prefill_32k"): dict(
        layers=(1, 2), full=32, fsdp_opt=False,
        opt=dict(shard_activations=True, attn_impl="chunked",
                 gqa_impl="grouped")),
}

PEAK = 197e12
HBM = 819e9
ICI = 50e9


def measure_variant(arch, shape_name, layer_points, overrides, fsdp, mesh):
    import jax
    from repro.launch.dryrun import build_cell, collective_bytes

    pts = {}
    for n in layer_points:
        ov = dict(overrides, n_layers=n)
        fn, args, in_sh, out_sh, cfg, pspecs, shape = build_cell(
            arch, shape_name, mesh, unroll=True, overrides=ov, fsdp=fsdp)
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, in_shardings=in_sh,
                               out_shardings=out_sh).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        ma = compiled.memory_analysis()
        pts[n] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(collective_bytes(compiled.as_text())["total"]),
            "temp": float(ma.temp_size_in_bytes),
        }
    return pts


def extrapolate(pts, l0, l1, full):
    delta_w = float(full - l0)
    out = {}
    for key in ("flops", "bytes", "coll"):
        out[key] = pts[l0][key] + delta_w * (pts[l1][key] - pts[l0][key])
    out["temp"] = pts[l1]["temp"]  # peak temp is per-layer-ish (scan reuses)
    return out


def run():
    import jax
    from repro.launch.mesh import make_production_mesh

    os.makedirs(ART, exist_ok=True)
    mesh = make_production_mesh(multi_pod=False)
    for (arch, shape_name), spec in CELLS.items():
        out_path = os.path.join(ART, f"{arch}__{shape_name}.json")
        if os.path.exists(out_path):
            print(f"cached {arch} {shape_name}")
            continue
        l0, l1 = spec["layers"]
        rec = {"arch": arch, "shape": shape_name}
        for variant, ov, fsdp in (
                ("baseline", {}, True),
                ("optimized", spec["opt"], spec["fsdp_opt"])):
            pts = measure_variant(arch, shape_name, spec["layers"], ov, fsdp,
                                  mesh)
            full = extrapolate(pts, l0, l1, spec["full"])
            rec[variant] = {
                "points": pts, **full,
                "compute_s": full["flops"] / PEAK,
                "memory_s": full["bytes"] / HBM,
                "collective_s": full["coll"] / (mesh.size * ICI),
            }
            print(f"{arch} {shape_name} {variant}: "
                  f"comp={rec[variant]['compute_s']:.2e}s "
                  f"mem={rec[variant]['memory_s']:.2e}s "
                  f"coll={rec[variant]['collective_s']:.2e}s "
                  f"temp={full['temp']:.2e}B", flush=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)


def report():
    rows = []
    if not os.path.isdir(ART):
        return rows
    for fn in sorted(os.listdir(ART)):
        with open(os.path.join(ART, fn)) as f:
            r = json.load(f)
        b, o = r["baseline"], r["optimized"]
        rows.append({
            "name": f"perf_{r['arch']}_{r['shape']}",
            "baseline": b, "optimized": o,
            "speedup_dominant":
                max(b["compute_s"], b["memory_s"], b["collective_s"])
                / max(o["compute_s"], o["memory_s"], o["collective_s"]),
        })
    return rows


if __name__ == "__main__":
    run()
    for r in report():
        print(r["name"], f"dominant-term speedup {r['speedup_dominant']:.1f}x")
