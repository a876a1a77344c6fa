import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real step function (train_step / prefill /
serve_step) against ShapeDtypeStruct stand-ins on the production mesh,
compiles it, and extracts:
  * memory_analysis()      — proves the cell fits per-device HBM,
  * cost_analysis()        — HLO FLOPs / bytes for the roofline terms,
  * collective schedule    — parsed from the post-SPMD HLO text (bytes per
    collective kind, wire-traffic convention documented in
    ``collective_bytes``),
  * roofline terms         — compute / memory / collective seconds +
    dominant bottleneck + MODEL_FLOPS/HLO_FLOPs utilization ratio.

Results are cached as JSON under ``artifacts/dryrun/`` so EXPERIMENTS.md and
``benchmarks/roofline.py`` read from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, get_config, list_archs, shape_applicable
from repro.distributed import sharding as shd
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models.registry import get_model, input_specs
from repro.serving.engine import make_prefill_step, make_serve_step
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}

_COLL_RE = re.compile(
    r"=\s*[a-z0-9]+\[[0-9,]*\][^=]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,. ]*)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum wire bytes per collective kind from post-SPMD HLO.

    Convention (documented for the roofline): per-op total wire traffic =
    (participants - 1) × payload, where payload = per-device output bytes
    (all-gather) / input bytes (reduce-scatter, all-to-all, permute) /
    2 × input bytes (all-reduce ≈ RS + AG phases).
    """
    out = {}
    count = {}
    ops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        out_bytes = _shape_bytes(*shapes[0])
        in_bytes = (_shape_bytes(*shapes[1]) if len(shapes) > 1 else out_bytes)
        g = _GROUPS_RE.search(line)
        if g:
            ids = [x for x in g.group(1).replace(" ", "").split(",") if x]
            n_part = max(len(ids), 2)
        else:
            gi = _IOTA_GROUPS_RE.search(line)
            n_part = int(gi.group(2)) if gi else 2
        if kind == "all-gather":
            payload = out_bytes
        elif kind == "all-reduce":
            payload = 2 * in_bytes
        else:
            payload = in_bytes
        wire = (n_part - 1) * payload
        out[kind] = out.get(kind, 0) + wire
        count[kind] = count.get(kind, 0) + 1
        shape_str = f"{shapes[0][0]}[{shapes[0][1]}]"
        ops.append((wire, kind, shape_str, n_part))
    ops.sort(reverse=True)
    top = [{"kind": k, "shape": s, "participants": n, "wire_bytes": w}
           for w, k, s, n in ops[:12]]
    return {"bytes": out, "count": count, "total": sum(out.values()),
            "top_ops": top}


def _count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def model_flops(cfg, params_specs, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference); MoE uses N_active."""
    n_total = _count_params(params_specs)
    n = n_total
    if cfg.n_experts:
        # subtract inactive expert params
        e, f, d = cfg.n_experts, cfg.moe_d_ff, cfg.d_model
        n_moe_layers = cfg.n_layers - cfg.first_dense_layers
        per_layer = 3 * d * f
        n = n_total - n_moe_layers * per_layer * (e - cfg.top_k)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n * tokens, n_total, n


def build_cell(arch: str, shape_name: str, mesh, serve_dtype=jnp.bfloat16,
               unroll: bool = False, overrides=None, fsdp: bool = True):
    """Returns (fn, args (SDS pytrees), in_shardings, out_shardings).

    ``fsdp=False`` = the serving param profile (TP-only weights, no per-step
    weight re-gather) — a §Perf variant for the inference shapes."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    cfg = cfg.replace(param_dtype="float32" if shape.kind == "train" else "bfloat16")
    if unroll:
        cfg = cfg.replace(scan_layers=False)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = get_model(cfg)
    batch = input_specs(cfg, shape)
    batch_sh = shd.input_shardings(batch, mesh)
    params_specs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = shd.param_shardings(params_specs, mesh, fsdp=fsdp)

    if shape.kind == "train":
        opt_specs = {
            "m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                              params_specs),
            "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                              params_specs),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        opt_sh = {
            "m": params_sh, "v": jax.tree.map(lambda s: s, params_sh),
            "step": shd.replicated(mesh),
        }
        state = {"params": params_specs, "opt": opt_specs}
        state_sh = {"params": params_sh, "opt": opt_sh}
        fn = make_train_step(model, AdamWConfig(), remat=True)
        return (fn, (state, batch), (state_sh, batch_sh),
                (state_sh, None), cfg, params_specs, shape)

    if shape.kind == "prefill":
        fn = make_prefill_step(model, with_cache=False)
        out_sh = None
        return (fn, (params_specs, batch), (params_sh, batch_sh), out_sh,
                cfg, params_specs, shape)

    # decode
    cache_specs = model.cache_spec(shape.global_batch, shape.seq_len,
                                   serve_dtype)
    cache_sh = shd.cache_shardings(cache_specs, mesh)
    fn = make_serve_step(model)
    return (fn, (params_specs, cache_specs, batch),
            (params_sh, cache_sh, batch_sh), None, cfg, params_specs, shape)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             save: bool = True, unroll: bool = False, variant: str = "",
             overrides=None) -> dict:
    shape = SHAPES[shape_name]
    cfg0 = get_config(arch)
    ok, reason = shape_applicable(cfg0, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "variant": variant or ("unroll" if unroll else "")}
    if not ok:
        rec.update(status="skipped", reason=reason)
        _save(rec, save)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, cfg, params_specs, shape = build_cell(
            arch, shape_name, mesh, unroll=unroll, overrides=overrides)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
        except Exception as e:  # noqa: BLE001
            mem["error"] = str(e)

        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = {k: float(v) for k, v in ca.items()
                    if k in ("flops", "bytes accessed", "transcendentals",
                             "optimal_seconds")}
        except Exception as e:  # noqa: BLE001
            cost["error"] = str(e)

        coll = collective_bytes(compiled.as_text())

        # cost_analysis() reports the PER-DEVICE SPMD module (verified:
        # argument_size == global params+opt bytes / n_chips), so the
        # compute/memory terms divide by a single chip's peak, while the
        # collective term uses the fleet-total wire bytes over all links.
        hlo_flops = cost.get("flops", 0.0)          # per device
        hlo_bytes = cost.get("bytes accessed", 0.0)  # per device
        mflops, n_total, n_active = model_flops(cfg, params_specs, shape)
        t_comp = hlo_flops / PEAK_FLOPS_BF16
        t_mem = hlo_bytes / HBM_BW
        t_coll = coll["total"] / (n_chips * ICI_BW)
        terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
        dominant = max(terms, key=terms.get)

        rec.update(
            status="ok",
            chips=n_chips,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=mem,
            cost=cost,
            collectives=coll,
            params_total=n_total,
            params_active=n_active,
            model_flops=mflops,
            hlo_flops_global=hlo_flops * n_chips,
            useful_flops_ratio=(mflops / (hlo_flops * n_chips)
                                if hlo_flops else None),
            roofline=terms,
            dominant=dominant,
        )
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    _save(rec, save)
    return rec


def _save(rec, save):
    if not save:
        return
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"_{rec['variant']}" if rec.get("variant") else ""
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    with open(os.path.join(ART_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unrolled layer lowering: exact cost_analysis "
                         "(XLA:CPU counts scan bodies once)")
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_name, multi_pod=mp,
                               unroll=args.unroll)
                dom = rec.get("dominant", "-")
                print(f"{arch:24s} {shape_name:12s} {rec['mesh']:8s} "
                      f"{rec['status']:8s} {dom:13s} "
                      f"compile={rec.get('compile_s', '-')}s "
                      f"{rec.get('reason', rec.get('error', ''))}",
                      flush=True)
                results.append(rec)
    bad = [r for r in results if r["status"] == "error"]
    print(f"\n{len(results)} cells: "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skipped' for r in results)} skipped, "
          f"{len(bad)} errors")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
