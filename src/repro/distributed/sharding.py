"""Divisibility-aware named-sharding rules (DP/FSDP/TP/EP/SP).

Every logical tensor dim carries an ordered list of candidate mesh axes
(single names or tuples for composite axes); ``greedy_spec`` assigns the
first candidate whose axis product divides the dim and whose axes are still
unused for this tensor, else leaves the dim replicated. This is what lets
one rule set cover all 10 assigned architectures: 28 heads or 40 experts
simply fall through to the next candidate instead of producing an invalid
sharding (DESIGN.md §5).

Param rules are path-based: the pytree path (e.g. ``blocks/attn/wq/w``)
selects a rule; stacked layer dims (leading ``L``) are auto-detected and
skipped. FSDP ("zero-3") sharding rides the ``data`` axis on the non-TP dim
of every large matrix, which also fully shards the (same-shaped) AdamW
moments.
"""
from __future__ import annotations

import math
import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Candidates = Sequence  # each entry: axis name, tuple of names, or None


def _axes_of(cand):
    return cand if isinstance(cand, tuple) else (cand,)


def greedy_spec(shape, dim_prefs, mesh: Mesh, priority=None) -> P:
    """Assign the first still-unused, divisible candidate axis per dim.
    ``priority`` reorders which dims claim axes first (default: dim order)."""
    used = set()
    spec = [None] * len(shape)
    order = priority if priority is not None else range(len(shape))
    for i in order:
        size, prefs = shape[i], (dim_prefs[i] if i < len(dim_prefs) else ())
        for cand in prefs or ():
            if cand is None:
                break
            axes = _axes_of(cand)
            if any(a in used or a not in mesh.shape for a in axes):
                continue
            prod = math.prod(mesh.shape[a] for a in axes)
            if prod > 1 and size % prod == 0:
                spec[i] = cand
                used.update(axes)
                break
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------
FSDP = ("data",)          # candidates for the "shard-for-memory" dim
TP = ("model",)           # candidates for the "shard-for-compute" dim
EP = ("model",)           # expert-parallel axis

# (path regex, dim_prefs for the *unstacked* shape)
_PARAM_RULES = [
    # embeddings / unembeddings: (vocab, d)
    (r"embed/table$", [TP, FSDP]),
    (r"lm_head/w$", [FSDP, TP]),
    (r"(frame|patch)_proj/w$", [None, TP]),
    # attention projections: (d, features) / (features, d)
    (r"attn/w[qkv]/w$", [FSDP, TP]),
    (r"attn/w[qkv]/b$", [TP]),
    (r"attn/wo/w$", [TP, FSDP]),
    # MLA
    (r"attn/wkv_a/w$", [FSDP, TP]),
    (r"attn/wkv_b/w$", [FSDP, TP]),
    # MLPs: (d, ff) up / (ff, d) down
    (r"mlp/(gate|up)/w$", [FSDP, TP]),
    (r"mlp/down/w$", [TP, FSDP]),
    # MoE: router (d, E); experts (E, d, f) / (E, f, d)
    (r"moe/router/w$", [FSDP, None]),
    (r"moe/(gate|up)$", [EP, FSDP, TP]),
    (r"moe/down$", [EP, TP, FSDP]),
    (r"moe/shared/(gate|up)/w$", [FSDP, TP]),
    (r"moe/shared/down/w$", [TP, FSDP]),
    # mamba2
    (r"mamba/in_proj/w$", [FSDP, TP]),
    (r"mamba/out_proj/w$", [TP, FSDP]),
    (r"mamba/conv_w$", [None, TP]),
    (r"mamba/conv_b$", [TP]),
    # xlstm cells
    (r"cell/w[qkvif]/w$", [FSDP, TP]),
    (r"cell/(wo_gate|out_proj)/w$", [TP, FSDP]),
    (r"cell/w_in/w$", [FSDP, TP]),
    # generic biases / norms / small vectors: replicate
    (r"(ln\d?|norm|final_norm|kv_norm)/", []),
]

_STACKED_PREFIXES = ("blocks/", "mamba/")  # leading layer dim present


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def strip_axis(spec: P, axis: str) -> P:
    """Remove one mesh axis from a spec (e.g. drop FSDP for serving)."""
    out = []
    for entry in spec:
        if entry == axis:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a != axis)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(entry)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_spec(path: str, shape, mesh: Mesh, fsdp: bool = True) -> P:
    """``fsdp=False`` drops the ``data``-axis (ZeRO) sharding — the serving
    profile: weights live TP-sharded and are never re-gathered per step."""
    lead = 1 if path.startswith(_STACKED_PREFIXES) else 0
    core_shape = shape[lead:]
    spec = None
    for pat, prefs in _PARAM_RULES:
        if re.search(pat, path):
            spec = greedy_spec(core_shape, prefs, mesh)
            break
    if spec is None:
        # generic fallback: biggest dim -> model, next -> data (if divisible)
        if len(core_shape) >= 2 and math.prod(core_shape) >= 1 << 16:
            order = sorted(range(len(core_shape)), key=lambda i: -core_shape[i])
            prefs = [[] for _ in core_shape]
            prefs[order[0]] = TP
            if len(order) > 1:
                prefs[order[1]] = FSDP
            spec = greedy_spec(core_shape, prefs, mesh)
        else:
            spec = P()
    if not fsdp:
        spec = strip_axis(spec, "data")
    return P(*([None] * lead + list(spec)))


def param_shardings(params_tree, mesh: Mesh, fsdp: bool = True):
    """Map a pytree of arrays/SDS to NamedShardings via the rules."""
    def one(path, leaf):
        spec = param_spec(_path_str(path), leaf.shape, mesh, fsdp=fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_tree)


# ---------------------------------------------------------------------------
# Input / activation / cache rules
# ---------------------------------------------------------------------------
BATCH = (("pod", "data"), "data", "pod")   # composite first, then singles


def batch_spec(shape, mesh: Mesh, seq_axis: Optional[int] = None) -> P:
    """Shard dim0 over batch candidates; optionally dim ``seq_axis`` over the
    model axis (sequence parallelism) when batch can't fill the mesh."""
    prefs = [list(BATCH)] + [[] for _ in shape[1:]]
    if seq_axis is not None:
        prefs[seq_axis] = ["model"]
    return greedy_spec(shape, prefs, mesh)


def input_shardings(batch_tree, mesh: Mesh):
    def one(leaf):
        return NamedSharding(mesh, batch_spec(leaf.shape, mesh))

    return jax.tree.map(one, batch_tree)


def cache_shardings(cache_tree, mesh: Mesh, stacked: bool = True):
    """KV/state cache rules. Leaf layouts (possibly with leading layer dim):
    GQA (B, S, H, D) — batch over (pod,data); heads over model, else seq.
    MLA (B, S, r)    — batch; r over model, else seq.
    SSM (B, H, P, N) / (B, H, P) / conv (B, K, C) — batch; heads/C over model.
    """
    def one(path, leaf):
        shape = leaf.shape
        path_s = _path_str(path)
        if path_s.endswith("offset") or leaf.ndim == 0:
            return NamedSharding(mesh, P())
        lead = 0
        core = list(shape)
        # detect stacked leading layer dim: heuristic — first dim that is the
        # layer count comes before batch; caches built by *_cache_spec put
        # layers first for stacked trees.
        if stacked and ("layers/" in path_s or path_s.startswith("mamba")
                        or path_s.startswith("attn")):
            lead = 1
            core = list(shape[1:])
        prefs = [[] for _ in core]
        prefs[0] = list(BATCH)
        priority = None
        if len(core) == 4:      # (B, S, H, D) or (B, H, P, N)
            if "mamba" in path_s or path_s.endswith(("C", "h")):
                prefs[1] = ["model"]            # heads
            else:
                prefs[2] = ["model"]            # kv heads first ...
                prefs[1] = ["model"]            # ... else sequence
                priority = [0, 2, 1, 3]
        elif len(core) == 3:    # (B, S, r) or (B, K, C) or (B, H, P)
            prefs[2] = ["model"]
            prefs[1] = ["model"]
            priority = [0, 2, 1]
        elif len(core) == 2:
            prefs[1] = ["model"]
        spec = greedy_spec(core, prefs, mesh, priority)
        return NamedSharding(mesh, P(*([None] * lead + list(spec))))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


# ---------------------------------------------------------------------------
# Fleet (FCPO agent-axis) rules
# ---------------------------------------------------------------------------
# Agent-stacked leaves (A, ...): the agent axis is the fleet's data
# parallelism — spread over (pod, data) when A fills both, else data alone.
AGENT = (("pod", "data"), "data")
# Per-pod base networks (P, ...): the FL hierarchy. Pods ride the mesh's
# ``pod`` axis when present (multi-pod production mesh); on a 2D mesh the
# ``data`` candidate only engages when P divides the data axis size —
# otherwise the (small) base networks replicate, which is always valid.
POD = ("pod", "data")


def agent_spec(shape, mesh) -> P:
    """Shard an agent-stacked leaf's leading dim over the agent candidates;
    trailing (per-agent) dims are tiny and stay replicated."""
    if not shape:
        return P()
    return greedy_spec(shape, [list(AGENT)], mesh)


def pod_spec(shape, mesh) -> P:
    """Shard a per-pod leaf's leading dim over the FL-hierarchy candidates."""
    if not shape:
        return P()
    return greedy_spec(shape, [list(POD)], mesh)


def agent_batch_spec(shape, mesh, agent_axis: int = 1) -> P:
    """Episode-major driver inputs, e.g. rates (n_eps, A, n_steps): shard the
    *agent* dim over the agent candidates, replicate the scan/time dims."""
    prefs = [[] for _ in shape]
    if agent_axis < len(shape):
        prefs[agent_axis] = list(AGENT)
    return greedy_spec(shape, prefs, mesh)


def ambient_mesh():
    """The mesh in context at trace time (``jax.set_mesh``); None when
    there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def agent_axes(n_agents: int, mesh):
    """The mesh axes a fleet of ``n_agents`` is sharded over (the leading
    entry of ``agent_spec``), or None when it is replicated."""
    spec = agent_spec((n_agents,), mesh)
    return spec[0] if len(spec) else None


def shard_hint(x, *dim_prefs, priority=None):
    """Divisibility-aware ``with_sharding_constraint`` against the AMBIENT
    mesh; a silent no-op when no mesh is in context (tests, single device).

    ``dim_prefs``: per-dim candidate lists as in ``greedy_spec`` (trailing
    dims may be omitted -> replicated).
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    prefs = list(dim_prefs) + [[]] * (x.ndim - len(dim_prefs))
    spec = greedy_spec(x.shape, prefs, mesh, priority)
    return jax.lax.with_sharding_constraint(x, spec)


def agent_hint(x):
    """Constrain an agent-stacked intermediate (A, ...) to the fleet's agent
    placement inside jit. With these hints on both sides of the Alg. 1
    segment-sums, XLA's SPMD partitioner lowers the pod aggregation to a
    reduce-scatter + gather over the mesh instead of a full-replica
    reshape. No-op without an ambient mesh."""
    return shard_hint(x, list(AGENT))


def pod_hint(x):
    """Constrain a per-pod intermediate (P, ...) to the FL-hierarchy
    placement inside jit (see ``agent_hint``). No-op without a mesh."""
    return shard_hint(x, list(POD))


def logits_shardings(mesh: Mesh):
    return NamedSharding(mesh, greedy_spec(
        (1 << 30, 1, 1 << 30), [list(BATCH), [], ["model"]], mesh))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
