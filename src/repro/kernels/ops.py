"""jit'd public wrappers for the Pallas kernels.

Dispatch is backend-aware: on CPU the kernels execute with
``interpret=True`` (Pallas interpreter — same kernel body, Python/XLA-CPU
execution); on any accelerator backend the same call sites compile (TPU ->
Mosaic, GPU -> Triton). To see what Mosaic makes of a kernel without a chip,
compile it ahead of time for a described TPU (tests/test_tpu_compile.py).
The model code defaults to the jnp reference path under dry-run
(identical math — see DESIGN.md §6) and switches to these via
``use_pallas=True``.

Flight-recorder hook: every wrapper consults
``repro.obs.trace.kernel_trace_tid()``. When it returns None (the default:
no active tracer, or inside an un-instrumented trace) the call goes through
the same cached jit wrapper as before this layer existed — the exact
pre-observability program. When a tracer with ``kernel_spans=True`` is
active at the top level (or an instrumented caller has bound a trace-id via
``bind_tid``), the call routes to a *traced twin* — same kernel, bracketed
by ``kernel/<name>`` spans — jitted separately with the trace-id as a plain
operand, so per-kernel timing never recompiles per tracer and never leaks
into the untraced cache.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.kernels import decode_attention as _dec
from repro.kernels import delta_codec as _codec
from repro.kernels import diversity as _div
from repro.kernels import flash_attention as _fa
from repro.kernels import packing as _pack
from repro.kernels import queue_advance as _qa
from repro.obs import trace as obs_trace


def _interpret_default() -> bool:
    """Backend-aware kernel dispatch: interpret on CPU (no Pallas lowering
    there), compiled Pallas on every accelerator backend (TPU -> Mosaic,
    GPU -> Triton)."""
    return jax.default_backend() == "cpu"


def _twins(name, impl, static_argnames=()):
    """Build (untraced, traced) jitted variants of kernel ``impl``. The
    untraced one is the original wrapper; the traced one takes the trace-id
    as its first (non-static) operand and brackets the kernel with
    ``kernel/<name>`` spans."""
    untraced = functools.partial(jax.jit, static_argnames=static_argnames)(
        impl) if static_argnames else jax.jit(impl)

    def traced_impl(tid, *args, **kw):
        tok = obs_trace.span_begin(f"kernel/{name}", tid, args,
                                   cat="kernel")
        out = impl(*args, **kw)
        obs_trace.span_end(f"kernel/{name}", tid, tok, out)
        return out

    traced = (functools.partial(jax.jit, static_argnames=static_argnames)(
        traced_impl) if static_argnames else jax.jit(traced_impl))
    return untraced, traced


def _flash_impl(q, k, v, *, causal=True, bq=128, bk=128):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=_interpret_default())


def _decode_impl(q, k_cache, v_cache, kv_len, *, bk=512):
    return _dec.decode_attention(q, k_cache, v_cache, kv_len, bk=bk,
                                 interpret=_interpret_default())


def _pack_impl(tokens, indices):
    return _pack.pack(tokens, indices, interpret=_interpret_default())


def _diversity_impl(states, probs, score, filled, s_sum, s_outer, p_sum,
                    n_filled, cand_states, cand_probs, *, alpha, beta,
                    ridge=0.1):
    return _div.diversity_insert(states, probs, score, filled, s_sum,
                                 s_outer, p_sum, n_filled, cand_states,
                                 cand_probs, alpha=alpha, beta=beta,
                                 ridge=ridge, interpret=_interpret_default())


def _delta_codec_impl(delta, residual, *, codec, k=1):
    return _codec.delta_codec(delta, residual, codec=codec, k=k,
                              interpret=_interpret_default())


def _queue_advance_impl(arrive, counters, credits, lat_sum, hist, arrivals,
                        caps):
    return _qa.queue_advance(arrive, counters, credits, lat_sum, hist,
                             arrivals, caps, interpret=_interpret_default())


_FLASH = _twins("flash_attention", _flash_impl, ("causal", "bq", "bk"))
_DECODE = _twins("decode_attention", _decode_impl, ("bk",))
_PACK = _twins("pack", _pack_impl)
_DIVERSITY = _twins("diversity_insert", _diversity_impl,
                    ("alpha", "beta", "ridge"))
_DELTA_CODEC = _twins("delta_codec", _delta_codec_impl, ("codec", "k"))
_QUEUE_ADVANCE = _twins("queue_advance", _queue_advance_impl)


def _dispatch(twins, args, kw, agent_batched=None):
    """Call the untraced or the traced twin. ``agent_batched`` marks a fleet
    kernel: True when ``args`` lead with the agent dim, False when they are
    one agent's (the call then sits under the fleet's ``vmap``). Under an
    ambient mesh such a kernel runs in ``shard_map``, each device on its
    own agents: Mosaic kernels cannot be partitioned automatically, and
    agents are independent. Unbatched operands are replicated there; the
    fleet's ``vmap(spmd_axis_name=...)`` shards the agent dim it adds."""
    tid = obs_trace.kernel_trace_tid()
    if tid is None:
        call = lambda *a: twins[0](*a, **kw)
    else:
        args = (tid,) + tuple(args)
        call = lambda t, *a: twins[1](t, *a, **kw)
    mesh = None if agent_batched is None else shd.ambient_mesh()
    if mesh is None:
        return call(*args)
    agent = P(shd.agent_axes(args[-1].shape[0], mesh)) if agent_batched \
        else P()
    in_specs = tuple(P() if tid is not None and i == 0 else agent
                     for i in range(len(args)))
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=agent, check_vma=False)(*args)


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128):
    return _dispatch(_FLASH, (q, k, v),
                     dict(causal=causal, bq=bq, bk=bk))


def decode_attention(q, k_cache, v_cache, kv_len, *, bk=512):
    return _dispatch(_DECODE, (q, k_cache, v_cache, kv_len), dict(bk=bk))


def pack(tokens, indices):
    return _dispatch(_PACK, (tokens, indices), {})


def diversity_insert(states, probs, score, filled, s_sum, s_outer, p_sum,
                     n_filled, cand_states, cand_probs, *, alpha, beta,
                     ridge=0.1):
    """Fused streaming diversity-buffer insert (Eq. 6): score ->
    argmin-evict -> scatter over T candidates per agent, one kernel call for
    the whole agent batch. Oracle: ``repro.kernels.ref.diversity_insert_ref``."""
    return _dispatch(_DIVERSITY,
                     (states, probs, score, filled, s_sum, s_outer, p_sum,
                      n_filled, cand_states, cand_probs),
                     dict(alpha=alpha, beta=beta, ridge=ridge),
                     agent_batched=states.ndim == 3)


def delta_codec(delta, residual, *, codec, k=1):
    """Fused FL transport codec (error feedback + encode + decode): one
    kernel call per fleet turns the flat (A, L) parameter deltas into their
    lossy on-wire round trip plus the carried residuals. Oracle:
    ``repro.kernels.ref.delta_codec_ref``."""
    return _dispatch(_DELTA_CODEC, (delta, residual),
                     dict(codec=codec, k=k), agent_batched=delta.ndim == 2)


def queue_advance(arrive, counters, credits, lat_sum, hist, arrivals, caps):
    """Fused request-level data-plane advance (digital twin): admit ->
    pre-process -> batch-form -> inference -> post-process -> deadline check,
    K microticks per agent in one kernel call for the whole agent batch.
    Oracle: ``repro.kernels.ref.queue_advance_ref``."""
    return _dispatch(_QUEUE_ADVANCE,
                     (arrive, counters, credits, lat_sum, hist, arrivals,
                      caps), {}, agent_batched=arrive.ndim == 2)


# name -> untraced jit wrapper — the profiler (repro.obs.profile) uses
# these to lower and cost/memory-account every kernel variant; they are the
# exact objects the dispatchers call, so the analyzed program is the one
# that runs.
KERNEL_JITS = {
    "flash_attention": _FLASH[0],
    "decode_attention": _DECODE[0],
    "pack": _PACK[0],
    "diversity_insert": _DIVERSITY[0],
    "delta_codec": _DELTA_CODEC[0],
    "queue_advance": _QUEUE_ADVANCE[0],
}
