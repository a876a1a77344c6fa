"""jit'd public wrappers for the Pallas kernels.

Dispatch is backend-aware: on CPU the kernels execute with
``interpret=True`` (Pallas interpreter — same kernel body, Python/XLA-CPU
execution); on any accelerator backend the same call sites compile (TPU ->
Mosaic, GPU -> Triton). To see what Mosaic makes of a kernel without a chip,
compile it ahead of time for a described TPU (tests/test_tpu_compile.py).
The model code defaults to the jnp reference path under dry-run
(identical math — see DESIGN.md §6) and switches to these via
``use_pallas=True``.

Each wrapper is one ``jax.jit`` of its ``_<kernel>_impl`` function, so a
profiler trace names the kernel's op after it (``_queue_advance_impl.<n>``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.kernels import decode_attention as _dec
from repro.kernels import delta_codec as _codec
from repro.kernels import diversity as _div
from repro.kernels import flash_attention as _fa
from repro.kernels import packing as _pack
from repro.kernels import queue_advance as _qa


def _interpret_default() -> bool:
    """Backend-aware kernel dispatch: interpret on CPU (no Pallas lowering
    there), compiled Pallas on every accelerator backend (TPU -> Mosaic,
    GPU -> Triton)."""
    return jax.default_backend() == "cpu"


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


def _pipeline_advance_impl(arrive, counters, credits, lat_sum, hist,
                           arrivals, caps, objects, phase, *, stages):
    return _qa.pipeline_advance(arrive, counters, credits, lat_sum, hist,
                                arrivals, caps, objects, phase, stages=stages,
                                interpret=_interpret_default())


_FLASH = jax.jit(_flash_impl, static_argnames=("causal", "bq", "bk"))
_DECODE = jax.jit(_decode_impl, static_argnames=("bk",))
_PACK = jax.jit(_pack_impl)
_DIVERSITY = jax.jit(_diversity_impl, static_argnames=("alpha", "beta",
                                                       "ridge"))
_DELTA_CODEC = jax.jit(_delta_codec_impl, static_argnames=("codec", "k"))
_QUEUE_ADVANCE = jax.jit(_queue_advance_impl)
_PIPELINE_ADVANCE = jax.jit(_pipeline_advance_impl,
                            static_argnames=("stages",))


def _dispatch(kernel, args, kw, agent_batched=None):
    """Call the jitted ``kernel``. ``agent_batched`` marks a fleet
    kernel: True when ``args`` lead with the agent dim, False when they are
    one agent's (the call then sits under the fleet's ``vmap``). Under an
    ambient mesh such a kernel runs in ``shard_map``, each device on its
    own agents: Mosaic kernels cannot be partitioned automatically, and
    agents are independent. Unbatched operands are replicated there; the
    fleet's ``vmap(spmd_axis_name=...)`` shards the agent dim it adds."""
    call = lambda *a: kernel(*a, **kw)
    mesh = None if agent_batched is None else shd.ambient_mesh()
    if mesh is None:
        return call(*args)
    agent = P(shd.agent_axes(args[-1].shape[0], mesh)) if agent_batched \
        else P()
    return jax.shard_map(call, mesh=mesh, in_specs=(agent,) * len(args),
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


@jax.custom_batching.custom_vmap
def queue_advance(arrive, counters, credits, lat_sum, hist, arrivals, caps):
    """Fused request-level data-plane advance (digital twin): admit ->
    pre-process -> batch-form -> inference -> post-process -> deadline check,
    K microticks for a block of agents per grid step, one kernel call for
    the whole agent batch. Operands are one agent's (``lat_sum`` a scalar)
    or lead with the agent dim. Under ``vmap`` (the fleet's, over one
    agent's call) the batching rule below makes it one call on the batch.
    Oracle: ``repro.kernels.ref.queue_advance_ref``."""
    return _dispatch(_QUEUE_ADVANCE,
                     (arrive, counters, credits, lat_sum, hist, arrivals,
                      caps), {}, agent_batched=lat_sum.ndim > 0)


@queue_advance.def_vmap
def _queue_advance_vmap(axis_size, in_batched, *args):
    args = [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(args, in_batched)]
    return queue_advance(*args), (True,) * 5


def pipeline_advance(arrive, counters, credits, lat_sum, hist, arrivals,
                     caps, objects, phase, *, stages):
    """Every stage of a pipeline fleet through one control interval in one
    kernel call, each later stage fed the fan-out of the completions one
    stage up in the tick they happen. Operands lead with the stage-major
    agents. Oracle: ``sim/harness``'s stage-by-stage jnp route
    (``sim_interval_recorded`` and ``sim.state.fanout_arrivals``)."""
    return _PIPELINE_ADVANCE(arrive, counters, credits, lat_sum, hist,
                             arrivals, caps, objects, phase, stages=stages)


# name -> jit wrapper — the profiler (repro.obs.profile) uses these to lower
# and cost/memory-account every kernel variant; they are the exact objects
# the dispatchers call, so the analyzed program is the one that runs.
KERNEL_JITS = {
    "flash_attention": _FLASH,
    "decode_attention": _DECODE,
    "pack": _PACK,
    "diversity_insert": _DIVERSITY,
    "delta_codec": _DELTA_CODEC,
    "queue_advance": _QUEUE_ADVANCE,
    "pipeline_advance": _PIPELINE_ADVANCE,
}
