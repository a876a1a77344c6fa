"""Flight recorder: XLA cost/memory accounting, request-grade latency
attribution, and the Chrome trace-event writer they export through.

* ``repro.obs.profile`` — ``cost_analysis``/``memory_analysis`` of the
  compiled fleet scan and each kernel variant, plus the donation audit;
  persisted via ``benchmarks.common.save_bench`` as ``BENCH_profile``.
* ``repro.obs.requests`` — sampled per-request lifecycle records
  reconstructed from the twin's monotone stage counters, decomposing
  tail latency into per-stage queueing / service / batching delay.
* ``repro.obs.trace`` — Chrome trace-event JSON (Perfetto /
  chrome://tracing) for those request timelines.

Phase timing of the training program is the JAX profiler's: named scopes
inside the compiled scan and ``TraceAnnotation`` host spans in
``core.fleet.train_fleet_scan`` (see ``repro.obs.trace``). ``profile`` and
``requests`` sit above ``core``/``sim`` and must not be imported from them.
"""
from repro.obs.trace import Tracer, validate_chrome_trace

__all__ = ["Tracer", "validate_chrome_trace"]
