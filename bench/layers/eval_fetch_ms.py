"""Twin evaluation's host entry: wall time of ``sim.fetch`` per dispatch,
the per-interval history's fetch, which waits for the device to finish
the chunk."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "sim.fetch")
