"""Twin evaluation's host entry: wall time of ``sim.summary`` per
dispatch, the eager request-grade summary of the final twin state and its
censoring check."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "sim.summary")
