"""Twin evaluation's host entry: wall time of ``sim.call`` per dispatch,
the jitted evaluation's call (argument checks, enqueueing the program); it
returns before the device finishes."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "sim.call")
