"""Host entry: wall time of ``fleet.call`` per dispatch, the jitted scan's
call (argument checks, buffer handover, enqueueing the program); it
returns before the device finishes."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "fleet.call")
