"""Twin evaluation's host entry: wall time of ``sim.prep`` per dispatch,
``simulate_fleet``'s input checks and the queue caps' fetch for the
ring-capacity check."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "sim.prep")
