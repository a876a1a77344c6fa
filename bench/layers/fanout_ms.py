"""Twin evaluation: device time of the ops under the ``sim_fanout`` scope
(the frames' spread into microticks and each later stage's fan-out of its
upstream's completions), per simulated control interval of the whole
fleet."""
from bench import program_spans_sim


def read(ctx):
    return program_spans_sim.scope_ms_per_interval(ctx, "sim_fanout")
