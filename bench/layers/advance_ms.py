"""Twin evaluation: device time of the ops under the ``sim_advance`` scope
(every stage's K microticks, through ``pipeline_advance``), per simulated
control interval of the whole fleet."""
from bench import program_spans_sim


def read(ctx):
    return program_spans_sim.scope_ms_per_interval(ctx, "sim_advance")
