"""Twin evaluation: device time of the ops under the ``sim_policy`` scope
(observe, the iAgents' forward pass and sampling, the action decode), per
simulated control interval of the whole fleet."""
from bench import program_spans_sim


def read(ctx):
    return program_spans_sim.scope_ms_per_interval(ctx, "sim_policy")
