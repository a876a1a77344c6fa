"""CRL episode: device time of the ops under the ``fcpo_rollout`` scope
(the step scan: policy forward and sampling, the environment's observe and
step; in the twin the K microticks and ``queue_advance``), per episode."""
from bench import program_spans


def read(ctx):
    return program_spans.scope_ms_per_episode(ctx, "fcpo_rollout")
