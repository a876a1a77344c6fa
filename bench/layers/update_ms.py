"""CRL episode: device time of the ops under the ``fcpo_update`` scope (the
gated PPO update, ``agent_update``), per episode."""
from bench import program_spans


def read(ctx):
    return program_spans.scope_ms_per_episode(ctx, "fcpo_update")
