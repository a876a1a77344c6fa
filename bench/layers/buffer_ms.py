"""CRL episode: device time of the ops under the ``fcpo_buffer`` scope
(the episode's candidates into the diversity buffer,
``buffer_insert_batch``), per episode."""
from bench import program_spans


def read(ctx):
    return program_spans.scope_ms_per_episode(ctx, "fcpo_buffer")
