"""Host entry: wall time of ``fleet.prep`` per dispatch, the host's part of
``train_fleet_scan`` before the call (FL schedule, straggler and fault
draws from episode 0 to the offset, the rates' reshape, each scan input's
transfer to the device)."""
from bench import program_spans


def read(ctx):
    return program_spans.host_span_ms(ctx["trace"], "fleet.prep")
