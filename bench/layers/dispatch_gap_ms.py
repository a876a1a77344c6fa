"""Host entry: device-idle time between one dispatch's main program and the
next one's, per dispatch gap (the host's argument prep, the call and the
result fetch that the device waits through)."""


def read(ctx):
    gaps = ctx["trace"].dispatch_gaps()
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
