"""Kernel: the twin's ``queue_advance`` calls, bytes each call must move
(every agent's twin state in and out, arrivals and caps in; from shapes)
over the kernel's device time, as a share of the chip's HBM bandwidth."""
from bench import costs


def read(ctx):
    calls, secs = ctx["trace"].kernel("queue_advance")
    if not calls or not secs:
        return None
    per_chip = ctx["agents"] // ctx["chips"]
    moved = calls * costs.queue_advance_bytes(per_chip, ctx["config"]["twin"])
    return 100.0 * moved / secs / ctx["peaks"]["hbm_bytes_per_s"]
