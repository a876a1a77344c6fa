"""Kernel: the pipeline twin's ``pipeline_advance`` calls (one per control
interval, every stage of every camera), bytes each must move
(``bench/costs_sim.py``: state in and out, arrivals, caps and the fan-out's
objects and phase in, the phase out) over the kernel's device time, as a
share of the chip's HBM bandwidth."""
from bench import costs_sim


def read(ctx):
    calls, secs = ctx["trace"].kernel("pipeline_advance")
    if not calls or not secs:
        return None
    per_chip = ctx["agents"] // ctx["chips"]
    moved = calls * costs_sim.pipeline_advance_bytes(per_chip,
                                                     ctx["config"]["twin"])
    return 100.0 * moved / secs / ctx["peaks"]["hbm_bytes_per_s"]
