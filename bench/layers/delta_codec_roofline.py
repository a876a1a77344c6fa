"""Kernel: the int8 FL codec's ``delta_codec`` calls (one per parameter
leaf per round), the float32 delta and residual in and the decoded delta
and residual out (from shapes), over the kernels' device time, as a share
of the chip's HBM bandwidth."""
from bench import costs


def read(ctx):
    calls, secs = ctx["trace"].kernel("delta_codec")
    if not calls or not secs or not ctx["rounds"]:
        return None
    per_chip = ctx["agents"] // ctx["chips"]
    moved = ctx["rounds"] * costs.delta_codec_bytes(per_chip,
                                                    ctx["config"]["iagent"])
    return 100.0 * moved / secs / ctx["peaks"]["hbm_bytes_per_s"]
