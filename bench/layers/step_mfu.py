"""Device (whole step): the iAgent's model operations per agent-interval
(from the configuration's widths) times the intervals the traced window
completed, over the window and the chips' bf16 peak."""
from bench import costs


def read(ctx):
    tr = ctx["trace"]
    if not tr.window_s or not ctx["intervals"]:
        return None
    c = ctx["config"]
    per = costs.train_flops_per_interval(c["iagent"], c["rl"], c["fl"])
    rate = ctx["intervals"] / tr.window_s
    return 100.0 * per * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
