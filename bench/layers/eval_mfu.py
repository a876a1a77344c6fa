"""Device (whole step): the iAgent's forward-pass operations per
agent-interval (from the configuration's widths) times the agent-intervals
the traced window evaluated, over the window and the chips' bf16 peak."""
from bench import costs


def read(ctx):
    tr = ctx["trace"]
    if not tr.window_s or not ctx["intervals"]:
        return None
    rate = ctx["intervals"] / tr.window_s
    flops = costs.iagent_forward_flops(ctx["config"]["iagent"])
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
