"""Operations and bytes the measured work needs, computed from shapes.

These are the yardstick's counts: a kernel's roofline share divides the
bytes a call must move by its device time, and ``step_mfu`` multiplies the
iAgent's model operations per agent-interval by the measured rate. All
sizes come from the configuration file (``bench/configs/<name>.json``).
"""
from __future__ import annotations

SIM_NCOUNTERS = 12   # twin counters per agent (int32)
SIM_NCAPS = 6        # decoded action caps per agent (float32)
ADAM_FLOPS_PER_PARAM = 12


def iagent_layers(iagent):
    """(fan_in, fan_out) of every dense layer of the iAgent (Fig. 4): the
    backbone, the value head and the three cascaded action heads, the
    batch-size and threading heads reading the features plus the
    resolution head's softmax."""
    s, h, f = iagent["state_dim"], iagent["hidden_dim"], iagent["feat_dim"]
    r, b, m = iagent["n_res"], iagent["n_bs"], iagent["n_mt"]
    return [(s, h), (h, f), (f, 1), (f, r), (f + r, b), (f + r, m)]


def iagent_params(iagent):
    return sum(i * o + o for i, o in iagent_layers(iagent))


def iagent_forward_flops(iagent):
    """Multiply-adds of one forward pass, two operations each."""
    return 2 * sum(i * o for i, o in iagent_layers(iagent))


def train_flops_per_interval(iagent, rl, fl):
    """Model operations of training per agent-interval: one forward to act,
    forward and backward (three forwards' worth) over the episode's samples
    in the CRL update, and per FL round the per-head losses (one forward
    per sample) and ``finetune_steps`` forward-backward passes; Adam on
    every parameter for each update. Recomputed forwards do not count."""
    fwd = iagent_forward_flops(iagent)
    n_steps, fl_every = rl["n_steps"], fl["fl_every"]
    steps_per_round = n_steps * fl_every
    adam = ADAM_FLOPS_PER_PARAM * iagent_params(iagent)
    act = fwd
    update = 3 * fwd + adam / n_steps
    round_ = (fwd * n_steps + fl["finetune_steps"] * (3 * fwd * n_steps + adam)
              ) / steps_per_round
    return act + update + round_


def queue_advance_bytes(n_agents, twin):
    """HBM bytes one ``queue_advance`` call moves for ``n_agents``: every
    agent's twin state in (ring, counters, credits, latency sum, histogram),
    its per-tick arrivals and caps in, and the state out."""
    state = 4 * (twin["ring"] + SIM_NCOUNTERS + 2 + 1 + twin["hist_n"])
    inputs = 4 * (twin["k_ticks"] + SIM_NCAPS)
    return n_agents * (2 * state + inputs)


def delta_codec_bytes(n_agents, iagent):
    """HBM bytes the ``delta_codec`` calls of one FL round move: per leaf, the
    float32 delta and residual in, the decoded delta and new residual out,
    summed over every parameter of every agent."""
    return n_agents * iagent_params(iagent) * 4 * 4
