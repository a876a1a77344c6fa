"""The operation and byte counts the roofline and MFU readers divide by."""
import json
import os

from bench import costs
from bench.run import BENCH


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_iagent_counts_match_the_network():
    ia = _config("fleet-twin")["iagent"]
    # 8->64, 64->48, 48->1, 48->4, 52->7, 52->4 dense layers
    assert costs.iagent_params(ia) == (8 * 64 + 64 + 64 * 48 + 48 + 48 + 1
                                       + 48 * 4 + 4 + 52 * 7 + 7 + 52 * 4 + 4)
    assert costs.iagent_params(ia) == 4524
    assert costs.iagent_forward_flops(ia) == 2 * (512 + 3072 + 48 + 192
                                                  + 364 + 208)


def test_train_flops_per_interval():
    c = _config("fleet-twin")
    fwd, adam = 8792, 12 * 4524
    act, update = fwd, 3 * fwd + adam / 10
    round_ = (fwd * 10 + 2 * (3 * fwd * 10 + adam)) / 20
    assert costs.train_flops_per_interval(c["iagent"], c["rl"], c["fl"]) \
        == act + update + round_


def test_kernel_bytes():
    c = _config("fleet-twin")
    state = 4 * (512 + 12 + 2 + 1 + 64)
    assert costs.queue_advance_bytes(2048, c["twin"]) == \
        2048 * (2 * state + 4 * (20 + 6))
    assert costs.delta_codec_bytes(2048, c["iagent"]) == 2048 * 4524 * 16
