"""Readings for setting a cell's comparison limits, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 4,5,6] [--fault half_batch --fault-seeds 7,8,9]

In one process: for every seed, the cell's set-up at the cell's own size,
then the numbers the comparison reads; with ``--control-seeds``, the
reference computed in bfloat16 put in the program's place; with
``--fault``, the program with that fault planted (``bench/faults.py``).
Prints one JSON line per seed and kind; the limits in
``bench/limits/<cell>.json`` are set from them (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def seeds(text):
    return [int(x) for x in text.split(",") if x]


def readings(cell, seed, require_tpu, n_agents, control=False):
    *_, entry = run.prepare(cell, seed, require_tpu, n_agents)
    entry.setup()
    entry.free()
    return {k: v[0] for k, v in entry.numbers(control=control).items()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--agents", type=int, default=None,
                    help="fleet size (default: the configuration's)")
    ap.add_argument("--cpu", action="store_true",
                    help="skip the look for a chip (a rehearsal)")
    args = ap.parse_args(argv)
    tpu = not args.cpu
    out = lambda kind, seed, nums: print(json.dumps(
        {"kind": kind, "seed": seed, "numbers": nums}), flush=True)
    for s in args.seeds:
        out("program", s, readings(args.workload, s, tpu, args.agents))
    for s in args.control_seeds:
        out("control", s, readings(args.workload, s, tpu, args.agents,
                                   control=True))
    from bench import faults

    kind = args.workload.split(".")[0]
    for name in args.fault:
        with faults.FAULTS[kind][name]():
            for s in args.fault_seeds:
                out(f"fault:{name}", s, readings(args.workload, s, tpu,
                                                 args.agents))
    return 0


if __name__ == "__main__":
    sys.exit(main())
