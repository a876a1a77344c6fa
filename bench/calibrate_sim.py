"""Readings for setting a twin-evaluation cell's comparison limits, on the
chip: ``bench/calibrate.py``'s, with the faults of ``bench/faults_sim.py``.

    python bench/calibrate_sim.py --workload sim.reid-twin.burst \\
        --seeds 1,2,3 [--control-seeds 4,5,6] \\
        [--fault no_fanout --fault-seeds 7,8,9]

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

from bench import calibrate  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=calibrate.seeds, default=[])
    ap.add_argument("--control-seeds", type=calibrate.seeds, default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", type=calibrate.seeds, default=[])
    ap.add_argument("--agents", type=int, default=None,
                    help="fleet size (default: the configuration's)")
    ap.add_argument("--cpu", action="store_true",
                    help="skip the look for a chip (a rehearsal)")
    args = ap.parse_args(argv)
    tpu = not args.cpu
    out = lambda kind, seed, nums: print(json.dumps(
        {"kind": kind, "seed": seed, "numbers": nums}), flush=True)
    read = lambda s, **kw: calibrate.readings(args.workload, s, tpu,
                                              args.agents, **kw)
    for s in args.seeds:
        out("program", s, read(s))
    for s in args.control_seeds:
        out("control", s, read(s, control=True))
    from bench import faults_sim

    kind = args.workload.split(".")[0]
    for name in args.fault:
        with faults_sim.FAULTS[kind][name]():
            for s in args.fault_seeds:
                out(f"fault:{name}", s, read(s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
