"""Readings that the limits of ``correct`` are set from, on the chip at
the cell's own size: the program's numbers over many seeds, the control's
(the reference one precision step below the configuration's) and each
planted fault's (bench/faults.py).  The benchmark's own runs never run
this.

    python3 -m bench.calibrate --workload qwen1.5-0.5b.chat \
        --seeds 11,12,13 [--faults token_altered] [--seconds 51]

One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json

from bench import harness


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, seconds=args.seconds)
    devs = harness.require_chips(cell.workload["chips"])
    harness.use_program()
    loop = harness.load_by_name("loops", cell.traffic["loop"])
    faults = tuple(f for f in args.faults.split(",") if f)
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell.seed = seed
        row = loop.readings(cell, seed, faults)
        print(json.dumps({"seed": seed, "device": devs[0].device_kind,
                          **row}), flush=True)


if __name__ == "__main__":
    main()
