"""Run one cell of the benchmark on the chip and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a device trace of the
window.  The run exits non-zero, printing no result, unless JAX's first
device is a TPU and there are as many as the cell asks for.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
last ``compared``, the numbers ``correct`` was judged by with their
limits, which also end standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's "
                    "run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace))
    devs = harness.require_chips(cell.workload["chips"])
    cache = harness.use_program()
    clock = harness.CompileClock()
    harness.log(f"bench: {cell.name} seed {cell.seed} on "
                f"{devs[0].device_kind} x{len(devs)}, compile cache {cache}")
    loop = harness.load_by_name("loops", cell.traffic["loop"])
    out = loop.run(cell, devs, T0, clock)
    harness.log(f"bench: compiled {clock.count} programs in "
                f"{clock.seconds:.1f}s; run took "
                f"{time.perf_counter() - T0:.1f}s")
    for k, v in out["compared"].items():
        harness.log(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
