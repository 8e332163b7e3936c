"""Find the knee of an open-loop serving cell: run its traffic at a few
fixed rates in one process on the chip and report, per rate, how the
system moved over several windows after the cell's own warm-up.

    python3 -m bench.sweep --workload qwen1.5-0.5b.chat \
        --rates 0.35,0.45,0.55,0.65 --seed 7

Each rate gets a fresh engine (same weights, compiled programs reused),
so no rate inherits another's queue.  Arrivals run for the traffic
file's ``warmup_s`` and then for ``WINDOWS`` windows of the cell's
``run_seconds``; the readings place the knee only where ``warmup_s`` is
longer than a request lives, as the cell's own window needs too.  Per window: requests
due and completed, requests in the system and slots held at its start
and end, and the tails.  The knee is the highest rate at which the
windows complete about what falls due and the requests in the system
stop growing; the cell's traffic file fixes its rate at about 0.8 of
it.  One JSON line per window on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

from bench import generate, harness

WINDOWS = 2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload, seed=args.seed)
    devs = harness.require_chips(cell.workload["chips"])
    harness.use_program()
    loop = harness.load_by_name("loops", cell.traffic["loop"])
    span = WINDOWS * cell.seconds
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic, rate_per_s=rate)
        eng, mcfg = loop.build(cell, args.seed)
        loop.warm_up(eng, mcfg.vocab, args.seed)
        reqs = generate.open_loop(traffic, span, args.seed, mcfg.vocab)
        spans = harness.Spans()
        out = loop.serve(eng, reqs, warmup_s=traffic["warmup_s"],
                         seconds=span, spans=spans)
        tr = out["tracker"]
        ws = out["window"][0]
        for k in range(WINDOWS):
            a, b = ws + k * cell.seconds, ws + (k + 1) * cell.seconds
            nums = loop.window_numbers(dict(out, window=(a, b)))
            done = sum(1 for rid in tr.reason
                       if tr.times.get(rid) and a <= tr.times[rid][-1] <= b)
            steps = spans.of("engine.step", a, b)
            print(json.dumps({
                "rate_per_s": rate, "window": k,
                "starts_s": a - (ws - traffic["warmup_s"]),
                "due": nums["attempted"], "completed": done,
                "in_system": [loop.in_system(out, a), loop.in_system(out, b)],
                "slots_held": [loop.slots_held(out, a),
                               loop.slots_held(out, b)],
                "ttft_p90_s": harness.percentile(nums["ttft"], 90),
                "itl_p95_ms": 1e3 * (harness.percentile(nums["gaps"], 95)
                                     or 0.0),
                "engine_step_ms": 1e3 * sum(e - s for s, e in steps)
                / max(len(steps), 1),
                "device": devs[0].device_kind}), flush=True)
        del eng, tr, out
        gc.collect()
        time.sleep(1.0)


if __name__ == "__main__":
    main()
