"""Paper reproduction driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig4]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
The chip benchmark is ``bench/`` (``python3 -m bench.run``); these
sections reproduce the paper's Table I accuracy, Table II and Fig. 4.
"""
from __future__ import annotations

import argparse
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,table2,fig4")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (fig4_combined_savings, table1_accuracy,
                   table2_dualmode_overhead)
    sections = {
        "table1": table1_accuracy.main,
        "table2": table2_dualmode_overhead.main,
        "fig4": fig4_combined_savings.main,
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    print("name,us_per_call,derived")
    failures = []
    for name in chosen:
        try:
            sections[name]()
        except Exception:  # noqa: BLE001 — report all sections
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
