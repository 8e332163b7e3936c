"""Benchmark driver — one section per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig4]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
from __future__ import annotations

import argparse
import functools
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table1,table2,fig4,"
                         "kernels,flash,roofline")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_kernels, fig4_combined_savings, roofline,
                   table1_accuracy, table2_dualmode_overhead)
    sections = {
        "table1": table1_accuracy.main,
        "table2": table2_dualmode_overhead.main,
        "fig4": fig4_combined_savings.main,
        "kernels": bench_kernels.main,
        "flash": functools.partial(bench_kernels.main_flash,
                                   "BENCH_flash.json"),
        "roofline": roofline.main,
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
