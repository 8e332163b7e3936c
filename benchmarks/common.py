"""Shared benchmark utilities: timing, HLO op counting, CSV rows."""
from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds (jit'd fns: call once to
    compile first)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def hlo_op_counts(fn: Callable, *args) -> dict[str, int]:
    """Count optimized-HLO ops by kind — the TPU analogue of datapath
    area: how many distinct hardware operations the program needs."""
    import re
    txt = jax.jit(fn).lower(*args).compile().as_text()
    counts: dict[str, int] = {}
    for line in txt.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*.*?\s([\w\-]+)\(", line)
        if m:
            op = m.group(1)
            counts[op] = counts.get(op, 0) + 1
    return counts


def total_real_ops(counts: dict[str, int]) -> int:
    """Ops that map to datapath work (exclude pure bookkeeping)."""
    skip = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant",
            "after-all", "copy"}
    return sum(v for k, v in counts.items() if k not in skip)
