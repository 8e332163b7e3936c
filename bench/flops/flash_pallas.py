"""Operations and bytes that one causal prefill chunk needs from the
blocked attention kernel, for one layer.

A chunk of ``n`` real prompt tokens at offset ``c0`` attends its cached
prefix and itself: query ``i`` sees ``c0 + i + 1`` keys.  Counted are the
live pairs only (no padding rows, no masked keys): two matmuls (QK^T and
PV) of ``head_dim`` multiply-adds per pair and head, two operations each.
Bytes are what must cross HBM at least once: the real query rows in, the
output rows out, and K and V of the live prefix.
"""
from __future__ import annotations

# the kernel's name as the device trace carries it
MATCH = "_flash_pallas_jit"


def cost(call: dict, dims: dict) -> tuple[float, float]:
    c0, n = call["c0"], call["n"]
    h, kv, hd, item = (dims["heads"], dims["kv_heads"], dims["head_dim"],
                       dims["itemsize"])
    pairs = n * c0 + n * (n + 1) // 2
    flops = 4.0 * h * hd * pairs
    moved = item * (2 * n * h * hd + 2 * (c0 + n) * kv * hd)
    return flops, float(moved)
