"""Operations and bytes that one paged decode tick needs from the
split-KV decode kernel, for one layer.

Each live row holds one query token and ``kv`` cached keys (its own new
key included).  Counted are the live rows and their live keys only: rows
of free slots and table entries past a row's length are not work.  Two
matmuls of ``head_dim`` multiply-adds per key and head, two operations
each; bytes are K and V of the live keys plus each row's query and
output.
"""
from __future__ import annotations

# the kernel's name as the device trace carries it
MATCH = "_flash_decode_paged_jit"


def cost(call: dict, dims: dict) -> tuple[float, float]:
    kvs = call["kv"]
    h, kv, hd, item = (dims["heads"], dims["kv_heads"], dims["head_dim"],
                       dims["itemsize"])
    keys = sum(kvs)
    flops = 4.0 * h * hd * keys
    moved = item * (2 * keys * kv * hd + 2 * len(kvs) * h * hd)
    return flops, float(moved)
