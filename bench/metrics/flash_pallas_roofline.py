"""The blocked prefill kernel's share of its roofline: the least time the
window's prompt chunks need (causal pairs against the cached prefix only,
bench/flops/flash_pallas.py) over the kernel's device time in the trace.
Each chunk shares its engine step with the decode tick, so it moves
``itl_p95_ms``."""
from bench.harness import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_pallas")
