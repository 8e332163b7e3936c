"""The paged split-KV decode kernel's share of its roofline: the least
time its calls in the window need (live rows and keys only,
bench/flops/flash_decode_paged.py, against the chip's peaks) over its
device time in the trace.  Moves ``itl_p95_ms``."""
from bench.harness import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_decode_paged")
