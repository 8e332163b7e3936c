"""Tiny versions of the benchmark's cells for CPU tests: the real files,
with widths, lengths, slots and window cut so a run takes seconds."""
from __future__ import annotations

import sys

from bench import harness

SRC = str(harness.ROOT / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def tiny_cell(name: str, seed: int = 12345678901, seconds: float = 1.5):
    cell = harness.load_cell(name, seed=seed, seconds=seconds)
    c = cell.config
    if c["family"] == "dense_decoder":
        c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=128,
                 vocab_size=512, max_position_embeddings=256)
        c["run"]["engine"].update(n_slots=4, num_blocks=33, prefill_chunk=64)
        c["run"]["check"] = {"requests": 4, "tokens": 40}
        cell.traffic.update(rate_per_s=20.0, warmup_s=0.5, tail_s=10.0)
        cell.traffic["prompt"].update(median=20, min=4, max=100)
        cell.traffic["output"].update(median=6, min=2, max=20)
    else:
        c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                 intermediate_size=128, vocab_size=512,
                 max_position_embeddings=64)
        cell.traffic.update(batch=4, seq=64)
    return cell


def run_tiny(cell) -> dict:
    """A whole run of the cell's loop on the CPU, past the look for a
    chip."""
    import time

    import jax
    loop = harness.load_by_name("loops", cell.traffic["loop"])
    return loop.run(cell, jax.devices(), time.perf_counter(),
                    harness.CompileClock())
