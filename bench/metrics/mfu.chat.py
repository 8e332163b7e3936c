"""The serving steps' share of the chip's peak: the operations the model
needs for the tokens it ran through in the window, 2 per matmul parameter
a token passes (prompt tokens skip the output head; attention's quadratic
term is left out), over the summed time of the window's engine steps
(waits for arrivals excluded), over the bf16 peak.  Moves ``itl_p95_ms``."""
from bench import peaks


def read(run):
    steps = run.step_spans("engine.step")
    pre = run.counters.get("prefill_tokens", 0)
    dec = run.counters.get("decode_tokens", 0)
    if not steps or pre + dec == 0:
        return None
    n = run.n_active
    flops = 2 * n["body"] * (pre + dec) + 2 * n["head"] * dec
    busy = sum(b - a for a, b in steps)
    return 100.0 * flops / busy / peaks.device_peaks(run.device_kind)["flops"]
