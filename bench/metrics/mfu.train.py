"""The training step's share of the chip's peak: 6 operations per matmul
parameter per token (forward and backward; attention's quadratic term and
recomputation not counted), times tokens per second over the window, over
the bf16 peak.  Moves ``train_tok_s``."""
from bench import peaks


def read(run):
    tps = run.extra.get("tokens_per_s")
    if not tps:
        return None
    n = run.n_active
    flops = 6 * (n["body"] + n["head"]) * tps
    return 100.0 * flops / peaks.device_peaks(run.device_kind)["flops"]
