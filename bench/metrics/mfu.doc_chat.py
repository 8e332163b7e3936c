"""The serving steps' share of the chip's peak for a model whose MoE
layers hold a share of the experts: 2 operations per matmul parameter a
token passes on this chip, over the summed time of the window's engine
steps, over the bf16 peak.  A token passes the non-expert body (prompt
tokens skip the head), and one routed expert for each of its top-k slots
in each MoE layer that lands on a held expert: the window's tokens times
MoE layers times top-k, scaled by the held share of routed rows.  That
share is the engine's ``moe_held_rows / moe_routed_rows``, counted over
the whole run (the warm-up and the arrivals before the window too), not
over the window alone; the token counts are the window's.  Attention's
quadratic term and MLA's latent reads are left out.  Nothing to read (no
held-row counters): None.  Moves ``itl_p95_ms``."""
from bench import peaks


def read(run):
    steps = run.step_spans("engine.step")
    c, n = run.counters, run.n_active
    pre, dec = c.get("prefill_tokens", 0), c.get("decode_tokens", 0)
    routed = c.get("moe_routed_rows")
    if not steps or pre + dec == 0 or not routed or "expert" not in n:
        return None
    rows = ((pre + dec) * n["moe_layers"] * n["top_k"]
            * c["moe_held_rows"] / routed)
    flops = (2 * n["body"] * (pre + dec) + 2 * n["head"] * dec
             + 2 * n["expert"] * rows)
    busy = sum(b - a for a, b in steps)
    return 100.0 * flops / busy / peaks.device_peaks(run.device_kind)["flops"]
