"""The one traffic generator: it reads a mix's parameters
(bench/traffic/<mix>.json) and makes the requests or batches of a run.

Every seed gets the same work in another order.  The sizes and the gaps
between arrivals are drawn once from the mix's own ``sizes_seed``; the
run's ``--seed`` only permutes them and draws the token ids.  So two
seeds differ in what a run measures only by order, and the spread between
runs is the system's, not the draw's.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # offset from the start of arrivals
    prompt: list[int]
    max_new: int


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    """``n`` log-normal lengths with the given median and sigma, rounded
    and clipped to [min, max]."""
    raw = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def request_count(traffic: dict, seconds: float) -> int:
    """Enough arrivals to cover warm-up, window and a tail past it."""
    span = traffic["warmup_s"] + seconds + traffic.get("tail_s", 10.0)
    return int(math.ceil(traffic["rate_per_s"] * span))


def open_loop(traffic: dict, seconds: float, seed: int, vocab: int
              ) -> list[Request]:
    """Poisson arrivals at the mix's fixed rate; log-normal prompt and
    output lengths; token ids uniform over the vocabulary."""
    n = request_count(traffic, seconds)
    base = np.random.default_rng(traffic["sizes_seed"])
    plens = lengths(traffic["prompt"], n, base)
    outs = lengths(traffic["output"], n, base)
    gaps = base.exponential(1.0 / traffic["rate_per_s"], n)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    plens, outs = plens[order], outs[rng.permutation(n)]
    due = np.cumsum(gaps[rng.permutation(n)])
    return [Request(rid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab, int(plens[i])).tolist(),
                    max_new=int(outs[i]))
            for i in range(n)]


class TrainData:
    """Batches for ``Trainer(data=...)``: ``batch(step)`` is a pure
    function of (seed, step), made on the device in one jitted call.

    Tokens are uniform over the vocabulary; a share ``mask_rate`` of them
    is replaced by ``mask_id`` and the labels are the original tokens, as
    in BERT's masked-LM objective (here scored at every position, as the
    program's loss has no mask)."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        import jax
        import jax.numpy as jnp

        from bench.harness import jax_key
        b, s = traffic["batch"], traffic["seq"]
        rate, mask_id = traffic["mask_rate"], traffic["mask_id"]
        self.key = jax_key(seed)

        def make(key, step):
            k = jax.random.fold_in(key, step)
            kt, km = jax.random.split(k)
            labels = jax.random.randint(kt, (b, s), 0, vocab, jnp.int32)
            masked = jax.random.bernoulli(km, rate, (b, s))
            return jnp.where(masked, mask_id, labels), labels

        self._make = jax.jit(make)

    def batch(self, step: int):
        return self._make(self.key, step)
