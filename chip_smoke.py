"""Smoke run of the serving and training paths, compiled on a TPU.

    python chip_smoke.py               # one chip: serve (float, dual-mode) + train
    python chip_smoke.py --four-chips  # four chips: ring prefill vs one chip

One chip, through the entry points a user calls:

* serve: qwen1.5-0.5b at full width (24 layers, d_model 1024, vocab
  151936), random weights from ``--seed``, driven through ``ServeEngine``
  with the paged cache at max_seq 4096 — 8 requests of 100-1500 prompt
  tokens and 32 new tokens each.  Run twice: float with the fused FFN and
  norm seams (``ffn_impl``/``norm_impl`` 'auto'), then the paper's unit
  (``softmax_impl='dualmode'``, ``activation='silu_dualmode'``).  Each run
  checks that prefill and decode resolved to the Pallas kernels, and
  compares last-prompt-position logits (and the first decode step's) of
  those kernels against the repo's naive attention on the same chip.
* train: bert-base at full width with ``activation='gelu_dualmode'`` (GELU
  through the softmax unit), 3 ``Trainer`` steps at batch 8, seq 512;
  every loss must be finite.

``--four-chips`` runs only the sequence-parallel ring prefill: one
8192-token prompt through ``ServeEngine`` on a 4-chip ("model",) mesh,
checked for state on all four devices and against the same prompt on one
chip.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU; it never falls back to the CPU.  Its last line is one JSON
object naming the device.  Timings printed here are smoke timings (one
run, compile included where stated), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SERVE_ARCH, TRAIN_ARCH = "qwen1.5-0.5b", "bert-base"
MAX_SEQ, MAX_NEW, N_REQUESTS = 4096, 32, 8
PROMPT_LENS = (100, 1500)
# one chunk holds the longest prompt, and at 2048 x 4096 the score tile
# is past the blocked-attention threshold, so prefill resolves to flash
PREFILL_CHUNK = 2048
PARITY_REQUESTS = 3
RING_CHIPS, RING_PROMPT = 4, 8192
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 512
# Logits parity, |kernel - naive| / max|naive|.  Both sides run the model
# in bf16 and differ only in attention: the naive path rounds the
# probabilities to bf16 before P·V, the kernels keep them in f32 and sum
# in blocks.  Those roundings alone move full-depth qwen1.5-0.5b logits by
# 1.9e-2 of their scale (pure-JAX blocked vs naive attention, bf16, CPU;
# 2e-6 in f32).  A wrong mask, head or block mapping moves them by their
# own scale, so the limit sits 3x above the rounding and far below a bug.
PARITY_RTOL = 6e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(count: int):
    """The TPU devices, or exit non-zero before any other work."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); it does not run elsewhere")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


class CompileClock:
    """Seconds JAX spent compiling (or reading back from the persistent
    cache) since construction, from its own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def prompts(seed: int, vocab: int, n: int, lens) -> list[list[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(*lens))).tolist()
            for _ in range(n)]


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def logits_parity(cfg, params, eng, prompt_list, dtype) -> float:
    """Worst |kernel - naive| / max|naive| over last-prompt-position
    logits and the first decode step's logits of a few requests, through
    the engine's own paged step programs at the resolved impls vs the
    same programs with naive attention."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import init_paged_caches
    from repro.serve.engine import (make_chunk_prefill_step,
                                    make_paged_decode_step)

    bs = eng.block_size
    nblk = MAX_SEQ // bs
    table = jnp.arange(1, nblk + 1, dtype=jnp.int32)[None]
    caches0 = init_paged_caches(cfg, nblk + 1, bs, dtype)

    def programs(c, prefill_impl, decode_impl):
        return (jax.jit(make_chunk_prefill_step(
                    c.replace(attn_impl=prefill_impl))),
                jax.jit(make_paged_decode_step(
                    c.replace(attn_impl=decode_impl))))

    kern = programs(cfg, eng.prefill_attn_impl, eng.decode_attn_impl)
    # the int kernels stream the unit with the power-of-two max snap, so
    # their words are naive attention's under 'dualmode_snap' (the classic
    # 'dualmode' words differ by design, ROADMAP C2)
    snap = "dualmode_snap" if cfg.softmax_impl == "dualmode" else \
        cfg.softmax_impl
    naive = programs(cfg.replace(softmax_impl=snap), "naive", "naive")
    worst = 0.0
    for p in prompt_list[:PARITY_REQUESTS]:
        toks = jnp.asarray([p + [0] * (PREFILL_CHUNK - len(p))], jnp.int32)
        last = jnp.asarray([len(p) - 1], jnp.int32)
        outs = []
        for prefill, decode in (kern, naive):
            lp, c = prefill(params, caches0, toks, jnp.int32(0), table, last)
            outs.append((lp, c))
        tok = jnp.argmax(outs[1][0], axis=-1).astype(jnp.int32)[:, None]
        pos = jnp.asarray([len(p)], jnp.int32)
        ld = [decode(params, c, tok, pos, table)[0]
              for (_, c), (_, decode) in zip(outs, (kern, naive))]
        e_pre, e_dec = rel_err(outs[0][0], outs[1][0]), rel_err(*ld)
        log(f"  parity plen={len(p)}: prefill {e_pre:.3e} "
            f"decode {e_dec:.3e} (limit {PARITY_RTOL})")
        worst = max(worst, e_pre, e_dec)
    return worst


def serve_run(label, cfg, params, seed, dtype, expect_prefill, clock):
    """Drive ``ServeEngine`` as launch/serve.py does; check impls,
    completions and logits parity."""
    import numpy as np
    from repro.kernels import dispatch
    from repro.serve import Request, ServeEngine

    t0, c0 = time.perf_counter(), clock.seconds
    eng = ServeEngine(cfg, params, n_slots=N_REQUESTS, max_seq=MAX_SEQ,
                      seed=seed, dtype=dtype, cache_mode="paged",
                      prefill_chunk=PREFILL_CHUNK)
    paged = dispatch.get_paged_attention(eng.decode_attn_impl) is not None
    decode = "flash_decode_paged" if paged else eng.decode_attn_impl
    ffn, norm = (dispatch.resolve_ffn(cfg.ffn_impl),
                 dispatch.resolve_norm(cfg.norm_impl))
    log(f"[serve:{label}] prefill={eng.prefill_attn_impl} decode={decode} "
        f"ffn={ffn} norm={norm} block={eng.block_size} "
        f"pool={eng.num_blocks} chunk={eng.prefill_chunk}")
    if eng.prefill_attn_impl != expect_prefill or decode != \
            "flash_decode_paged":
        raise SystemExit(f"[serve:{label}] resolved to "
                         f"{eng.prefill_attn_impl}/{decode}, expected "
                         f"{expect_prefill}/flash_decode_paged")
    plist = prompts(seed, cfg.vocab, N_REQUESTS, PROMPT_LENS)
    reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(plist)]
    outs = eng.run(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(v) for v in outs.values())
    bad = [r for r in range(N_REQUESTS)
           if len(outs.get(r, [])) != MAX_NEW
           or eng.reasons.get(r) != "max_new"
           or not all(0 <= t < cfg.vocab for t in outs[r])]
    if bad:
        raise SystemExit(f"[serve:{label}] requests {bad} incomplete: "
                         f"{ {r: eng.reasons.get(r) for r in bad} }")
    log(f"[serve:{label}] {len(outs)} requests, prompts "
        f"{min(map(len, plist))}-{max(map(len, plist))} tokens, {n_tok} "
        f"new tokens in {wall:.2f}s wall (compile "
        f"{clock.seconds - c0:.2f}s incl.) stats: prefills="
        f"{eng.stats['prefills']} decode_steps={eng.stats['decode_steps']}")
    t1, c1 = time.perf_counter(), clock.seconds
    worst = logits_parity(cfg, params, eng, plist, dtype)
    log(f"[serve:{label}] parity vs naive: worst {worst:.3e} in "
        f"{time.perf_counter() - t1:.2f}s (compile "
        f"{clock.seconds - c1:.2f}s)")
    if not np.isfinite(worst) or worst > PARITY_RTOL:
        raise SystemExit(f"[serve:{label}] logits parity {worst:.3e} over "
                         f"{PARITY_RTOL}")


def serve_phase(seed: int, clock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.models.transformer import init_lm

    base = registry.get_config(SERVE_ARCH).replace(ffn_impl="auto",
                                                   norm_impl="auto")
    dtype = jnp.bfloat16
    params = init_lm(jax.random.PRNGKey(seed), base, dtype)
    serve_run("float", base, params, seed, dtype, "flash_pallas", clock)
    unit = base.replace(softmax_impl="dualmode", activation="silu_dualmode")
    serve_run("dualmode", unit, params, seed, dtype, "flash_pallas_int",
              clock)


def train_phase(seed: int, clock) -> None:
    import math
    from repro.configs import registry
    from repro.configs.base import TrainConfig
    from repro.train import Trainer

    cfg = registry.get_config(TRAIN_ARCH).replace(activation="gelu_dualmode")
    tcfg = TrainConfig(total_steps=TRAIN_STEPS, warmup_steps=1, seed=seed,
                       checkpoint_every=10 ** 9,
                       checkpoint_dir=str(ROOT / ".smoke_ckpt"))
    t0, c0 = time.perf_counter(), clock.seconds
    trainer = Trainer(cfg, tcfg, global_batch=TRAIN_BATCH,
                      seq_len=TRAIN_SEQ, resume=False, log=lambda m: None)
    losses, times = [], []
    for _ in range(TRAIN_STEPS):
        s0 = time.perf_counter()
        losses.append(trainer.run(1)["loss"])
        times.append(time.perf_counter() - s0)
        trainer.start_step += 1
    log(f"[train:{cfg.name}] activation={cfg.activation} batch="
        f"{TRAIN_BATCH} seq={TRAIN_SEQ} losses={losses} step_s="
        f"{[round(t, 3) for t in times]} (compile "
        f"{clock.seconds - c0:.2f}s incl.)")
    if not all(math.isfinite(v) for v in losses):
        raise SystemExit(f"[train] non-finite loss: {losses}")


def ring_phase(seed: int, clock) -> None:
    """One 8192-token prompt: ring prefill over a 4-chip mesh vs the same
    prompt on one chip, first-token logits compared."""
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.launch.mesh import auto_mesh
    from repro.models.transformer import init_caches, init_lm
    from repro.serve import Request, ServeEngine
    from repro.serve.engine import make_prefill_step

    cfg = registry.get_config(SERVE_ARCH).replace(ring_axis="model")
    dtype = jnp.bfloat16
    params = init_lm(jax.random.PRNGKey(seed), cfg, dtype)
    prompt = prompts(seed, cfg.vocab, 1, (RING_PROMPT, RING_PROMPT + 1))[0]
    max_seq = RING_PROMPT + MAX_NEW
    mesh = auto_mesh((RING_CHIPS,), ("model",))

    t0, c0 = time.perf_counter(), clock.seconds
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=max_seq, mesh=mesh,
                      seed=seed, dtype=dtype,
                      prefill_buckets=(RING_PROMPT,))
    log(f"[ring] cache={eng.cache_mode} prefill={eng.prefill_attn_impl} "
        f"decode={eng.decode_attn_impl} mesh={dict(mesh.shape)}")
    if eng.prefill_attn_impl != "flash_ring":
        raise SystemExit(f"[ring] prefill resolved to "
                         f"{eng.prefill_attn_impl}, expected flash_ring")
    out = eng.run([Request(rid=0, prompt=prompt, max_new=MAX_NEW)])
    log(f"[ring] {len(out[0])} tokens in {time.perf_counter() - t0:.2f}s "
        f"wall (compile {clock.seconds - c0:.2f}s incl.)")
    spread = {
        "params": {d.id for leaf in jax.tree.leaves(eng.params)
                   for d in leaf.sharding.device_set},
        "kv": {d.id for leaf in jax.tree.leaves(eng.caches)
               for d in leaf.sharding.device_set},
    }
    kv_shards = {tuple(s.data.shape) for leaf in jax.tree.leaves(eng.caches)
                 for s in leaf.addressable_shards}
    log(f"[ring] devices holding params={sorted(spread['params'])} "
        f"kv={sorted(spread['kv'])} kv shard shapes={sorted(kv_shards)}")
    if any(len(v) != RING_CHIPS for v in spread.values()):
        raise SystemExit(f"[ring] state not on all {RING_CHIPS} chips")

    # first-token logits: the engine's ring prefill program vs the same
    # prompt on one chip (flash_pallas, no mesh), same process
    toks = jnp.asarray([prompt], jnp.int32)
    last = jnp.asarray([RING_PROMPT - 1], jnp.int32)
    with jax.set_mesh(mesh):
        ring = jax.jit(make_prefill_step(
            cfg.replace(attn_impl=eng.prefill_attn_impl)))
        l_ring, _ = ring(eng.params, init_caches(cfg, 1, max_seq, dtype),
                         toks, last)
    one = jax.device_put(params, jax.devices()[0])
    single = jax.jit(make_prefill_step(
        cfg.replace(ring_axis="", attn_impl="flash_pallas")))
    l_one, _ = single(one, init_caches(cfg, 1, max_seq, dtype), toks, last)
    err = rel_err(l_ring, l_one)
    log(f"[ring] first-token logits vs one chip: {err:.3e} "
        f"(limit {PARITY_RTOL})")
    if err > PARITY_RTOL:
        raise SystemExit(f"[ring] parity {err:.3e} over {PARITY_RTOL}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip ring prefill phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = tpu_devices(RING_CHIPS if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[smoke] device={devs[0].device_kind} count={len(devs)} "
        f"compile_cache={enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        ring_phase(args.seed, clock)
    else:
        serve_phase(args.seed, clock)
        train_phase(args.seed, clock)
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        log(f"[smoke] peak HBM {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
            f" GiB")
    log(f"[smoke] total {time.perf_counter() - t0:.1f}s, compile "
        f"{clock.seconds:.1f}s over {clock.count} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
