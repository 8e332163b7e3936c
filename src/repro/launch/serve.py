"""Serving launcher: continuous-batching engine over synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 16 --slots 4 --max-new 32 --reduced
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_lm
from repro.serve import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ring-devices", type=int, default=0,
                    help="shard long-context prefill KV over a ring of "
                         "this many local devices (0 = off; off-TPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count accordingly)")
    ap.add_argument("--prefill-impl", default=None,
                    help="attention impl for the prefill program "
                         "(default: resolve cfg.attn_impl per phase)")
    ap.add_argument("--decode-impl", default=None,
                    help="attention impl for the decode program — e.g. "
                         "'flash_decode' to force the split-KV decode "
                         "kernel at any cache length, 'naive' to pin the "
                         "whole-row path (default: 'auto' resolution, "
                         "which picks flash_decode at long --max-seq)")
    ap.add_argument("--cache-mode", default="auto",
                    choices=("auto", "paged", "contiguous"),
                    help="KV cache layout: 'paged' = block-table pool "
                         "with prefix sharing + chunked prefill, "
                         "'contiguous' = per-slot rows with bucketed "
                         "prefill, 'auto' = paged wherever the arch "
                         "supports it")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged KV block size in tokens (0 = the tiling "
                         "policy's pick for --max-seq)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size in blocks incl. sentinel "
                         "(0 = match the contiguous HBM budget: "
                         "slots*max_blocks + 1)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged prefill chunk length in tokens "
                         "(0 = default 64)")
    ap.add_argument("--admission", default="reactive",
                    choices=("reactive", "worst_case"),
                    help="paged admission: 'reactive' reserves only the "
                         "prompt's block reach and grows per decode tick "
                         "(preempting under pool pressure), 'worst_case' "
                         "reserves prompt+max_new up front so admitted "
                         "requests never preempt")
    ap.add_argument("--preempt-policy", default="youngest",
                    choices=("youngest", "oldest"),
                    help="victim choice under pool pressure (always "
                         "lowest-priority first; this orders ties)")
    ap.add_argument("--preempt-mode", default="recompute",
                    choices=("recompute", "swap"),
                    help="'recompute' drops a victim's blocks and "
                         "re-prefills on resume; 'swap' copies them to "
                         "host memory and restores the exact bytes")
    ap.add_argument("--hol-window", type=int, default=4,
                    help="queue entries a pool-blocked head request can "
                         "be skipped past at admission (1 = strict FCFS)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none); "
                         "expired requests retire with reason 'deadline'")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (registry.reduced_config(args.arch) if args.reduced
           else registry.get_config(args.arch))
    mesh = None
    if args.ring_devices:
        from repro.launch.mesh import auto_mesh
        cfg = cfg.replace(ring_axis="model")
        mesh = auto_mesh((args.ring_devices,), ("model",))
    params = init_lm(jax.random.PRNGKey(args.seed), cfg)
    eng = ServeEngine(cfg, params, n_slots=args.slots,
                      max_seq=args.max_seq, mesh=mesh, seed=args.seed,
                      prefill_attn_impl=args.prefill_impl,
                      decode_attn_impl=args.decode_impl,
                      cache_mode=args.cache_mode,
                      block_size=args.block_size or None,
                      num_blocks=args.num_blocks or None,
                      prefill_chunk=args.prefill_chunk or None,
                      admission=args.admission,
                      preempt_policy=args.preempt_policy,
                      preempt_mode=args.preempt_mode,
                      hol_window=args.hol_window)
    mode = eng.cache_mode
    if mode == "paged":
        mode += (f" (block={eng.block_size} pool={eng.num_blocks} "
                 f"chunk={eng.prefill_chunk})")
    print(f"[serve] cache={mode} attention impls: "
          f"prefill={eng.prefill_attn_impl} decode={eng.decode_attn_impl}")
    rng = jax.random.PRNGKey(args.seed + 1)
    reqs = []
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        plen = int(jax.random.randint(k, (), 2, 16))
        prompt = [int(t) for t in
                  jax.random.randint(k, (plen,), 0, cfg.vocab - 1)]
        reqs.append(Request(rid=i, prompt=prompt, max_new=args.max_new,
                            temperature=args.temperature,
                            deadline_s=args.deadline_s or None))
    t0 = time.perf_counter()
    outs = eng.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in outs.values())
    print(f"[serve] {cfg.name}: {len(outs)} requests, {toks} tokens in "
          f"{dt:.2f}s ({toks/dt:.1f} tok/s) stats={eng.stats}")
    for rid in sorted(outs)[:4]:
        print(f"  rid={rid}: {outs[rid][:12]}...")


if __name__ == "__main__":
    main()
