"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 200 --batch 8 --seq 128 --reduced --ckpt /tmp/ck

--reduced runs the smoke-scale config (CPU-feasible); full-scale runs use
the production mesh on real hardware (same code path the dry-run proves).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.train import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS
                    + ["bert-base"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-feasible)")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (registry.reduced_config(args.arch) if args.reduced
           else registry.get_config(args.arch))
    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 1),
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt,
                       microbatch=args.microbatch, fsdp=args.fsdp,
                       grad_compress=args.grad_compress, remat=True,
                       seed=args.seed)
    trainer = Trainer(cfg, tcfg, global_batch=args.batch, seq_len=args.seq)
    print(f"[train] {cfg.name} reduced={args.reduced} "
          f"devices={len(jax.devices())} start={trainer.start_step}")
    metrics = trainer.run(args.steps)
    print(f"[train] done: {metrics}")
    trainer.save(trainer.start_step)


if __name__ == "__main__":
    main()
