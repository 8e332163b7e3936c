"""Trainer: the fault-tolerant training driver.

Responsibilities (DESIGN.md §5):
  * checkpoint/restart — async sharded saves every `checkpoint_every`
    steps; on construction the trainer auto-resumes from the newest
    complete checkpoint in `tcfg.checkpoint_dir` (crash -> relaunch ->
    continue, with the data pipeline replaying deterministically from the
    restored step).
  * straggler monitor  — per-step wall time vs a P50 watermark (EMA);
    steps slower than `straggler_factor`x are counted and logged.  On a
    real fleet this signal feeds the launcher's replace-node path; here it
    is surfaced in metrics and asserted on by tests.
  * elastic remesh     — `Trainer.from_checkpoint(new_mesh)` restores any
    checkpoint onto a different mesh/device count (gathered-leaf store +
    fresh `param_pspecs` = resharding on restore).
"""
from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointStore, latest_step
from repro.configs.base import ModelConfig, TrainConfig
from repro.data import SyntheticLM
from repro.distributed import batch_pspec
from repro.launch.mesh import auto_mesh
from .step import (TrainState, jit_train_step, make_train_state,
                   state_pspecs)


def default_mesh() -> Mesh:
    return auto_mesh((len(jax.devices()),), ("data",))


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 global_batch: int, seq_len: int, *,
                 mesh: Mesh | None = None, dtype=jnp.float32,
                 data: SyntheticLM | None = None,
                 straggler_factor: float = 1.5,
                 log: Callable[[str], None] = print,
                 resume: bool = True):
        self.cfg, self.tcfg = cfg, tcfg
        self.mesh = mesh or default_mesh()
        self.dtype = dtype
        self.global_batch, self.seq_len = global_batch, seq_len
        self.data = data or SyntheticLM(vocab=cfg.vocab, seq_len=seq_len,
                                        global_batch=global_batch,
                                        seed=tcfg.seed)
        self.log = log
        self.straggler_factor = straggler_factor
        self.store = CheckpointStore(tcfg.checkpoint_dir)
        self.step_fn = jit_train_step(cfg, tcfg, self.mesh, global_batch,
                                      dtype)
        self._bsharding = NamedSharding(self.mesh,
                                        batch_pspec(self.mesh, global_batch))
        self.start_step = 0
        if resume and latest_step(tcfg.checkpoint_dir) is not None:
            self.state, self.start_step = self._restore()
            self.log(f"[trainer] resumed from step {self.start_step}")
        else:
            self.state, _ = make_train_state(cfg, tcfg, self.mesh,
                                             dtype=dtype)
        # telemetry
        self._steps_watched = 0
        self.straggler_steps: list[int] = []
        self._ema: float | None = None

    # ---------------- fault tolerance ----------------

    def _restore(self) -> tuple[TrainState, int]:
        sds, spec = state_pspecs(self.cfg, self.tcfg, self.mesh, self.dtype)
        sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec,
                          is_leaf=lambda x: isinstance(x, PartitionSpec))
        state, step, _ = self.store.restore(sds, shardings=sh)
        return state, step

    @classmethod
    def from_checkpoint(cls, cfg, tcfg, global_batch, seq_len, *,
                        mesh: Mesh, **kw) -> "Trainer":
        """Elastic restart: restore the latest checkpoint onto a NEW mesh
        (different device count / axis shape)."""
        return cls(cfg, tcfg, global_batch, seq_len, mesh=mesh, resume=True,
                   **kw)

    def save(self, step: int, block: bool = True) -> None:
        self.store.save(step, self.state, block=block,
                        extra={"arch": self.cfg.name})

    # ---------------- main loop ----------------

    def run(self, n_steps: int | None = None) -> dict[str, Any]:
        """Train to ``total_steps``, or ``n_steps`` more.  Each step is
        three consecutive profiler spans, so a device trace puts each
        idle gap of the chip down to the host work that held it:
        ``trainer.feed`` (make the batch, put it on the devices),
        ``trainer.compute`` (dispatch the step and wait for its
        metrics) and, on a step that saves, ``trainer.checkpoint`` (the
        device-to-host copy of the state); the wait for the last save's
        disk write at the end is a ``trainer.checkpoint`` span too."""
        end = self.tcfg.total_steps if n_steps is None \
            else self.start_step + n_steps
        metrics = {}
        for step in range(self.start_step, end):
            with jax.profiler.TraceAnnotation("trainer.feed"):
                tokens, labels = self.data.batch(step)
                batch = {"tokens": jax.device_put(tokens, self._bsharding),
                         "labels": jax.device_put(labels, self._bsharding)}
            with jax.profiler.TraceAnnotation("trainer.compute"):
                t0 = time.perf_counter()
                with jax.set_mesh(self.mesh):   # sharding constraints resolve at trace time
                    self.state, metrics = self.step_fn(self.state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
                dt = time.perf_counter() - t0
                self._watch_straggler(step, dt)
                if step % 10 == 0 or step == end - 1:
                    self.log(f"[trainer] step {step} "
                             f"loss={metrics['loss']:.4f} "
                             f"gnorm={metrics['grad_norm']:.2f} "
                             f"{dt*1e3:.0f}ms")
                save = (step + 1) % self.tcfg.checkpoint_every == 0
            if save:
                with jax.profiler.TraceAnnotation("trainer.checkpoint"):
                    self.save(step + 1, block=False)
        with jax.profiler.TraceAnnotation("trainer.checkpoint"):
            self.store.wait()
        self.start_step = end
        return metrics

    def _watch_straggler(self, step: int, dt: float) -> None:
        self._steps_watched += 1
        if self._ema is None:
            self._ema = dt
            return
        if dt > self.straggler_factor * self._ema and self._steps_watched > 3:
            self.straggler_steps.append(step)
            self.log(f"[trainer] STRAGGLER step {step}: {dt*1e3:.0f}ms vs "
                     f"EMA {self._ema*1e3:.0f}ms")
        self._ema = 0.9 * self._ema + 0.1 * dt
