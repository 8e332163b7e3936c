"""Faults planted under the timed path, to show that ``correct`` catches
them: the CPU tests plant them in tiny runs, and bench/calibrate.py reads
them on the chip at the cell's size.  Each is a context manager that
patches the program for its duration and restores it after.

* ``token_altered``: the engine's sampler returns another token than the
  one it picked, every fifth call (serving).
* ``state_unchanged``: the training step returns its state as it got it.
* ``half_batch``: the training step sees half of the batch and takes the
  mean over the rest.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def token_altered():
    import jax.numpy as jnp

    import repro.serve.engine as engine
    orig, calls = engine.sample_token, [0]

    def altered(key, logits, temperature):
        tok = orig(key, logits, temperature)
        calls[0] += 1
        if calls[0] % 5 == 0:
            tok = (tok + 1) % logits.shape[-1]
        return jnp.asarray(tok)
    engine.sample_token = altered
    try:
        yield
    finally:
        engine.sample_token = orig


@contextlib.contextmanager
def _train_step(make):
    import repro.train.trainer as trainer
    orig = trainer.jit_train_step

    def patched(cfg, tcfg, mesh, global_batch, dtype):
        import jax

        from repro.train.step import make_train_step
        return jax.jit(make(make_train_step(cfg, tcfg, mesh)))
    trainer.jit_train_step = patched
    try:
        yield
    finally:
        trainer.jit_train_step = orig


def state_unchanged():
    return _train_step(lambda step: lambda s, b: (s, step(s, b)[1]))


def half_batch():
    import jax

    def make(step):
        def f(s, b):
            n = jax.tree.leaves(b)[0].shape[0] // 2
            return step(s, jax.tree.map(lambda x: x[:n], b))
        return f
    return _train_step(make)


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged,
          "half_batch": half_batch}
