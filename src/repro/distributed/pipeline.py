"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Opt-in feature (DESIGN.md §5): the 40 baseline dry-run cells use DP×TP;
this module provides stage parallelism for depth-dominated models at
1000+-node scale, where a pure 2D mesh runs out of useful TP width.

Scheme: the layer stack is split into S contiguous stages along a 'stage'
mesh axis; the global batch is split into M microbatches.  Each step of the
(S + M - 1)-slot schedule runs the resident stage on its current microbatch
and ppermutes activations to the next stage.  Bubble fraction is
(S-1)/(S+M-1) — reported by `bubble_fraction` so launch configs can size M.

The stage body is a user function `stage_fn(stage_params, x) -> x`; stacked
stage params live on the 'stage' axis, so the whole pipeline is one
shard_map with no per-stage python dispatch.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)


def pipelined(stage_fn: Callable, mesh: Mesh, n_micro: int,
              axis: str = "stage") -> Callable:
    """Wrap `stage_fn` into a GPipe forward over the `axis` mesh axis.

    Returns f(stage_params, x) where
      stage_params : pytree with leading dim = n_stages (sharded over axis)
      x            : (B, ...) global batch, B % n_micro == 0
    """
    n_stages = mesh.shape[axis]

    def run(params, x):
        # inside shard_map: params have the stage dim stripped to local (1,...)
        local = jax.tree.map(lambda a: a[0], params)
        b = x.shape[0]
        mb = b // n_micro
        micro = x.reshape(n_micro, mb, *x.shape[1:])
        stage_id = jax.lax.axis_index(axis)
        n_slots = n_stages + n_micro - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def slot(carry, t):
            state, out = carry                       # (mb,...) in-flight act
            # stage s processes microbatch t-s when 0 <= t-s < n_micro
            mb_idx = t - stage_id
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 injects a fresh microbatch; others consume the permuted
            inject = micro[jnp.clip(mb_idx, 0, n_micro - 1)]
            x_in = jnp.where(stage_id == 0, inject, state)
            y = stage_fn(local, x_in)
            y = jnp.where(active, y, state)
            # last stage banks its finished microbatch
            done = active & (stage_id == n_stages - 1)
            out = jax.lax.select(
                done,
                jax.lax.dynamic_update_index_in_dim(
                    out, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                out)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, out), None

        state0 = jnp.zeros((mb, *x.shape[1:]), x.dtype)
        out0 = jnp.zeros((n_micro, mb, *x.shape[1:]), x.dtype)
        (_, out), _ = jax.lax.scan(slot, (state0, out0), jnp.arange(n_slots))
        # finished microbatches live on the last stage; broadcast via a
        # masked psum (one all-reduce of the output, GPipe's usual epilogue)
        out = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out.reshape(b, *x.shape[1:])

    def wrapped(stage_params, x):
        in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
        return jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(stage_params, x)

    return wrapped
