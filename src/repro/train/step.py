"""Loss + train-step factory: remat, microbatch accumulation, grad
compression, and sharding-aware jit wiring.

The step is a pure function (TrainState, batch) -> (TrainState, metrics);
all distribution comes from the in/out shardings installed by
`jit_train_step` (GSPMD turns the data-parallel gradient mean into
reduce-scatter/all-reduce, tensor-parallel matmuls into collective
schedules — nothing torch.distributed-like lives in the step itself).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.distributed import batch_pspec, data_axes, param_pspecs
from repro.models.accounting import pick_profile
from repro.models.transformer import (encoder_apply, init_lm, lm_apply,
                                      lm_head_weight)
from repro.optim import (OptState, adamw_init, adamw_update,
                         compress_decompress, ef_state_init, wsd_schedule)

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt: OptState
    ef: Params          # grad-compression residuals ({} when disabled)


def chunked_ce(h, head_w, labels, *, target_chunks: int = 8,
               dp=None, sp=None, dp_size: int = 1):
    """Mean next-token CE without materializing (B,S,vocab) logits.

    At train_4k the full logits tensor is global_batch·seq·vocab ~ 1e11
    floats — hundreds of TB; it CANNOT exist at any sharding.  We scan
    over BATCH chunks (not sequence chunks: splitting the seq dim would
    break its 'model' sequence-parallel sharding and every device would
    recompute the full global head — measured 50x flops bloat).  Chunk
    size stays divisible by the dp group (`dp_size`) so the split is
    shard-aligned, and explicit constraints keep (dp, sp) pinned inside
    the scan.  Peak extra memory: chunk·S·vocab / n_devices, freed per
    scan step (the jax.checkpoint recomputes logits in backward).
    """
    b, s, d = h.shape
    nc = min(target_chunks, b)
    while b % nc or (b // nc) % dp_size:
        nc -= 1
    bc = b // nc
    hc = h.reshape(nc, bc, s, d)
    lc = labels.reshape(nc, bc, s)
    if dp is not None or sp is not None:
        hc = jax.lax.with_sharding_constraint(hc, P(None, dp, sp, None))
        lc = jax.lax.with_sharding_constraint(lc, P(None, dp, sp))

    @jax.checkpoint
    def one(tot, xs):
        hh, ll = xs
        logits = (hh @ head_w).astype(jnp.float32)           # (bc,S,V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(logz - gold), None

    tot, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (hc, lc))
    return tot / (b * s)


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh | None = None,
                 aux_weight: float = 0.01):
    """Cross-entropy (+ MoE load-balance aux) over a batch dict.

    With a mesh given, the residual stream is pinned to
    (dp, 'model', None) — Megatron-style sequence parallelism, so the
    per-period remat carry is stored seq-sharded."""
    profile = (pick_profile(cfg) if tcfg.profile == "auto"
               else tcfg.profile)

    def loss_fn(params, batch):
        act_pspec, dp_ax, sp_ax, dp_size = None, None, None, 1
        if mesh is not None and "model" in mesh.axis_names:
            b, s = batch["tokens"].shape
            pools = []
            if profile == "dp":      # idle 'model' joins the DP group
                pools.append(data_axes(mesh) + ("model",))
            pools.append(data_axes(mesh))
            for pool in pools:
                dsize = 1
                for a in pool:
                    dsize *= mesh.shape[a]
                if pool and b % dsize == 0:
                    dp_ax = pool if len(pool) > 1 else pool[0]
                    dp_size = dsize
                    break
            # SP whenever 'model' is not already consumed by the batch —
            # under the dp profile an idle model axis would otherwise
            # DUPLICATE the compute on every model rank (measured 6-16x)
            model_free = not (isinstance(dp_ax, tuple) and "model" in dp_ax)
            if (s % mesh.shape["model"] == 0 and tcfg.seq_shard
                    and model_free):
                sp_ax = "model"
            act_pspec = P(dp_ax, sp_ax, None)
        cross_src = None
        if "frames" in batch:                      # enc-dec stub frontend
            cross_src = encoder_apply(params, cfg, batch["frames"])
        elif "image_embeds" in batch:              # VLM stub frontend
            cross_src = batch["image_embeds"]
        h, _, aux = lm_apply(params, cfg, batch["tokens"],
                             cross_src=cross_src, remat=tcfg.remat,
                             act_pspec=act_pspec, return_hidden=True,
                             inner_pins=tcfg.inner_pins,
                             remat_mode=tcfg.remat_mode)
        ce = chunked_ce(h, lm_head_weight(params, cfg), batch["labels"],
                        dp=dp_ax, sp=sp_ax, dp_size=dp_size)
        return ce + aux_weight * aux, (ce, aux)
    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    mesh: Mesh | None = None):
    loss_fn = make_loss_fn(cfg, tcfg, mesh)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch):
        lr = wsd_schedule(state.opt.step, lr=tcfg.lr,
                          warmup=tcfg.warmup_steps, total=tcfg.total_steps)
        if tcfg.microbatch:
            b = batch["tokens"].shape[0]
            n_acc = b // tcfg.microbatch
            micro = jax.tree.map(
                lambda x: x.reshape(n_acc, tcfg.microbatch, *x.shape[1:]),
                batch)

            def acc(carry, mb):
                g_sum, ce_sum, aux_sum = carry
                (_, (ce, aux)), g = grad_fn(state.params, mb)
                g_sum = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), g_sum, g)
                return (g_sum, ce_sum + ce, aux_sum + aux), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            (grads, ce, aux), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros(()), jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / n_acc, grads)
            ce, aux = ce / n_acc, aux / n_acc
        else:
            (_, (ce, aux)), grads = grad_fn(state.params, batch)

        ef = state.ef
        if tcfg.grad_compress:
            grads, ef = compress_decompress(grads, ef)

        params, opt, om = adamw_update(
            grads, state.opt, state.params, lr=lr, b1=tcfg.b1, b2=tcfg.b2,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip)
        metrics = {"loss": ce + 0.01 * aux, "ce": ce, "aux": aux,
                   "grad_norm": om["grad_norm"], "lr": lr}
        return TrainState(params, opt, ef), metrics

    return train_step


# ---------------- sharding-aware state construction ----------------

def state_pspecs(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                 dtype=jnp.float32) -> tuple[TrainState, TrainState]:
    """(state ShapeDtypeStructs, state PartitionSpecs) — no allocation.

    'dp' profile (small models): params replicated, optimizer moments
    ZeRO-1-sharded over 'data' (they are 4x the bf16 params and have no
    per-step latency role)."""
    profile = (pick_profile(cfg) if tcfg.profile == "auto"
               else tcfg.profile)
    p_sds = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg, dtype))
    p_spec = param_pspecs(p_sds, mesh, fsdp=tcfg.fsdp, profile=profile)
    # optimizer moments are always ZeRO-1 sharded over 'data' on top of
    # the param layout: they are 4x the bf16 params, off the latency path
    m_spec = param_pspecs(p_sds, mesh, fsdp=True, profile=profile)
    opt_sds = jax.eval_shape(adamw_init, p_sds)
    opt_spec = OptState(m=m_spec, v=m_spec, step=P())
    ef_sds = jax.eval_shape(ef_state_init, p_sds) if tcfg.grad_compress else {}
    ef_spec = m_spec if tcfg.grad_compress else {}
    return (TrainState(p_sds, opt_sds, ef_sds),
            TrainState(p_spec, opt_spec, ef_spec))


def make_train_state(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                     seed: int | None = None, dtype=jnp.float32):
    """Allocate a sharded TrainState on `mesh` (jit'd init -> no host copy).

    Returns (state, state_shardings)."""
    seed = tcfg.seed if seed is None else seed
    _, spec = state_pspecs(cfg, tcfg, mesh, dtype)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                             is_leaf=lambda x: isinstance(x, P))

    def build():
        params = init_lm(jax.random.PRNGKey(seed), cfg, dtype)
        ef = ef_state_init(params) if tcfg.grad_compress else {}
        return TrainState(params, adamw_init(params), ef)

    with jax.set_mesh(mesh):
        state = jax.jit(build, out_shardings=shardings)()
    return state, shardings


def jit_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                   global_batch: int, dtype=jnp.float32):
    """jit the step with explicit in/out shardings + donated state."""
    _, spec = state_pspecs(cfg, tcfg, mesh, dtype)
    bspec = batch_pspec(mesh, global_batch)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(cfg, tcfg, mesh)
    return jax.jit(step,
                   in_shardings=(state_sh, NamedSharding(mesh, bspec)),
                   out_shardings=(state_sh, None),
                   donate_argnums=(0,))
