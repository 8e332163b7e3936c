"""Dedicated Pallas backward kernels for blocked flash attention.

The forward kernel (``kernels/flash_attention.py``) saves the per-row
online-softmax statistics ``(m, l)`` instead of discarding them, so the
backward never re-runs the whole unfused graph: each tile re-derives the
probabilities from the SAME :func:`repro.kernels.datapath.
online_softmax_update` arithmetic the forward streamed —

    _, _, p, _ = online_softmax_update(m_final, l_final, s)
    p          = online_softmax_finish(l_final, p)          # normalized

(with ``m_final`` the whole-row max, the update's running max is already
saturated, so ``p`` is exactly the forward's probability tile) — plus
Dao et al.'s recompute trick ``D_i = rowsum(dO_i * O_i)``, which turns
the softmax-jacobian term into one per-row scalar:

    dS = P * (dO V^T - D)        dQ = dS K     dK = dS^T Q     dV = P^T dO

``D`` is FUSED into the kernels instead of running as a separate
pre-pass: the dq kernel computes it from the (o, dO) tiles on its first
KV step and carries it in VMEM scratch for the rest of the sweep; the
dk/dv kernel recomputes it per (q tile) visit — a (bq, hv) elementwise
row sum, noise next to the tile matmuls — so neither kernel reads a
third per-row statistic from HBM and no extra XLA pass materializes
``D`` at all.

Standard two-pass split, one kernel per output side:

  * dq:    grid (b, heads, q_tiles, kv_tiles) — stream KV per q tile,
           accumulate dQ in VMEM scratch across the sequential kv dim
           (``attention_blockspecs``' layout, reused verbatim); D lives
           in a second scratch row, computed once at kv step 0.
  * dk/dv: grid (b, kv_heads, kv_tiles, groups, q_tiles) — stream Q per
           kv tile; the G query groups of a KV head and all q tiles
           accumulate into the SAME (bkv, h)/(bkv, hv) scratch, so the
           GQA group-sum happens in VMEM, not HBM.

Masking is :func:`flash_attention.masked_score_block` — the one
definition the forward uses — so forward and backward can never disagree
on which keys are "off".  Masked positions behave exactly like the
reference VJP: their MASK_VALUE probability mass still reaches dV (the
forward really attends that mass), but dS is zeroed where the score was
replaced by the constant — the reference's ``jnp.where`` routes no
gradient into the untaken branch, so dQ/dK see exactly 0 there.  Tiling
phantoms score -inf and contribute to nothing.

``q`` arrives pre-scaled (the traced scale is folded in before the
custom_vjp), so every kernel here is scale-free and dq is the cotangent
of the pre-scaled q — the chain rule through the fold-in multiply is
handled by JAX outside.  Runs on CPU with ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import datapath as dp
from . import tiling
from .flash_attention import (attention_blockspecs, heads_major,
                              kernel_operands, masked_score_block,
                              rowstat_blockspec, tokens_major)


def _probs_from_stats(m_row, l_row, s):
    """Forward probability tile from the saved (m, l) row statistics,
    through the forward's own datapath steps."""
    _, _, p, _ = dp.online_softmax_update(m_row, l_row, s)
    return dp.online_softmax_finish(l_row, p)


def _rowsum_do_o(do_ref, o_ref):
    """Dao's per-row scalar D = rowsum(dO ∘ O) from the two output-layout
    tiles — the fused replacement for the old host-side pre-pass."""
    do = do_ref[0, 0].astype(jnp.float32)                 # (bq, hv)
    o = o_ref[0, 0].astype(jnp.float32)
    return jnp.sum(do * o, axis=-1, keepdims=True)        # (bq, 1)


def _tile_grads(qpos_ref, valid_ref, q_ref, k_ref, v_ref, do_ref, m_ref,
                l_ref, d_row, kv_tile, *, block_kv: int, causal: bool,
                t_kv: int):
    """The shared per-tile recompute of both backward kernels.

    Loads one (q tile, kv tile) operand pair, re-derives the forward
    probability tile p from the saved (m, l), and forms the score
    cotangent dS = P * (dO V^T - D), zeroed where the forward's mask
    replaced the score by the constant MASK_VALUE (matching the reference
    ``jnp.where`` VJP, which routes no gradient into the untaken branch).
    ``d_row`` is the (bq, 1) fused D — from scratch (dq kernel) or
    recomputed in-tile (dk/dv kernel).

    Returns (p, ds, q, kb, do) — everything either kernel body combines.
    """
    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, h) pre-scaled
    kb = k_ref[0, 0].astype(jnp.float32)                  # (bkv, h)
    vb = v_ref[0, 0].astype(jnp.float32)                  # (bkv, hv)
    do = do_ref[0, 0].astype(jnp.float32)                 # (bq, hv)
    s, mask = masked_score_block(q, kb, qpos_ref[0], valid_ref[0], kv_tile,
                                 block_kv=block_kv, causal=causal,
                                 t_kv=t_kv)
    m_row = m_ref[0, 0]                                   # (bq, 1)
    l_row = l_ref[0, 0]
    p = _probs_from_stats(m_row, l_row, s)                # (bq, bkv)
    dpv = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = jnp.where(mask, p * (dpv - d_row), 0.0)          # (bq, bkv)
    return p, ds, q, kb, do


def _dq_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
             m_ref, l_ref, dq_ref, dq_acc, d_sc, *, block_kv: int,
             causal: bool, t_kv: int):
    kj = pl.program_id(3)
    hd = q_ref.shape[-1]

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        # fused D: computed once per q tile on the first KV step, carried
        # in VMEM scratch across the sequential kv dim — no pre-pass
        d_sc[...] = jnp.broadcast_to(_rowsum_do_o(do_ref, o_ref),
                                     d_sc.shape)

    _, ds, _, kb, _ = _tile_grads(
        qpos_ref, valid_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
        d_sc[:, :1], kj, block_kv=block_kv, causal=causal, t_kv=t_kv)
    dq_acc[:, :hd] = dq_acc[:, :hd] + jnp.dot(
        ds, kb, preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:, :hd]


def _dkdv_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
               m_ref, l_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
               block_kv: int, causal: bool, t_kv: int):
    kv_ = pl.program_id(2)
    g_ = pl.program_id(3)
    qi = pl.program_id(4)
    hd = q_ref.shape[-1]
    hv = v_ref.shape[-1]

    @pl.when((g_ == 0) & (qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    p, ds, q, _, do = _tile_grads(
        qpos_ref, valid_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
        _rowsum_do_o(do_ref, o_ref), kv_, block_kv=block_kv,
        causal=causal, t_kv=t_kv)
    dv_acc[:, :hv] = dv_acc[:, :hv] + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # P^T dO
    dk_acc[:, :hd] = dk_acc[:, :hd] + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # dS^T Q

    @pl.when((g_ == pl.num_programs(3) - 1)
             & (qi == pl.num_programs(4) - 1))
    def _():
        dk_ref[0, 0] = dk_acc[:, :hd]
        dv_ref[0, 0] = dv_acc[:, :hv]


def vmem_plan(s_q: int, t_kv: int, hd: int, hv: int, g: int = 1):
    """Static VMEM residency of the dq and dk/dv backward kernels (see
    ``flash_attention.vmem_plan`` for the contract)."""
    bq, bkv = tiling.attention_blocks(s_q, t_kv)
    common = {
        "in:q_pos": ((1, bq, 1), jnp.int32),
        "in:kv_valid": ((1, 1, bkv), jnp.int32),
        "in:q": ((1, 1, bq, hd), jnp.float32),
        "in:k": ((1, 1, bkv, hd), jnp.float32),
        "in:v": ((1, 1, bkv, hv), jnp.float32),
        "in:o": ((1, 1, bq, hv), jnp.float32),
        "in:do": ((1, 1, bq, hv), jnp.float32),
        "in:m": ((1, 1, bq, 1), jnp.float32),
        "in:l": ((1, 1, bq, 1), jnp.float32),
    }
    return {
        "flash_bwd_dq": dict(
            common,
            **{"out:dq": ((1, 1, bq, hd), jnp.float32),
               "scratch:dq_acc": ((bq, tiling.scratch_lanes(hd)),
                                  jnp.float32),
               "scratch:d": ((bq, tiling.scratch_lanes(1)), jnp.float32)}),
        "flash_bwd_dkdv": dict(
            common,
            **{"out:dk": ((1, 1, bkv, hd), jnp.float32),
               "out:dv": ((1, 1, bkv, hv), jnp.float32),
               "scratch:dk_acc": ((bkv, tiling.scratch_lanes(hd)),
                                  jnp.float32),
               "scratch:dv_acc": ((bkv, tiling.scratch_lanes(hv)),
                                  jnp.float32)}),
    }


def flash_attention_bwd_pallas(q, k, v, o, m, l, do, *, q_pos, kv_valid,
                               causal: bool, block_q: int, block_kv: int,
                               interpret: bool):
    """(dq, dk, dv) in f32 via the dedicated backward kernels.

    q is the PRE-SCALED f32 query; (o, m, l) are the forward's output and
    per-row statistics (m/l laid out (B, K, G, S)); do is the output
    cotangent.  Blocks must match the forward's so padded grids line up.
    """
    b, s_q, kh, g, hd = q.shape
    t = k.shape[1]
    hv = v.shape[-1]
    bq, bkv = block_q, block_kv

    # Dao et al.'s D = rowsum(dO∘O) is fused INTO the kernels (dq: first
    # KV step into scratch; dk/dv: per q-tile visit) — only o/do are
    # padded here, no per-row D array ever exists in HBM
    qf, qp, kf, vf, valid = kernel_operands(q, q_pos, k, v, kv_valid,
                                            bq, bkv)
    of = heads_major(tiling.pad_dim(o.astype(jnp.float32), 1, bq)[0])
    dof = heads_major(tiling.pad_dim(do.astype(jnp.float32), 1, bq)[0])
    # phantom q rows: o/dO pad with 0 (so the fused D is 0 there) and l
    # with 1, so the re-derived probabilities stay finite and every
    # phantom contribution is 0
    s_p, t_p = qf.shape[2], kf.shape[2]
    mf = tiling.pad_dim(m, 3, bq)[0].reshape(b, kh * g, s_p, 1)
    lf = tiling.pad_dim(l, 3, bq, value=1.0)[0].reshape(b, kh * g, s_p, 1)

    body = dict(block_kv=bkv, causal=causal, t_kv=t)
    in_specs, out_spec = attention_blockspecs(bq, bkv, g, hd, hv)
    stat = rowstat_blockspec(bq)
    dq = pl.pallas_call(
        functools.partial(_dq_body, **body),
        grid=(b, kh * g, s_p // bq, t_p // bkv),
        in_specs=in_specs + [out_spec, out_spec, stat, stat],  # + o, do, m, l
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, qi, kj: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh * g, s_p, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, tiling.scratch_lanes(hd)), jnp.float32),
            pltpu.VMEM((bq, tiling.scratch_lanes(1)), jnp.float32)],  # D
        interpret=interpret,
    )(qp, valid, qf, kf, vf, of, dof, mf, lf)

    # dk/dv grid: kv tiles OUTER, (group, q tile) inner — consecutive
    # inner steps revisit the same output block, so the accumulation
    # (incl. the GQA sum over groups) stays in VMEM scratch
    def q_rows(width):
        return pl.BlockSpec(
            (1, 1, bq, width),
            lambda b_, kh_, kv_, g_, qi: (b_, kh_ * g + g_, qi, 0))

    def kv_rows(width):
        return pl.BlockSpec((1, 1, bkv, width),
                            lambda b_, kh_, kv_, g_, qi: (b_, kh_, kv_, 0))

    dkdv_specs = [
        pl.BlockSpec((1, bq, 1), lambda b_, kh_, kv_, g_, qi: (b_, qi, 0)),
        pl.BlockSpec((1, 1, bkv),
                     lambda b_, kh_, kv_, g_, qi: (b_, 0, kv_)),
        q_rows(hd), kv_rows(hd), kv_rows(hv),
        q_rows(hv), q_rows(hv),                            # o, do
        q_rows(1), q_rows(1),                              # m, l
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_body, **body),
        grid=(b, kh, t_p // bkv, g, s_p // bq),
        in_specs=dkdv_specs,
        out_specs=[kv_rows(hd), kv_rows(hv)],
        out_shape=[jax.ShapeDtypeStruct((b, kh, t_p, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, kh, t_p, hv), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bkv, tiling.scratch_lanes(hd)), jnp.float32),
            pltpu.VMEM((bkv, tiling.scratch_lanes(hv)), jnp.float32)],
        interpret=interpret,
    )(qp, valid, qf, kf, vf, of, dof, mf, lf)

    return (tiling.unpad(tokens_major(dq, kh), 1, s_q),
            tiling.unpad(dk.transpose(0, 2, 1, 3), 1, t),
            tiling.unpad(dv.transpose(0, 2, 1, 3), 1, t))
