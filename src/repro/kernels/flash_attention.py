"""Pallas blocked flash attention on the unit's log-domain datapath.

The paper's softmax normalizes in the LOG domain (Eq. 10); that form
telescopes exactly into the online-softmax recurrence, so the streamed
inner step here is literally :func:`repro.kernels.datapath.
online_softmax_update` — the same function the pure-JAX blocked path
(``models/flash.py``) runs.  This kernel adds the Pallas grid around it:
KV is streamed through VMEM in (block_kv)-sized tiles while the running
(m, l, acc) state lives in VMEM scratch across the sequential kv grid
dimension, so the (S, T) score matrix is never materialized in HBM.

Shapes match the model-side attention core (GQA/MLA compatible):

    q (B, S, K, G, h)   k (B, T, K, h)   v (B, T, K, hv)  ->  (B, S, K, G, hv)

with G query groups per KV head and hv possibly != h (MLA).  Masking: kv
position t attends iff ``kv_valid[b, t]`` and (not causal or
``t <= q_pos[b, s]``); masked scores take ``datapath.MASK_VALUE`` exactly
like the naive path, so all three implementations agree on masking.

Non-divisible S/T are padded up to the block grid (``kernels/tiling.py``
policy) and the output sliced back; padded KV rows are simply invalid.
Runs on CPU with ``interpret=True`` (the default on the CPU backend).

Residual contract: the forward emits the per-row online-softmax
statistics ``(m, l)`` — running max and normalizer of the PRE-SCALED
masked scores, laid out (B, K, G, S) — as extra kernel outputs.  The
custom VJP saves ``(o, m, l)`` so the backward kernels
(``kernels/flash_attention_bwd.py``) re-derive the probabilities from the
same :func:`datapath.online_softmax_update` arithmetic instead of
re-running the whole unfused forward graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import datapath as dp
from . import dispatch, tiling

_STATE_LANES = 128   # lane width of the (m, l) scratch rows


def attention_blockspecs(bq: int, bkv: int, g: int, hd: int, hv: int):
    """(in_specs for (q_pos, kv_valid, q, k, v), out_spec) shared by every
    flash kernel flavor, over the head-major kernel layout of
    :func:`kernel_operands`.  Index maps take (b, head, q_tile, *rest)
    with the kv tile as the LAST grid dim, so the same specs serve the
    float kernel's 4D grid and the int kernel's 5D (extra sweep dim) grid.

    Every block's last two dims are (sublane-aligned rows, full minor
    dim) — the TPU block rule — which the token-major (B, T, K, h) layout
    cannot give a one-head block.
    """
    in_specs = [
        pl.BlockSpec((1, bq, 1), lambda b_, h_, qi, *r: (b_, qi, 0)),
        pl.BlockSpec((1, 1, bkv), lambda b_, h_, qi, *r: (b_, 0, r[-1])),
        pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, qi, *r: (b_, h_, qi, 0)),
        pl.BlockSpec((1, 1, bkv, hd),
                     lambda b_, h_, qi, *r: (b_, h_ // g, r[-1], 0)),
        pl.BlockSpec((1, 1, bkv, hv),
                     lambda b_, h_, qi, *r: (b_, h_ // g, r[-1], 0)),
    ]
    out_spec = pl.BlockSpec((1, 1, bq, hv),
                            lambda b_, h_, qi, *r: (b_, h_, qi, 0))
    return in_specs, out_spec


def heads_major(x):
    """(B, S, K, G, d) query-layout array -> (B, K*G, S, d) kernel layout."""
    b, s, kh, g, d = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(b, kh * g, s, d)


def tokens_major(x, kh: int):
    """Inverse of :func:`heads_major`: (B, K*G, S, d) -> (B, S, K, G, d)."""
    b, h, s, d = x.shape
    return x.reshape(b, kh, h // kh, s, d).transpose(0, 3, 1, 2, 4)


def kernel_operands(q, q_pos, k, v, kv_valid, bq: int, bkv: int):
    """Pad the five blocked-attention operands to the (bq, bkv) grid and
    lay them out for the kernels: q (B, K*G, S, h), k (B, K, T, h),
    v (B, K, T, hv), q_pos (B, S, 1) and kv_valid (B, 1, T) int32.

    One definition for every blocked flash flavor: q/q_pos pad along the
    query axis, k/v/kv_valid along the kv axis (validity pads with 0, so
    padded keys are invalid)."""
    qf, _ = tiling.pad_dim(q, 1, bq)
    qp, _ = tiling.pad_dim(q_pos.astype(jnp.int32), 1, bq)
    kf, _ = tiling.pad_dim(k, 1, bkv)
    vf, _ = tiling.pad_dim(v, 1, bkv)
    valid, _ = tiling.pad_dim(kv_valid.astype(jnp.int32), 1, bkv, value=0)
    return (heads_major(qf), qp[:, :, None], kf.transpose(0, 2, 1, 3),
            vf.transpose(0, 2, 1, 3), valid[:, None, :])


def vmem_plan(s_q: int, t_kv: int, hd: int, hv: int, g: int = 1):
    """Static VMEM residency of the forward float kernel at this shape.

    {call_name: {ref_name: (block_shape, dtype)}} with ``in:``/``out:``/
    ``scratch:`` key prefixes — ``repro.analysis.vmem`` prices each call
    as 2x(in+out tiles, double-buffered) + scratch against
    ``tiling.VMEM_CORE_BUDGET`` and cross-checks the shapes against the
    traced kernel's ref avals.  Must mirror the pallas_call specs above
    exactly (the audit fails on drift, not this module).
    """
    bq, bkv = tiling.attention_blocks(s_q, t_kv)
    return {"flash_fwd": {
        "in:q_pos": ((1, bq, 1), jnp.int32),
        "in:kv_valid": ((1, 1, bkv), jnp.int32),
        "in:q": ((1, 1, bq, hd), jnp.float32),
        "in:k": ((1, 1, bkv, hd), jnp.float32),
        "in:v": ((1, 1, bkv, hv), jnp.float32),
        "out:o": ((1, 1, bq, hv), jnp.float32),
        "out:m": ((1, 1, bq, 1), jnp.float32),
        "out:l": ((1, 1, bq, 1), jnp.float32),
        "scratch:m": ((bq, _STATE_LANES), jnp.float32),
        "scratch:l": ((bq, _STATE_LANES), jnp.float32),
        "scratch:acc": ((bq, tiling.scratch_lanes(hv)), jnp.float32),
    }}


def rowstat_blockspec(bq: int, lanes: int = 1):
    """BlockSpec for the (B, K*G, S, lanes) per-row statistic arrays on
    the forward/dq grid layout (b, head, q_tile, *rest)."""
    return pl.BlockSpec((1, 1, bq, lanes),
                        lambda b_, h_, qi, *r: (b_, h_, qi, 0))


def masked_score_block(q, kb, q_pos, valid, kv_tile: int, *,
                       block_kv: int, causal: bool, t_kv: int):
    """(masked scores, mask) tile — ONE definition of the flash masking.

    Scores take ``datapath.MASK_VALUE`` for user-invalid / causally
    masked keys (matching the naive path bitwise) and ``-inf`` for
    tiling-padded phantom keys, which must carry NO mass.  Shared by the
    forward body and both backward kernels so forward and backward can
    never disagree on which keys are "off".  ``q_pos`` is the (bq, 1)
    column of query positions (or one scalar position) and ``valid`` the
    (1, bkv) int validity row of the tile.  The mask is returned because
    the backward must zero dS where the score was replaced by the
    constant MASK_VALUE — the ``jnp.where`` routes no gradient into the
    untaken branch, and the reference VJP therefore sends exactly 0
    through masked positions while their (tiny but nonzero) probability
    mass still reaches dV.
    """
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bkv)
    mask = valid != 0                                     # (1, bkv) -> bcast
    kv_pos = kv_tile * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    if causal:
        mask = mask & (kv_pos <= q_pos)
    s = jnp.where(mask, s, dp.MASK_VALUE)
    return jnp.where(kv_pos < t_kv, s, -jnp.inf), \
        jnp.broadcast_to(mask, s.shape)


def _flash_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                block_kv: int, causal: bool, t_kv: int, with_stats: bool):
    if with_stats:
        m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    kj = pl.program_id(3)
    hv = o_ref.shape[-1]

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, dp.MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, h) pre-scaled
    kb = k_ref[0, 0].astype(jnp.float32)                  # (bkv, h)
    vb = v_ref[0, 0].astype(jnp.float32)                  # (bkv, hv)
    s, _ = masked_score_block(q, kb, qpos_ref[0], valid_ref[0], kj,
                              block_kv=block_kv, causal=causal, t_kv=t_kv)

    m, l = m_ref[:, :1], l_ref[:, :1]                     # (bq, 1)
    m_new, l_new, p, corr = dp.online_softmax_update(m, l, s)
    # acc scratch is lane-rounded (hv may be off the 128 grid — MLA);
    # only the live [:, :hv] slice carries data
    acc_ref[:, :hv] = acc_ref[:, :hv] * corr + jnp.dot(
        p, vb, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        out = dp.online_softmax_finish(l_ref[:, :1], acc_ref[:, :hv])
        o_ref[0, 0] = out.astype(o_ref.dtype)
        if with_stats:
            m_out_ref[0, 0] = m_ref[:, :1]
            l_out_ref[0, 0] = l_ref[:, :1]


def _flash_fwd_call(q, k, v, q_pos, kv_valid, *, causal: bool, bq: int,
                    bkv: int, interpret: bool, with_stats: bool):
    """Padded forward pallas_call; ``q`` must already be pre-scaled f32.

    ``with_stats=True`` (the grad/fwd path) returns (o, m, l) with m/l
    the (B, K, G, S) per-row online-softmax statistics — the backward
    kernels' residuals.  ``with_stats=False`` (the inference primal)
    returns o alone, so forward-only calls never pay the extra stat HBM
    writes.  Everything is sliced back to the logical sequence length.
    """
    b, s_q, kh, g, hd = q.shape
    t = k.shape[1]
    hv = v.shape[-1]
    qf, qp, kf, vf, valid = kernel_operands(q, q_pos, k, v, kv_valid,
                                            bq, bkv)
    s_p, t_p = qf.shape[2], kf.shape[2]

    in_specs, out_spec = attention_blockspecs(bq, bkv, g, hd, hv)
    stat_spec = rowstat_blockspec(bq)
    o_shape = jax.ShapeDtypeStruct((b, kh * g, s_p, hv), v.dtype)
    stat_shape = jax.ShapeDtypeStruct((b, kh * g, s_p, 1), jnp.float32)
    grid = (b, kh * g, s_p // bq, t_p // bkv)
    out = pl.pallas_call(
        functools.partial(_flash_body, block_kv=bkv, causal=causal,
                          t_kv=t, with_stats=with_stats),
        grid=grid,
        in_specs=in_specs,
        out_specs=([out_spec, stat_spec, stat_spec] if with_stats
                   else out_spec),
        out_shape=([o_shape, stat_shape, stat_shape] if with_stats
                   else o_shape),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATE_LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, _STATE_LANES), jnp.float32),  # running sum l
            pltpu.VMEM((bq, tiling.scratch_lanes(hv)),
                       jnp.float32),                      # weighted-v acc
        ],
        interpret=interpret,
    )(qp, valid, qf, kf, vf)
    if not with_stats:
        return tiling.unpad(tokens_major(out, kh), 1, s_q)
    o, m, l = out
    return (tiling.unpad(tokens_major(o, kh), 1, s_q),
            tiling.unpad(m.reshape(b, kh, g, s_p), 3, s_q),
            tiling.unpad(l.reshape(b, kh, g, s_p), 3, s_q))


def flash_attention_pallas(q, k, v, *, q_pos, kv_valid, causal: bool = True,
                           scale: float | None = None,
                           block_q: int | None = None,
                           block_kv: int | None = None,
                           interpret: bool | None = None,
                           return_stats: bool = False):
    """Blocked flash attention; see module docstring for shapes/masking.

    ``scale`` rides as a TRACED operand (folded into the q pre-scale
    before the kernel), so distinct head-dim/user scales share one
    compilation — only genuinely structural args (blocks, causal,
    interpret) are jit-static.

    Differentiable: the custom VJP runs the dedicated dq and dk/dv Pallas
    kernels (``kernels/flash_attention_bwd.py``) from the saved
    ``(o, m, l)`` residuals — the pure-JAX blocked path (models/flash.py)
    remains the reference the backward is pinned against in tests.

    ``return_stats=True`` returns ``(out, m, l)`` with the (B, K, G, S)
    per-row statistics of the pre-scaled scores (forward-only form, for
    residual-contract parity tests).
    """
    hd = q.shape[-1]
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / hd ** 0.5) if scale is None else scale
    bq, bkv = tiling.attention_blocks(q.shape[1], k.shape[1])
    bq = bq if block_q is None else block_q
    bkv = bkv if block_kv is None else block_kv
    return _flash_pallas_jit(q, k, v, q_pos, kv_valid,
                             jnp.float32(scale), causal=causal, block_q=bq,
                             block_kv=bkv, interpret=interpret,
                             return_stats=return_stats)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "interpret", "return_stats"))
def _flash_pallas_jit(q, k, v, q_pos, kv_valid, scale, *, causal: bool,
                      block_q: int, block_kv: int, interpret: bool,
                      return_stats: bool = False):
    bq, bkv = block_q, block_kv
    # fold the traced scale into q HERE, outside the custom_vjp, so (a) no
    # tracer is closed over by fwd/bwd and (b) d(scale) flows through the
    # multiply for free while the kernel itself stays scale-free
    q = q.astype(jnp.float32) * scale

    if return_stats:
        return _flash_fwd_call(q, k, v, q_pos, kv_valid, causal=causal,
                               bq=bq, bkv=bkv, interpret=interpret,
                               with_stats=True)

    # q_pos / kv_valid ride along as explicit primals (closing over them
    # would leak the enclosing jit's tracers into the custom_vjp jaxpr);
    # being integer/bool they get float0 cotangents.
    @jax.custom_vjp
    def run(q_, k_, v_, q_pos_, kv_valid_):
        # the non-differentiated primal: stats are only a backward
        # residual, so inference calls skip their HBM writes entirely
        return _flash_fwd_call(q_, k_, v_, q_pos_, kv_valid_,
                               causal=causal, bq=bq, bkv=bkv,
                               interpret=interpret, with_stats=False)

    def fwd(q_, k_, v_, q_pos_, kv_valid_):
        o, m, l = _flash_fwd_call(q_, k_, v_, q_pos_, kv_valid_,
                                  causal=causal, bq=bq, bkv=bkv,
                                  interpret=interpret, with_stats=True)
        return o, (q_, k_, v_, o, m, l, q_pos_, kv_valid_)

    def bwd(res, gy):
        import numpy as np
        from .flash_attention_bwd import flash_attention_bwd_pallas
        q_, k_, v_, o, m, l, q_pos_, kv_valid_ = res
        # q_ is already pre-scaled, so the backward kernels run scale-free
        # (the scale's own gradient flows through the fold-in multiply)
        dq, dk, dv = flash_attention_bwd_pallas(
            q_, k_, v_, o, m, l, gy, q_pos=q_pos_, kv_valid=kv_valid_,
            causal=causal, block_q=bq, block_kv=bkv, interpret=interpret)
        f0 = jax.dtypes.float0
        return (dq, dk.astype(k_.dtype), dv.astype(v_.dtype),
                np.zeros(q_pos_.shape, f0), np.zeros(kv_valid_.shape, f0))

    run.defvjp(fwd, bwd)
    return run(q, k, v, q_pos, kv_valid)


def _attention_entry(q, k, v, *, q_pos, kv_valid, causal, scale,
                     softmax_impl="float", ring_axis=""):
    if softmax_impl != "float":
        raise ValueError(
            "attn_impl='flash_pallas' is the float blocked kernel and "
            f"cannot honor softmax_impl={softmax_impl!r} (a dualmode word "
            "contract) — use 'naive' or 'flash_pallas_int'")
    return flash_attention_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                  causal=causal, scale=scale)


dispatch.register_attention(
    "flash_pallas", _attention_entry,
    modes=("float",), grad=True,
    note="Pallas float kernel with custom VJP")
