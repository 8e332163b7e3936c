"""Pallas blocked flash attention on the BIT-ACCURATE int datapath.

``kernels/flash_attention.py`` streams the float log-domain form of
Eq. (10); this sibling streams the S5.10/int32 unit itself
(``repro.core.softmax_unit``), so the paper's dual-mode numerics run on
blocked shapes instead of silently degrading to fp32 the moment the
dispatcher picks a streamed path.

Two kernels live here:

``flash_pallas_int`` — ONE KV sweep, snapped-max mode.  The running max
is ceil-snapped to a power of two (``softmax_unit.snap_max_int``), which
makes every rescale-by-``exp2(m_old - m_new)`` an EXACT arithmetic shift
on int words: the PWL probability word depends only on ``t mod 2**16``
(max-independent), the max contributes an integer depth, and the
normalizer carry is one int32 partial sum per depth (the bucket vector
of ``softmax_unit.online_merge_int`` — a true word monoid).  The f32
weighted-value accumulator rescales by exact powers of two
(``snap_scale_f32``), so the kernel's output equals the whole-row
:func:`repro.core.softmax_unit.softmax_snap` reference with only f32
summation-order noise — and is BITWISE equal under an identity-v probe.

``flash_pallas_int3`` — the original three-sweep kernel, kept as the
pinned oracle of the UNSNAPPED unit: the classic rescale is not
multiplicative in words (the 8-piece exp2 is not multiplicative), so the
unsnapped recurrence must run max, sum, emit as three sequential sweeps
over the same KV tiles

    sweep 0  m <- max(m, max(block))            int32 S5.10 carry
    sweep 1  l <- l + sum(exp2 words >> guard)  int32 guard-shifted carry
    sweep 2  acc <- acc + dequant(prob words) @ v

telescoping to the EXACT whole-row
:func:`repro.core.softmax_unit.softmax_int` words.  KV is read 3x per q
tile — the bandwidth price the snapped kernel exists to remove.

Shapes, masking, and tiling match the float kernel: q (B,S,K,G,h),
k (B,T,K,h), v (B,T,K,hv) -> (B,S,K,G,hv); user-invalid or causally
masked keys score ``datapath.MASK_VALUE`` BEFORE quantization (the same
finite word the naive dual-mode path sees), while tiling-phantom keys
take the ``PHANTOM_Q`` sentinel whose exponential is the literal 0 word.
Scores quantize as ``quantize((q*scale) . k)`` in exactly the naive
path's operation order (scale folded into q in f32 before the dot), so
the S5.10 score words — and therefore the probability words — are
identical to naive ``softmax_impl='dualmode'`` (three-sweep) /
``'dualmode_snap'`` (one-sweep).

Forward-only: the int unit is step-quantized (gradients vanish a.e.), so
no VJP is defined and differentiating through these kernels raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import softmax_unit as unit
from repro.core.fixedpoint import EXP_FRAC, I32, T_FRAC, dequantize, quantize

from . import datapath as dp
from . import dispatch, tiling
from .flash_attention import (_STATE_LANES, attention_blockspecs,
                              kernel_operands, rowstat_blockspec,
                              tokens_major)


def int_score_words(q, kb, q_pos, valid, kv_tile, *, block_kv: int,
                    causal: bool, t_kv: int):
    """One tile of S5.10 score WORDS — the int twin of
    ``flash_attention.masked_score_block``, shared by every int kernel
    body (one-sweep, three-sweep, decode) so they can never disagree on
    masking or quantization order: mask to ``MASK_VALUE`` (the finite
    word the naive dual-mode path sees), quantize, then overwrite
    tiling-phantom positions with the ``PHANTOM_Q`` sentinel."""
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = valid != 0                                     # (1, bkv) -> bcast
    kv_pos = kv_tile * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    if causal:
        mask = mask & (kv_pos <= q_pos)
    s = jnp.where(mask, s, dp.MASK_VALUE)
    sq = quantize(s)                                      # S5.10 score words
    return jnp.where(kv_pos < t_kv, sq, I32(unit.PHANTOM_Q))


def slide_lanes(S, k):
    """Kernel-side bucket slide: S'[:, d] = S[:, d-k] (0-fill, drop past
    the last bucket).  Same words as ``softmax_unit.slide_buckets_int``
    but built from STATIC lane shifts (pad/slice) selected by the binary
    decomposition of k — no gathers, so it lowers on the TPU vector unit.
    """
    nb = unit.N_SNAP_BUCKETS
    S = jnp.where(k >= nb, 0, S)
    kc = jnp.minimum(k, nb - 1)
    for b in (1, 2, 4, 8):
        shifted = jnp.concatenate(
            [jnp.zeros(S.shape[:-1] + (b,), S.dtype), S[..., :nb - b]],
            axis=-1)
        S = jnp.where((kc & b) != 0, shifted, S)
    return S


def snap_tile_update(m, S, acc, sq, vb, guard_shift: int):
    """One KV tile of the snapped online recurrence — the kernel-shaped
    form of insert-then-merge, shared by the one-sweep flash body and the
    dual-mode decode body.

    m (rows, 1) int32 snapped carry, S (rows, N_SNAP_BUCKETS) int32
    bucket carry, acc (rows, hv) f32, sq (rows, bkv) S5.10 score words,
    vb (bkv, hv) f32.  Returns the updated (m, S, acc).  Words are
    bit-identical to folding ``online_partial_int`` of this tile into the
    carry with ``online_merge_int``; acc additionally accumulates the
    exact f32 numerators against vb.
    """
    t = unit.to_snap_domain(sq)
    m_new = jnp.maximum(
        m, unit.snap_max_int(jnp.max(t, axis=-1, keepdims=True)))
    k_corr = (m_new - m) >> T_FRAC
    p = unit.snap_prob_word(t, guard_shift)               # (rows, bkv)
    d = (m_new >> T_FRAC) - (t >> T_FRAC)
    S_blk = jnp.concatenate(
        [jnp.sum(jnp.where(d == kk, p, 0), axis=-1, keepdims=True)
         for kk in range(unit.N_SNAP_BUCKETS)], axis=-1)
    S_new = slide_lanes(S, k_corr) + S_blk
    num = p.astype(jnp.float32) * unit.snap_scale_f32(d)  # exact f32
    acc_new = acc * unit.snap_scale_f32(k_corr) + jnp.dot(
        num, vb, preferred_element_type=jnp.float32)
    return m_new, S_new, acc_new


# --------------------------------------------------------------------------
# one-sweep snapped kernel ('flash_pallas_int')
# --------------------------------------------------------------------------

def _flash_snap_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                     block_kv: int, causal: bool, t_kv: int,
                     guard_shift: int, with_partial: bool):
    if with_partial:
        m_out_ref, s_out_ref, m_ref, s_ref, acc_ref = rest
    else:
        m_ref, s_ref, acc_ref = rest
    kj = pl.program_id(3)
    hv = o_ref.shape[-1]
    nb = unit.N_SNAP_BUCKETS

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, unit.SNAP_MIN)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, h) pre-scaled
    kb = k_ref[0, 0].astype(jnp.float32)                  # (bkv, h)
    vb = v_ref[0, 0].astype(jnp.float32)                  # (bkv, hv)
    sq = int_score_words(q, kb, qpos_ref[0], valid_ref[0], kj,
                         block_kv=block_kv, causal=causal, t_kv=t_kv)

    m_new, S_new, acc_new = snap_tile_update(
        m_ref[:, :1], s_ref[:, :nb], acc_ref[:, :hv], sq, vb, guard_shift)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[:, :nb] = S_new
    acc_ref[:, :hv] = acc_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        if with_partial:
            # UNNORMALIZED partial out: the ring folds (m, S, acc) across
            # hops with the int monoid and finishes ONCE at the end
            o_ref[0, 0] = acc_ref[:, :hv]
            m_out_ref[0, 0] = m_ref[:, :1]
            s_out_ref[0, 0] = s_ref[:, :nb]
        else:
            l = unit.online_finish_int(s_ref[:, :nb])     # (bq,)
            out = acc_ref[:, :hv] / l[:, None].astype(jnp.float32)
            o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "interpret", "guard_shift",
    "with_partial"))
def _flash_snap_jit(q, k, v, q_pos, kv_valid, scale, *, causal: bool,
                    block_q: int, block_kv: int, interpret: bool,
                    guard_shift: int, with_partial: bool):
    b, s_q, kh, g, hd = q.shape
    t = k.shape[1]
    hv = v.shape[-1]
    bq, bkv = block_q, block_kv
    nb = unit.N_SNAP_BUCKETS
    # naive op order: q*scale in f32 BEFORE the dot (pins the score words)
    q = q.astype(jnp.float32) * scale

    qf, qp, kf, vf, valid = kernel_operands(q, q_pos, k, v, kv_valid,
                                            bq, bkv)
    s_p, t_p = qf.shape[2], kf.shape[2]

    in_specs, out_spec = attention_blockspecs(bq, bkv, g, hd, hv)
    grid = (b, kh * g, s_p // bq, t_p // bkv)
    if with_partial:
        out_specs = [out_spec, rowstat_blockspec(bq),
                     rowstat_blockspec(bq, nb)]
        out_shape = [
            jax.ShapeDtypeStruct((b, kh * g, s_p, hv), jnp.float32),
            jax.ShapeDtypeStruct((b, kh * g, s_p, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, kh * g, s_p, nb), jnp.int32),
        ]
    else:
        out_specs = out_spec
        out_shape = jax.ShapeDtypeStruct((b, kh * g, s_p, hv), v.dtype)
    out = pl.pallas_call(
        functools.partial(_flash_snap_body, block_kv=bkv, causal=causal,
                          t_kv=t, guard_shift=guard_shift,
                          with_partial=with_partial),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, _STATE_LANES), jnp.int32),    # snapped max m
            pltpu.VMEM((bq, _STATE_LANES), jnp.int32),    # depth buckets S
            pltpu.VMEM((bq, tiling.scratch_lanes(hv)),
                       jnp.float32),                      # weighted-v acc
        ],
        interpret=interpret,
    )(qp, valid, qf, kf, vf)
    if with_partial:
        acc, m, S = out
        return (tiling.unpad(tokens_major(acc, kh), 1, s_q),
                tiling.unpad(m.reshape(b, kh, g, s_p), 3, s_q),
                tiling.unpad(S.reshape(b, kh, g, s_p, nb), 3, s_q))
    return tiling.unpad(tokens_major(out, kh), 1, s_q)


def flash_attention_pallas_int(q, k, v, *, q_pos, kv_valid,
                               causal: bool = True,
                               scale: float | None = None,
                               block_q: int | None = None,
                               block_kv: int | None = None,
                               interpret: bool | None = None,
                               guard_shift: int | None = None,
                               return_partial: bool = False):
    """ONE-sweep blocked dual-mode attention (snapped-max unit).

    Output is the naive ``softmax_impl='dualmode_snap'`` attention with
    identical (p, d, l) words; only the final f32 numerator@v summation
    order differs (blocked vs whole-row), and under an identity-v probe
    the outputs are bitwise equal.

    ``guard_shift`` defaults to the whole-row rule for an n=t row; ring
    callers override it with the GLOBAL row guard so hop partials merge
    word-exact.  ``return_partial=True`` returns the UNNORMALIZED
    ``(acc, m, S)`` — acc (B,S,K,G,hv) f32, m (B,K,G,S) int32 snapped,
    S (B,K,G,S,N_SNAP_BUCKETS) int32 — the mergeable monoid partial.
    """
    hd = q.shape[-1]
    t = k.shape[1]
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / hd ** 0.5) if scale is None else scale
    if guard_shift is None:
        guard_shift = max(0, t.bit_length() - 16)
    bq, bkv = tiling.attention_blocks(q.shape[1], t)
    bq = bq if block_q is None else block_q
    bkv = bkv if block_kv is None else block_kv
    return _flash_snap_jit(q, k, v, q_pos, kv_valid, jnp.float32(scale),
                           causal=causal, block_q=bq, block_kv=bkv,
                           interpret=interpret, guard_shift=guard_shift,
                           with_partial=return_partial)


# --------------------------------------------------------------------------
# three-sweep unsnapped kernel ('flash_pallas_int3', the pinned oracle)
# --------------------------------------------------------------------------

def _flash_int_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref,
                    o_ref, m_ref, l_ref, acc_ref, *, block_kv: int,
                    causal: bool, t_kv: int, guard_shift: int):
    phase = pl.program_id(3)
    kj = pl.program_id(4)
    hv = o_ref.shape[-1]

    @pl.when((phase == 0) & (kj == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, unit.PHANTOM_Q)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, h) pre-scaled
    kb = k_ref[0, 0].astype(jnp.float32)                  # (bkv, h)
    sq = int_score_words(q, kb, qpos_ref[0], valid_ref[0], kj,
                         block_kv=block_kv, causal=causal, t_kv=t_kv)

    m = m_ref[:, :1]                                      # (bq, 1)

    @pl.when(phase == 0)
    def _():
        m_ref[...] = jnp.broadcast_to(unit.online_max_int(m, sq),
                                      m_ref.shape)

    @pl.when(phase == 1)
    def _():
        l_new = unit.online_sum_int(l_ref[:, :1], m, sq, guard_shift)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(phase == 2)
    def _():
        p = unit.online_probs_int(m, l_ref[:, :1], sq, guard_shift)
        pf = dequantize(p, EXP_FRAC)                      # exact prob floats
        vb = v_ref[0, 0].astype(jnp.float32)              # (bkv, hv)
        # acc scratch is lane-rounded (hv may be off the 128 grid — MLA);
        # only the live [:, :hv] slice carries data
        acc_ref[:, :hv] = acc_ref[:, :hv] + jnp.dot(
            pf, vb, preferred_element_type=jnp.float32)

    @pl.when((phase == 2) & (kj == pl.num_programs(4) - 1))
    def _():
        o_ref[0, 0] = acc_ref[:, :hv].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "interpret"))
def _flash_int_jit(q, k, v, q_pos, kv_valid, scale, *, causal: bool,
                   block_q: int, block_kv: int, interpret: bool):
    b, s_q, kh, g, hd = q.shape
    t = k.shape[1]
    hv = v.shape[-1]
    bq, bkv = block_q, block_kv
    # same guard as the whole-row unit applies for an n=t row
    guard_shift = max(0, t.bit_length() - 16)
    # fold the traced scale into q in the naive path's op order (q*scale
    # in f32 BEFORE the dot): the per-element score dot is then bitwise
    # identical to the naive einsum, keeping the quantized words pinned
    q = q.astype(jnp.float32) * scale

    qf, qp, kf, vf, valid = kernel_operands(q, q_pos, k, v, kv_valid,
                                            bq, bkv)
    s_p, t_p = qf.shape[2], kf.shape[2]

    in_specs, out_spec = attention_blockspecs(bq, bkv, g, hd, hv)
    # only the emit sweep reads v: pin its block index to 0 during the
    # max/sum sweeps (ph // 2 = 0, 0, 1) so v HBM->VMEM traffic stays ~1x
    # instead of riding every kv step of all three sweeps
    in_specs[4] = pl.BlockSpec(
        (1, 1, bkv, hv),
        lambda b_, h_, qi, ph, kj: (b_, h_ // g, kj * (ph // 2), 0))
    grid = (b, kh * g, s_p // bq, 3, t_p // bkv)          # 3 = sweeps
    out = pl.pallas_call(
        functools.partial(_flash_int_body, block_kv=bkv, causal=causal,
                          t_kv=t, guard_shift=guard_shift),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh * g, s_p, hv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATE_LANES), jnp.int32),    # running max m
            pltpu.VMEM((bq, _STATE_LANES), jnp.int32),    # guard-shifted l
            pltpu.VMEM((bq, tiling.scratch_lanes(hv)),
                       jnp.float32),                      # weighted-v acc
        ],
        interpret=interpret,
    )(qp, valid, qf, kf, vf)
    return tiling.unpad(tokens_major(out, kh), 1, s_q)


def flash_attention_pallas_int3(q, k, v, *, q_pos, kv_valid,
                                causal: bool = True,
                                scale: float | None = None,
                                block_q: int | None = None,
                                block_kv: int | None = None,
                                interpret: bool | None = None):
    """THREE-sweep blocked dual-mode attention (unsnapped unit oracle).

    Output is the naive ``softmax_impl='dualmode'`` attention with the
    identical int probability words; only the final f32 prob@v
    accumulation order differs (blocked vs whole-row sum).
    """
    hd = q.shape[-1]
    t = k.shape[1]
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / hd ** 0.5) if scale is None else scale
    bq, bkv = tiling.attention_blocks(q.shape[1], t)
    bq = bq if block_q is None else block_q
    bkv = bkv if block_kv is None else block_kv
    return _flash_int_jit(q, k, v, q_pos, kv_valid,
                          jnp.float32(scale), causal=causal, block_q=bq,
                          block_kv=bkv, interpret=interpret)


def _attention_entry(q, k, v, *, q_pos, kv_valid, causal, scale,
                     softmax_impl="dualmode", ring_axis=""):
    # the one-sweep kernel runs on snap words internally, so it honors
    # BOTH int contracts: 'dualmode' and the snapped monoid
    # 'dualmode_snap' produce the identical finished words here
    if softmax_impl not in ("dualmode", "dualmode_snap"):
        raise ValueError(
            "attn_impl='flash_pallas_int' IS the bit-accurate unit; it "
            f"cannot honor softmax_impl={softmax_impl!r} (use 'dualmode', "
            "or a float impl: 'flash'/'flash_pallas')")
    return flash_attention_pallas_int(q, k, v, q_pos=q_pos,
                                      kv_valid=kv_valid, causal=causal,
                                      scale=scale)


def _attention_entry3(q, k, v, *, q_pos, kv_valid, causal, scale,
                      softmax_impl="dualmode", ring_axis=""):
    if softmax_impl != "dualmode":
        raise ValueError(
            "attn_impl='flash_pallas_int3' IS the bit-accurate unit; it "
            f"cannot honor softmax_impl={softmax_impl!r} (use 'dualmode', "
            "or a float impl: 'flash'/'flash_pallas')")
    return flash_attention_pallas_int3(q, k, v, q_pos=q_pos,
                                       kv_valid=kv_valid, causal=causal,
                                       scale=scale)


def vmem_plan(s_q: int, t_kv: int, hd: int, hv: int, g: int = 1):
    """Static VMEM residency of both int kernels (see
    ``flash_attention.vmem_plan`` for the contract).  The one-sweep plan
    prices the partial-emitting variant — its extra (m, S) outputs are
    the worst case."""
    bq, bkv = tiling.attention_blocks(s_q, t_kv)
    nb = unit.N_SNAP_BUCKETS
    common = {
        "in:q_pos": ((1, bq, 1), jnp.int32),
        "in:kv_valid": ((1, 1, bkv), jnp.int32),
        "in:q": ((1, 1, bq, hd), jnp.float32),
        "in:k": ((1, 1, bkv, hd), jnp.float32),
        "in:v": ((1, 1, bkv, hv), jnp.float32),
        "out:o": ((1, 1, bq, hv), jnp.float32),
        "scratch:m": ((bq, _STATE_LANES), jnp.int32),
        "scratch:s": ((bq, _STATE_LANES), jnp.int32),
        "scratch:acc": ((bq, tiling.scratch_lanes(hv)), jnp.float32),
    }
    return {
        "flash_int_onesweep": dict(
            common,
            **{"out:part_m": ((1, 1, bq, 1), jnp.int32),
               "out:part_s": ((1, 1, bq, nb), jnp.int32)}),
        "flash_int_threesweep": dict(common),
    }


dispatch.register_attention(
    "flash_pallas_int", _attention_entry,
    modes=("dualmode", "dualmode_snap"), grad=False,
    note="snapped one-sweep int kernel (forward-only)")
dispatch.register_attention(
    "flash_pallas_int3", _attention_entry3,
    modes=("dualmode",), grad=False,
    note="three-sweep int oracle (forward-only)")
