"""The dual-mode softmax unit (paper §III, Fig. 2/3) — bit-accurate emulation.

Normal mode implements Eq. (10) — division in the logarithm domain:

    y_i = exp(x_i - max(x) - log(sum_j exp(x_j - max(x))))
        = 2**(t_i - lmax - log2(sum_j 2**(t_j - lmax)))     with t = x*log2(e)

Each exponential is decomposed 2**t = 2**u * 2**v (u integer -> shift,
v in [0,1) -> 8-piece PWL); the log uses a leading-one detector plus a
mantissa PWL (the forward log converter of [Kim 2006]).

GELU mode (Fig. 3) computes, per element z (Eq. 8):

    k       = sqrt(2/pi) * (z + 0.044715 z^3)
    GELU(z) = z * softmax_1^2([k, -k])

by running the *same* exp/log datapath independently on the two-element
vector [k, -k].  SiLU mode (ours, beyond-paper) is the exact identity
SiLU(z) = z * softmax_1^2([z/2, -z/2]) — only the k-datapath differs.

Everything here is int32 (inputs S5.10) and jnp-traceable, so the same code
is the Pallas kernel body's arithmetic and the oracle for its tests.

This module is the tree's single INT definition of the unit's arithmetic;
the float-lane form lives in ``repro.kernels.datapath`` (the only other
place the log2e / GELU-cubic constants appear).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .fixedpoint import (
    EXP_FRAC, I32, IN_FRAC, IN_MAX, IN_MIN, T_FRAC,
    dequantize, floor_log2, mantissa_frac, quantize, sat_rshift,
)
from .pwl import exp2_frac_int, log2_mant_int

# fixed-point constants (the ROM words of the datapath)
_LOG2E_FRAC = 12
LOG2E_Q = int(round(math.log2(math.e) * (1 << _LOG2E_FRAC)))        # 5909
GELU_A_Q = int(round(0.044715 * (1 << 16)))                         # cubic coeff
GELU_C_Q = int(round(math.sqrt(2.0 / math.pi) * (1 << 14)))         # sqrt(2/pi)

# Sentinel word for positions that must carry EXACTLY zero mass (the int
# analogue of the float paths' -inf on tiling-phantom keys).  Any word w
# with w - m <= -(32 << IN_FRAC) hits the input saturation of
# ``_to_log2_domain`` and its exponential underflows the 14-bit output to
# the literal 0 word, so it contributes nothing to the sum, the probs, or
# (being far below any S5.10 word) the running max.  -2**20 keeps that
# margin for every possible S5.10 max (>= IN_MIN) with int32 to spare.
PHANTOM_Q = -(1 << 20)


def _to_log2_domain(d, in_frac: int):
    """t = d * log2(e) at scale 2**-T_FRAC (d at scale 2**-in_frac, d<=0).

    d is saturated at -32 (exp(-32) ~ 2**-46 underflows the 14-bit output
    anyway) — this keeps the int32 product in range for any input pair,
    exactly like the input saturation stage of the hardware unit.
    """
    d = jnp.maximum(d.astype(I32), I32(-32) << in_frac)
    return (d * I32(LOG2E_Q)) >> (in_frac + _LOG2E_FRAC - T_FRAC)


def _exp2_int(t):
    """2**t for t <= 0 at scale 2**-T_FRAC -> result at scale 2**-EXP_FRAC.

    Split t = u + v, u = floor(t) (arithmetic shift), v in [0,1):
    2**u is a right shift of the PWL 2**v value.
    """
    u = t >> T_FRAC                                   # floor (t<=0 -> u<=0)
    v = t - (u << T_FRAC)                             # in [0, 2**T_FRAC)
    p = exp2_frac_int(v)                              # [1,2) @ 2**-EXP_FRAC
    return sat_rshift(p, -u)


def _log2_int(s, s_frac: int):
    """log2 of s (int > 0 at scale 2**-s_frac) at scale 2**-T_FRAC."""
    e_pos = floor_log2(s)
    frac = mantissa_frac(s, e_pos, T_FRAC)
    log2m = log2_mant_int(frac)
    return ((e_pos - s_frac) << T_FRAC) + log2m


def softmax_int(x_fx, axis: int = -1, guard_shift: int | None = None):
    """Normal mode: Eq. (10) over `axis`.  x_fx int32 @ S5.10.

    Returns probabilities at scale 2**-EXP_FRAC (int32).
    `guard_shift` down-shifts each exponent before the sum so that rows up
    to 2**(16+guard_shift) elements cannot overflow the int32 accumulator.
    """
    n = x_fx.shape[axis]
    if guard_shift is None:
        guard_shift = max(0, n.bit_length() - 16)
    m = jnp.max(x_fx, axis=axis, keepdims=True)
    t = _to_log2_domain(x_fx - m, IN_FRAC)            # <= 0
    e = _exp2_int(t)                                  # @ 2**-EXP_FRAC
    s = jnp.sum(e >> guard_shift, axis=axis, keepdims=True)
    s = jnp.maximum(s, 1)                             # log(0) guard
    log2s = _log2_int(s, EXP_FRAC - guard_shift)      # @ 2**-T_FRAC
    w = t - log2s                                     # log2 of prob, <= ~0
    return _exp2_int(jnp.minimum(w, 0))


def _pair_softmax_first_int(k_fx, k_frac: int):
    """softmax_1^2([k, -k]) through the shared exp/log datapath.

    k_fx int32 at scale 2**-k_frac.  Returns sigma(2k) @ 2**-EXP_FRAC.
    This is the GELU-mode inner loop: max = |k| (the pairwise max-tree tap),
    two exponents, the pair adder-tree tap, one pair log unit, one exp.
    """
    amax = jnp.abs(k_fx)
    t1 = _to_log2_domain(k_fx - amax, k_frac)
    t2 = _to_log2_domain(-k_fx - amax, k_frac)
    e1 = _exp2_int(t1)
    e2 = _exp2_int(t2)
    s = jnp.maximum(e1 + e2, 1)                       # in (2**14, 2**15]
    log2s = _log2_int(s, EXP_FRAC)
    w = jnp.minimum(t1 - log2s, 0)
    return _exp2_int(w)


def gelu_k_int(z_fx):
    """k = sqrt(2/pi) * (z + 0.044715 z^3) in S5.10 -> int32 @ 2**-IN_FRAC.

    The cubic-path input is saturated at |z| <= 8 (k(8) = 24.6 already
    drives sigma(2k) to exactly 0/1 in 14-bit arithmetic), which bounds
    every int32 intermediate — the hardware's input saturation stage.
    """
    z = jnp.clip(z_fx.astype(I32), I32(-8) << IN_FRAC, I32(8) << IN_FRAC)
    z2 = (z * z) >> IN_FRAC
    z3 = (z2 * z) >> IN_FRAC
    az3 = (z3 * I32(GELU_A_Q)) >> 16
    return ((z + az3) * I32(GELU_C_Q)) >> 14


def gelu_int(z_fx):
    """GELU mode (Eq. 8): z * softmax_1^2([k, -k]).  S5.10 -> S5.10."""
    k = gelu_k_int(z_fx)
    sig = _pair_softmax_first_int(k, IN_FRAC)          # @ 2**-EXP_FRAC
    return (z_fx.astype(I32) * sig) >> EXP_FRAC


def silu_int(z_fx):
    """Exact-identity SiLU mode: z * softmax_1^2([z/2, -z/2]).

    k = z/2 is represented losslessly by reinterpreting z at scale
    2**-(IN_FRAC+1) — zero extra datapath.
    """
    sig = _pair_softmax_first_int(z_fx.astype(I32), IN_FRAC + 1)
    return (z_fx.astype(I32) * sig) >> EXP_FRAC


# --- blocked / online evaluation of normal mode -----------------------------
#
# The float flash recurrence corrects old partial sums by exp(m_old - m_new)
# when the running max moves; that correction is NOT exact in the PWL int
# domain (the 8-piece exp2 is not multiplicative), so a one-sweep online
# rescale would change words.  What IS exact: the max fold and the
# guard-shifted sum fold are associative int32 reductions, and the emit
# step is elementwise given the final (m, l).  Streaming therefore runs
# three KV sweeps — max, sum, emit — each an online fold whose carry
# (m, then l) never leaves the int domain, and ANY blocking schedule
# telescopes to the exact whole-row :func:`softmax_int` words.  These
# three steps are jnp-traceable; the pure-jnp blocked oracle below runs
# them.  The SNAPPED-max mode further down removes the
# three-sweep restriction: snapping the max to a power of two makes the
# rescale an exact bit-shift, yielding a true one-sweep word monoid.

def online_max_int(m, x_blk, axis: int = -1):
    """Sweep 1 fold: running row max.  Init carry with ``PHANTOM_Q``."""
    return jnp.maximum(m, jnp.max(x_blk.astype(I32), axis=axis,
                                  keepdims=True))


def online_sum_int(l, m, x_blk, guard_shift: int, axis: int = -1):
    """Sweep 2 fold: guard-shifted int32 row-sum carry (init 0).

    ``m`` is the FINAL sweep-1 max; the guard shift bounds the carry for
    rows up to 2**(16+guard_shift) elements exactly as in the whole-row
    unit, so the blocked carry can never overflow int32 either.
    """
    t = _to_log2_domain(x_blk.astype(I32) - m, IN_FRAC)
    e = _exp2_int(t)
    return l + jnp.sum(e >> guard_shift, axis=axis, keepdims=True)


def online_probs_int(m, l, x_blk, guard_shift: int):
    """Sweep 3 emit: this block's probability words @ 2**-EXP_FRAC.

    Elementwise given the final (m, l) — identical to the whole-row tail
    of :func:`softmax_int` (same log2, same subtraction, same exp2).
    """
    t = _to_log2_domain(x_blk.astype(I32) - m, IN_FRAC)
    log2s = _log2_int(jnp.maximum(l, 1), EXP_FRAC - guard_shift)
    return _exp2_int(jnp.minimum(t - log2s, 0))


def softmax_int_blocked(x_fx, block: int, guard_shift: int | None = None):
    """Whole-row normal mode evaluated as the three blocked sweeps.

    Pure-jnp driver over the last axis — the oracle that PROVES the
    telescoping: tests pin its output bit-identical to
    :func:`softmax_int` for any ``block`` (divisible or not).
    """
    n = x_fx.shape[-1]
    if guard_shift is None:
        guard_shift = max(0, n.bit_length() - 16)
    x_fx = x_fx.astype(I32)
    blocks = [x_fx[..., i:i + block] for i in range(0, n, block)]
    m = jnp.full(x_fx.shape[:-1] + (1,), PHANTOM_Q, I32)
    for b in blocks:
        m = online_max_int(m, b)
    l = jnp.zeros_like(m)
    for b in blocks:
        l = online_sum_int(l, m, b, guard_shift)
    return jnp.concatenate(
        [online_probs_int(m, l, b, guard_shift) for b in blocks], axis=-1)


# --- snapped-max mode: the word-exact online-softmax monoid ----------------
#
# Snap the running max UP to a multiple of 2**T_FRAC and the PWL rescale
# becomes multiplicative by construction: t_j - M keeps t_j's low T_FRAC
# bits (M is a multiple of 2**T_FRAC), so the PWL fraction word
# p_j = exp2_frac(t_j mod 2**T_FRAC) is MAX-INDEPENDENT and the max only
# selects an integer DEPTH d_j = (M - t_j) >> T_FRAC.  A max move by k
# octaves relabels every depth by +k — a pure shift, exact on int words.
#
# A scalar normalizer carry is still NOT schedule-invariant (sum-then-
# shift != shift-then-sum: 1+1 = 2, >>1 -> 1, while per-element 0+0 = 0),
# so the carry keeps one int32 partial sum PER DEPTH — a carry-save /
# Kulisch-style state (m, S[0..N_SNAP_BUCKETS)) whose merge is slide-by-k
# plus elementwise add: a TRUE monoid, bit-exact associative AND
# commutative, with identity (SNAP_MIN, zeros).  Depths beyond the last
# bucket are the unit's dynamic-range floor (N_SNAP_BUCKETS octaves below
# the max): those words are defined to carry exactly zero mass, which is
# schedule-invariant because an element's final depth depends only on the
# final max.  The finish collapses l = sum_d (S_d >> d) — each element
# shifted exactly once, after all same-depth words were summed at full
# width.
#
# Normalization is ONE f32 division at the end (SOLE-style guaranteed
# normalization): numerators float(p_j) * 2**-d_j are EXACT in f32 (p_j
# is a 15-bit word, the scale an exact power of two), so a streaming
# accumulator rescaled by 2**-k is bit-identical to the whole-row
# numerator — only f32 summation order can differ between schedules.

SNAP_MIN = -(1 << 30)     # sentinel carry: a multiple of 2**T_FRAC far
                          # below any real score's log2-domain word
N_SNAP_BUCKETS = 16       # depth range = the unit's 16-octave dynamic range


def to_snap_domain(x_fx):
    """ABSOLUTE log2-domain score t = x*log2(e) @ 2**-T_FRAC (int32).

    Unlike :func:`_to_log2_domain` this does not subtract a max first —
    snapped mode needs max-independent words.  |t| <= ~3.03e6 for any
    S5.10 input, so the int32 product has headroom.  ``PHANTOM_Q``
    sentinel words map straight to ``SNAP_MIN`` (their true t would
    overflow int32, and they must carry exactly zero mass anyway).
    """
    x = x_fx.astype(I32)
    t = (jnp.clip(x, IN_MIN, IN_MAX) * I32(LOG2E_Q)) \
        >> (IN_FRAC + _LOG2E_FRAC - T_FRAC)
    return jnp.where(x <= I32(PHANTOM_Q), I32(SNAP_MIN), t)


def snap_max_int(t_max):
    """Ceil-snap a log2-domain word UP to a multiple of 2**T_FRAC.

    exp2 of the snapped max is then exactly a power of two, so every
    rescale-by-``exp2(m_old - m_new)`` is an arithmetic shift.  SNAP_MIN
    is itself a multiple of 2**T_FRAC, so the sentinel is a fixed point.
    """
    t_max = t_max.astype(I32)
    return ((t_max + I32((1 << T_FRAC) - 1)) >> T_FRAC) << T_FRAC


def snap_prob_word(t, guard_shift: int):
    """The max-independent (guard-shifted) probability word of ``t``.

    ``t`` is an absolute :func:`to_snap_domain` word; because the snapped
    max is a multiple of 2**T_FRAC, ``t - M`` keeps t's low T_FRAC bits,
    so the PWL evaluates on ``t mod 2**T_FRAC`` alone — a 15-bit word in
    [2**EXP_FRAC, 2**(EXP_FRAC+1)) >> guard, independent of any max.
    SNAP_MIN sentinels produce the literal 0 word.
    """
    p = exp2_frac_int(t & I32((1 << T_FRAC) - 1)) >> guard_shift
    return jnp.where(t > I32(SNAP_MIN), p, 0)


def snap_scale_f32(d):
    """EXACT float32 ``2**-d`` for int depth d >= 0.

    Built by exponent-field construction (not a transcendental), so every
    consumer — whole-row oracle, one-sweep kernel, decode split fold,
    ring hop merge — multiplies by bit-identical scales.  Depths past the
    f32 normal range collapse to exact +0.0 (those words are below the
    dynamic-range floor anyway).
    """
    e = jnp.clip(I32(127) - d.astype(I32), 0, 254)
    return jax.lax.bitcast_convert_type(e << 23, jnp.float32)


def slide_buckets_int(S, k):
    """Relabel a bucket vector to a max ``k`` octaves deeper (k >= 0).

    S'[d] = S[d - k] with zero-fill; words sliding past the last bucket
    are dropped — their elements sit >= N_SNAP_BUCKETS octaves below the
    new max, the exactly-zero floor.  Slides compose additively
    (slide(k1) o slide(k2) == slide(k1+k2)), which is what makes the
    merge associative on ALL states, not just reachable ones.
    """
    idx = jnp.arange(N_SNAP_BUCKETS, dtype=I32)
    src = idx - k
    take = jnp.take_along_axis(
        S, jnp.clip(src, 0, N_SNAP_BUCKETS - 1), axis=-1)
    return jnp.where(src >= 0, take, 0)


def online_partial_int(x_blk, guard_shift: int, v=None, axis: int = -1):
    """Self-contained snapped partial (m, S, acc) of one block of words.

    The int twin of :func:`repro.kernels.datapath.online_softmax_partial`:
    ``m`` is the block's own ceil-snapped max (keepdims at ``axis``),
    ``S`` the per-depth bucket sums (bucket axis appended LAST), ``acc``
    the f32 unnormalized weighted-value accumulator (or the exact f32
    numerators themselves when ``v`` is None).  All-phantom blocks
    produce the merge identity ``(SNAP_MIN, 0, 0)``.
    """
    t = to_snap_domain(x_blk)
    m = snap_max_int(jnp.max(t, axis=axis, keepdims=True))
    p = snap_prob_word(t, guard_shift)
    d = (m >> T_FRAC) - (t >> T_FRAC)
    S = jnp.stack([jnp.sum(jnp.where(d == kk, p, 0), axis=axis)
                   for kk in range(N_SNAP_BUCKETS)], axis=-1)
    num = p.astype(jnp.float32) * snap_scale_f32(d)
    acc = num if v is None else jnp.einsum("...n,...nd->...d", num, v)
    return m, S, acc


def online_merge_int(part_a, part_b):
    """Word-exact merge of two snapped partials — the int monoid fold.

    The int twin of :func:`repro.kernels.datapath.online_softmax_merge`:
    each part is ``(m, S, acc)`` with ``m`` (..., 1) int32 snapped,
    ``S`` (..., N_SNAP_BUCKETS) int32 bucket sums, ``acc`` (..., d) f32.

        m   = max(m_a, m_b)
        S   = slide(S_a, (m-m_a)/2**T_FRAC) + slide(S_b, ...)
        acc = acc_a * 2**-k_a + acc_b * 2**-k_b   (exact f32 scales)

    ``m`` and ``S`` are bit-exact associative AND commutative (the slide
    is an exact relabeling, bucket adds are int32); ``acc`` rescales are
    exact f32 multiplies, so only its ADD order varies with the schedule.
    Identity element: ``(SNAP_MIN, 0, 0)``.
    """
    m_a, S_a, acc_a = part_a
    m_b, S_b, acc_b = part_b
    m = jnp.maximum(m_a, m_b)
    k_a = (m - m_a) >> T_FRAC
    k_b = (m - m_b) >> T_FRAC
    S = slide_buckets_int(S_a, k_a) + slide_buckets_int(S_b, k_b)
    acc = acc_a * snap_scale_f32(k_a) + acc_b * snap_scale_f32(k_b)
    return m, S, acc


def online_merge_n_int(m, S, acc, axis: int = 0):
    """Vectorized n-way fold of snapped partials stacked along ``axis``.

    The int twin of :func:`repro.kernels.datapath.online_softmax_merge_n`
    (the split-KV decode fold): one max, one slide, one sum.  ``axis``
    stays as a singleton on m/acc (shape-stable for the caller); the
    bucket axis of ``S`` is last.  Sentinel ``(SNAP_MIN, 0, 0)`` partials
    contribute exact zeros — including empty splits is a no-op.
    """
    m_all = jnp.max(m, axis=axis, keepdims=True)
    k = (m_all - m) >> T_FRAC
    S = jnp.sum(slide_buckets_int(S, k), axis=axis, keepdims=True)
    acc = jnp.sum(acc * snap_scale_f32(k), axis=axis, keepdims=True)
    return m_all, S, acc


def online_finish_int(S):
    """Exact bucketed normalizer: l = sum_d (S_d >> d), clamped >= 1.

    Scale 2**-(EXP_FRAC - guard_shift).  Each element was summed into
    exactly one bucket at full width BEFORE its depth shift, so l is
    schedule-invariant (the shift distributes over nothing).  Reduces the
    trailing bucket axis away.
    """
    l = jnp.sum(S >> jnp.arange(N_SNAP_BUCKETS, dtype=I32), axis=-1)
    return jnp.maximum(l, 1)


def snap_row_stats(x_fx, axis: int = -1, guard_shift: int | None = None):
    """Whole-row snapped statistics (p, d, l) — the streaming oracle.

    p: max-independent guard-shifted probability words (0 for sentinels),
    d: per-element depth below the ceil-snapped row max,
    l: the exact bucketed normalizer (keepdims at ``axis``).

    The guard-shift rule matches :func:`softmax_int`: rows up to
    2**(16+guard_shift) elements cannot overflow a bucket (each element
    lands in exactly ONE bucket).
    """
    n = x_fx.shape[axis]
    if guard_shift is None:
        guard_shift = max(0, n.bit_length() - 16)
    m, S, _ = online_partial_int(x_fx, guard_shift, axis=axis)
    t = to_snap_domain(x_fx)
    p = snap_prob_word(t, guard_shift)
    d = (m >> T_FRAC) - (t >> T_FRAC)
    return p, d, jnp.expand_dims(online_finish_int(S), axis)


def softmax_snap(x_fx, axis: int = -1, guard_shift: int | None = None):
    """Snapped-max normal mode over ``axis``: x_fx S5.10 -> f32 probs.

    prob_j = float(p_j) * 2**-d_j / float(l) — exact f32 numerators, one
    deterministic IEEE division.  This is the whole-row reference every
    streaming schedule telescopes to: the one-sweep kernel, the decode
    split fold, and the ring hop fold all reproduce (p, d, l) word-exact
    and therefore these exact probabilities.
    """
    p, d, l = snap_row_stats(x_fx, axis=axis, guard_shift=guard_shift)
    return p.astype(jnp.float32) * snap_scale_f32(d) / l.astype(jnp.float32)


def softmax_snap_blocked(x_fx, block: int, guard_shift: int | None = None):
    """Whole-row snapped mode evaluated as a blocked monoid fold.

    Pure-jnp driver over the last axis — the oracle that PROVES the
    telescoping: partials of arbitrary blocks fold with
    :func:`online_merge_int` and the result is bit-identical in (m, S)
    — hence in l and the probability words — to :func:`softmax_snap`
    for any ``block`` (divisible or not).
    """
    n = x_fx.shape[-1]
    if guard_shift is None:
        guard_shift = max(0, n.bit_length() - 16)
    x_fx = x_fx.astype(I32)
    lead = x_fx.shape[:-1]
    zero_acc = jnp.zeros(lead + (1,), jnp.float32)   # prob-word-only fold
    part = (jnp.full(lead + (1,), SNAP_MIN, I32),
            jnp.zeros(lead + (N_SNAP_BUCKETS,), I32),
            zero_acc)
    for i in range(0, n, block):
        m_b, S_b, _ = online_partial_int(x_fx[..., i:i + block], guard_shift)
        part = online_merge_int(part, (m_b, S_b, zero_acc))
    m, S, _ = part
    t = to_snap_domain(x_fx)
    p = snap_prob_word(t, guard_shift)
    d = (m >> T_FRAC) - (t >> T_FRAC)
    l = jnp.expand_dims(online_finish_int(S), -1)
    return p.astype(jnp.float32) * snap_scale_f32(d) / l.astype(jnp.float32)


# --- normalization mode (SOLE-style reuse of the exp/log datapath) ----------
#
# RMSNorm/LayerNorm need one rsqrt per row; on this unit that is one more
# log-domain traversal — NO divider, NO square-rooter:
#
#     xhat_i = x_i / sqrt(ms) = sign(x_i) * 2**(log2|x_i| - log2(ms)/2)
#
# i.e. the row statistic enters as HALF its log (an arithmetic shift),
# and the per-element normalize is the same log2 -> subtract -> exp2
# pipeline Eq. (10) runs for softmax.  SOLE's "guaranteed normalization"
# maps onto the word lattice as: the mean-square word is clamped >= 1
# (so the log never sees zero), the output saturates at the S5.10 rails,
# and the mean divide is a reciprocal MULTIPLY by the static ROM word
# round(2**15 / n) — integer division never appears in the datapath
# (audited: analysis/int_purity forbids div/rsqrt/sqrt on int paths).
#
# Gain/bias stay OUT of the int unit — the float wrappers apply them in
# f32 after dequantize, mirroring the dense contract's single-downcast
# op order (models/layers.py).

def _exp2_signed_to_in(w):
    """2**w (w @ 2**-T_FRAC, ANY sign) -> word @ 2**-IN_FRAC, saturating.

    Unlike :func:`_exp2_int` (t <= 0 only) the normalization exponent
    w = log2|x| - log2(ms)/2 can be positive (elements above the RMS).
    Split w = u + v as usual; the 2**u shift runs against the 5-bit
    headroom of the target scale and saturates at the S5.10 rail IN_MAX
    — the unit's output saturation stage.
    """
    u = w >> T_FRAC
    v = w - (u << T_FRAC)
    p = exp2_frac_int(v)                       # [1,2) @ 2**-EXP_FRAC
    # rescale 2**-EXP_FRAC -> 2**-IN_FRAC is >> 4; pre-shift by 5 keeps
    # the left-shift cases (u > 4) inside int32, then saturate
    shift = (EXP_FRAC - IN_FRAC) - u
    return jnp.minimum(sat_rshift(p << 5, shift + 5), I32(IN_MAX))


def _log2_ms_int(x, n: int, guard_shift: int):
    """log2 of the row mean square of ``x`` (S5.10) @ 2**-T_FRAC.

    Sum of squares with a guard shift (x*x is at 2**-2*IN_FRAC and <=
    2**30 per element, so rows up to 2**(guard_shift+1) elements cannot
    overflow int32), then the mean is a log-domain SUBTRACTION of the
    static word round(log2(n) * 2**T_FRAC) — no divide.
    """
    xx = (x * x) >> guard_shift                # @ 2**-(2*IN_FRAC - guard)
    s2 = jnp.maximum(jnp.sum(xx, axis=-1, keepdims=True), 1)
    log2n_q = int(round(math.log2(n) * (1 << T_FRAC)))
    return _log2_int(s2, 2 * IN_FRAC - guard_shift) - I32(log2n_q)


def rmsnorm_int(x_fx, guard_shift: int | None = None):
    """Normalization mode: x / sqrt(mean(x^2)) over the last axis.

    x_fx int32 @ S5.10 -> int32 @ S5.10 (saturating).  Entirely on the
    unit's datapath: per-element log2, one row log2, shifts, one exp2.
    Zero words stay exactly zero.
    """
    n = x_fx.shape[-1]
    if guard_shift is None:
        guard_shift = max(0, n.bit_length() - 1)
    x = x_fx.astype(I32)
    t_ms = _log2_ms_int(x, n, guard_shift)
    a = jnp.abs(x)
    t_x = _log2_int(jnp.maximum(a, 1), IN_FRAC)
    w = t_x - (t_ms >> 1)                      # log2 |xhat|
    y = _exp2_signed_to_in(w)
    return jnp.where(a == 0, 0, jnp.sign(x) * y)


def layernorm_int(x_fx, guard_shift: int | None = None):
    """Normalization mode with centering: (x - mu) / sqrt(var(x)).

    The mean is the ONE place a true divide-by-n appears; on the unit it
    is a multiply by the static reciprocal ROM word round(2**15 / n)
    (exact to the output lattice for the n of every assigned arch), then
    the centered row reuses the rmsnorm datapath — var(x) IS the mean
    square of the centered words.
    """
    n = x_fx.shape[-1]
    x = x_fx.astype(I32)
    recip_q = int(round((1 << 15) / n))
    s1 = jnp.sum(x, axis=-1, keepdims=True)    # |s1| <= n * 2**15
    mu = (s1 * I32(recip_q)) >> 15             # @ 2**-IN_FRAC
    xc = jnp.clip(x - mu, IN_MIN, IN_MAX)
    return rmsnorm_int(xc, guard_shift=guard_shift)


def rmsnorm_dualmode(x, g, eps: float):
    """float in/out RMSNorm through the int unit; ``g`` applied in f32.

    ``eps`` is accepted for signature parity with the float home
    (``kernels/datapath.rmsnorm``) but plays no role on the word lattice
    — the unit's guaranteed normalization (the >=1 mean-square clamp and
    the S5.10 output rails) is what bounds the zero/overflow cases.
    """
    del eps
    y = dequantize(rmsnorm_int(quantize(x)), IN_FRAC)
    return y * g.astype(jnp.float32)


def layernorm_dualmode(x, g, b, eps: float):
    """float in/out LayerNorm through the int unit; g/b applied in f32."""
    del eps
    y = dequantize(layernorm_int(quantize(x)), IN_FRAC)
    return y * g.astype(jnp.float32) + b.astype(jnp.float32)


# --- float wrappers (quantize -> int unit -> dequantize) --------------------
def softmax_dualmode(x, axis: int = -1):
    """float in/out softmax through the bit-accurate unit (normal mode)."""
    return dequantize(softmax_int(quantize(x), axis=axis), EXP_FRAC)


def softmax_dualmode_snap(x, axis: int = -1):
    """float in/out softmax through the SNAPPED-max unit.

    The whole-row oracle of every streamed dual-mode path (one-sweep int
    flash, dual-mode decode, dual-mode ring): identical word pipeline,
    one f32 division.  Registered as softmax impl 'dualmode_snap' so the
    naive attention path serves as the snapped reference for free.
    """
    return softmax_snap(quantize(x), axis=axis)


def gelu_dualmode(z):
    """float in/out GELU through the bit-accurate unit (GELU mode)."""
    return dequantize(gelu_int(quantize(z)), IN_FRAC)


def silu_dualmode(z):
    """float in/out SiLU through the bit-accurate unit (SiLU mode)."""
    return dequantize(silu_int(quantize(z)), IN_FRAC)
