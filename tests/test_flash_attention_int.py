"""Bit-accurate int flash attention (the snapped one-sweep kernel).

  1. WORDS, blocked classic folds — the pure-jnp three-sweep recurrence
     (``softmax_unit.softmax_int_blocked``) telescopes to the EXACT
     whole-row ``softmax_int`` words for any blocking.
  2. WORDS, one-sweep — the snapped-max online kernel
     (``flash_pallas_int``) carries the whole-row ``softmax_snap`` words:
     snapping the running max to a power of two makes every rescale an
     exact shift, so ONE kv sweep suffices, and an identity-matrix v
     (which turns the output into the raw probability words: no float
     accumulation) pins it bitwise against the naive 'dualmode_snap'
     reference, on every row the two paths' f32 score dots cannot round
     across an S5.10 step (``_clear_rows``).
  3. OUTPUTS — with a real v the only remaining difference vs naive
     'dualmode_snap' is f32 numerator@v reduction order (blocked vs
     whole-row), bounded at ~1e-7 of the row mass; snapped vs CLASSIC
     unsnapped words differ by <~1e-3 (the max-quantization step the
     Table-2 bench quantifies).

Plus the dispatch guarantee: softmax_impl='dualmode' can no longer be
silently dropped by ANY attention impl resolution.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import softmax_unit as unit
from repro.core.fixedpoint import quantize
from repro.kernels import dispatch
from repro.kernels.flash_attention_int import flash_attention_pallas_int
from repro.models.attention import _naive_sdpa, _sdpa

RNG = np.random.default_rng(11)


def _mk(b, s, t, k, g, h, hv=None, scale=1.0):
    hv = hv or h
    q = jnp.asarray(RNG.normal(size=(b, s, k, g, h)) * scale, jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, t, k, h)) * scale, jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, t, k, hv)), jnp.float32)
    return q, kk, v


def _clear_rows(q, kk, q_pos, kv_valid, causal, scale=None):
    """(b, s, k, g) mask of the query rows whose every live score sits
    farther from an S5.10 rounding boundary than f32 dot rounding can
    move it.

    Both paths fold ``scale`` into q in f32 and then dot in f32, but in
    different summation orders, so a score that close to a quantization
    step may land on either side of it and change its whole row's words.
    The exact dot of the same f32 products is taken here in f64; an
    h-term f32 sum is within ``h * 2**-24 * sum|terms|`` of it.  Masked
    keys quantize ``MASK_VALUE`` on both paths and do not count.
    """
    h = q.shape[-1]
    scale = (1.0 / h ** 0.5) if scale is None else scale
    qf = np.asarray(q.astype(jnp.float32) * jnp.float32(scale), np.float64)
    kf = np.asarray(kk, np.float64).transpose(0, 2, 1, 3)   # (b, k, t, h)
    terms = qf[..., None, :] * kf[:, None, :, None]          # (b,s,k,g,t,h)
    x = terms.sum(-1) * (1 << 10)                            # S5.10 steps
    edge = np.abs(x - np.floor(x) - 0.5) / (1 << 10)         # to a .5 step
    clear = edge > h * 2.0 ** -24 * np.abs(terms).sum(-1)
    live = np.broadcast_to(np.asarray(kv_valid)[:, None, :],
                           (q.shape[0], q.shape[1], kk.shape[1]))
    if causal:
        live = live & (np.arange(kk.shape[1])[None, None]
                       <= np.asarray(q_pos)[..., None])
    rows = (clear | ~live[:, :, None, None]).all(-1)
    # most rows must stay under test, or the word check says nothing
    assert rows.mean() > 0.5, rows.mean()
    return rows


# ---------------- the telescoping proof (pure int words) ----------------

@pytest.mark.parametrize("n,block", [(8, 8), (33, 8), (100, 16), (7, 3),
                                     (1000, 128), (513, 512)])
def test_blocked_int_recurrence_telescopes_bitexact(n, block):
    """Any blocking of the three-sweep recurrence == whole-row words,
    including non-divisible tails and rows long enough to engage the
    guard shift path bound."""
    x = quantize(jnp.asarray(RNG.normal(size=(16, n)) * 5, jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(unit.softmax_int_blocked(x, block)),
        np.asarray(unit.softmax_int(x)))


def test_blocked_int_guard_shift_long_row():
    """Rows past 2**16 elements force guard_shift > 0 in the whole-row
    unit; the blocked carry must use the identical guard so the int32
    accumulator never overflows and words stay pinned."""
    n = (1 << 16) + 17                      # bit_length 17 -> guard 1
    x = quantize(jnp.asarray(RNG.normal(size=(2, n)) * 3, jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(unit.softmax_int_blocked(x, 1 << 12)),
        np.asarray(unit.softmax_int(x)))


def test_phantom_word_carries_exactly_zero_mass():
    """The PHANTOM_Q sentinel must be invisible: appending phantoms to a
    row changes neither the max, the sum carry, nor any prob word."""
    x = quantize(jnp.asarray(RNG.normal(size=(4, 37)) * 5, jnp.float32))
    xp = jnp.concatenate(
        [x, jnp.full((4, 27), unit.PHANTOM_Q, jnp.int32)], axis=-1)
    # guard from the REAL row length, like the kernel computes it
    g = max(0, 37 .bit_length() - 16)
    got = unit.softmax_int_blocked(xp, 16, guard_shift=g)[:, :37]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(unit.softmax_int(x, guard_shift=g)))
    assert int(jnp.abs(
        unit.softmax_int_blocked(xp, 16, guard_shift=g)[:, 37:]).max()) == 0


# ---------------- the Pallas kernel vs the naive dual-mode oracle -------

def _ids(b, t, k):
    """v = per-head identity: attention output IS the dequantized
    probability words (each output element one p*1.0 product, every other
    term an exact float zero) — a bitwise probe through the kernel."""
    eye = jnp.eye(t, dtype=jnp.float32)
    return jnp.broadcast_to(eye[None, :, None, :], (b, t, k, t))


@pytest.mark.parametrize("causal", [True, False])
def test_onesweep_prob_words_bit_identical_to_naive_dualmode_snap(causal):
    """The ISSUE-7 word contract: ONE kv sweep, snapped recurrence, and
    the output words equal the whole-row snapped unit's bitwise — the
    identity-v probe makes every output element a single p*2^-d*1.0
    product, so any word drift in (p, d, l) would surface exactly."""
    b, s, t, k, g, h = 2, 24, 40, 2, 2, 8
    q, kk, _ = _mk(b, s, t, k, g, h)
    v = _ids(b, t, k)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    kv_valid = jnp.asarray(RNG.random((b, t)) > 0.25)
    want = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=causal, softmax_impl="dualmode_snap")
    got = flash_attention_pallas_int(q, kk, v, q_pos=q_pos,
                                     kv_valid=kv_valid, causal=causal,
                                     block_q=8, block_kv=16,
                                     interpret=True)
    rows = _clear_rows(q, kk, q_pos, kv_valid, causal)
    np.testing.assert_array_equal(np.asarray(got)[rows],
                                  np.asarray(want)[rows])


def test_onesweep_score_words_fold_scale_into_q_before_the_dot():
    """Scores quantize as ``quantize((q*scale) . k)``: scale rounds into q
    BEFORE the dot, as on the naive path.  Each key here has one nonzero
    lane, so the dot is a single product whatever its summation order,
    and every key is chosen so that folding scale in AFTER the dot would
    round its score to the neighbouring S5.10 word."""
    h, t = 8, 32
    scale = np.float32(1.0 / h ** 0.5)
    qv = np.float32(2.2903628)
    cand = (np.random.default_rng(0).normal(size=1 << 22) * 3).astype(
        np.float32)
    before = np.round(np.float64(np.float32(qv * scale) * cand) * 1024)
    after = np.round(np.float64(np.float32(qv * cand) * scale) * 1024)
    kv = cand[before != after][:t]
    assert kv.size == t
    q = jnp.zeros((1, 1, 1, 1, h), jnp.float32).at[..., 0].set(qv)
    kk = jnp.zeros((1, t, 1, h), jnp.float32).at[0, :, 0, 0].set(kv)
    v = _ids(1, t, 1)
    q_pos = jnp.full((1, 1), t - 1, jnp.int32)
    kv_valid = jnp.ones((1, t), bool)
    want = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=False, softmax_impl="dualmode_snap")
    got = flash_attention_pallas_int(q, kk, v, q_pos=q_pos,
                                     kv_valid=kv_valid, causal=False,
                                     block_q=8, block_kv=16,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_onesweep_matches_threesweep_and_wholerow_words():
    """Acceptance: one-sweep snapped == whole-row snapped (bitwise via
    identity-v above; here with a real v up to f32 reduction order) and
    tracks the classic whole-row unit within the snapped-vs-classic
    max-quantization bound."""
    b, s, t, k, g, h = 1, 16, 48, 2, 2, 8
    q, kk, v = _mk(b, s, t, k, g, h)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    kv_valid = jnp.ones((b, t), bool)
    one = flash_attention_pallas_int(q, kk, v, q_pos=q_pos,
                                     kv_valid=kv_valid, causal=True,
                                     interpret=True)
    snap = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=True, softmax_impl="dualmode_snap")
    rows = _clear_rows(q, kk, q_pos, kv_valid, True)
    np.testing.assert_allclose(np.asarray(one)[rows],
                               np.asarray(snap)[rows], atol=1e-6)
    classic = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                          causal=True, softmax_impl="dualmode")
    np.testing.assert_allclose(np.asarray(one), np.asarray(classic),
                               atol=2e-3)


@pytest.mark.parametrize("shape", [
    (2, 64, 128, 2, 3, 16, None),    # GQA: G=3 query groups per KV head
    (2, 32, 32, 4, 1, 24, 12),       # MLA-style: v head dim != qk head dim
    (1, 17, 33, 2, 2, 8, None),      # non-divisible S/T (tiling pad path)
    (1, 5, 100, 1, 2, 8, None),
])
def test_kernel_output_matches_naive_dualmode(shape):
    b, s, t, k, g, h, hv = shape
    q, kk, v = _mk(b, s, t, k, g, h, hv)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    kv_valid = jnp.asarray(RNG.random((b, t)) > 0.3)
    kv_valid = kv_valid.at[:, 0].set(True)
    want = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=True, softmax_impl="dualmode_snap")
    got = flash_attention_pallas_int(q, kk, v, q_pos=q_pos,
                                     kv_valid=kv_valid, causal=True,
                                     block_q=8, block_kv=16,
                                     interpret=True)
    assert got.shape == want.shape
    # identical prob words; only f32 numerator@v reduction order differs
    rows = _clear_rows(q, kk, q_pos, kv_valid, True)
    np.testing.assert_allclose(np.asarray(got)[rows],
                               np.asarray(want)[rows], atol=1e-6)


def test_kernel_all_rows_saturated_matches_naive():
    """Every real score below the S5.10 floor: the quantizer clips them
    all to the same word (uniform row) — phantoms must still carry zero
    mass rather than joining the uniform mass."""
    b, s, t, k, g, h = 1, 8, 100, 1, 1, 16
    q = jnp.full((b, s, k, g, h), 3.0, jnp.float32)
    kk = jnp.full((b, t, k, h), -3.0, jnp.float32)    # scores << -32
    v = jnp.asarray(RNG.normal(size=(b, t, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    kv_valid = jnp.ones((b, t), bool)
    want = _naive_sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=False, softmax_impl="dualmode_snap")
    got = flash_attention_pallas_int(q, kk, v, q_pos=q_pos,
                                     kv_valid=kv_valid, causal=False,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


def test_sdpa_routes_dualmode_to_int_kernel():
    q, kk, v = _mk(1, 48, 48, 2, 2, 8)
    q_pos = jnp.broadcast_to(jnp.arange(48)[None], (1, 48))
    kv_valid = jnp.ones((1, 48), bool)
    got = _sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                softmax_impl="dualmode", attn_impl="flash_pallas_int")
    want = _sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                 softmax_impl="dualmode", attn_impl="naive")
    # snapped kernel vs the CLASSIC whole-row unit: within the
    # max-quantization bound (p word error of one snapped octave frac)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3)
    want_s = _sdpa(q, kk, v, q_pos=q_pos, kv_valid=kv_valid,
                   softmax_impl="dualmode_snap", attn_impl="naive")
    rows = _clear_rows(q, kk, q_pos, kv_valid, True)
    np.testing.assert_allclose(np.asarray(got)[rows],
                               np.asarray(want_s)[rows], atol=1e-6)


# ---------------- dispatch: dualmode can never be dropped ---------------

def test_registry_has_int_impl():
    assert callable(dispatch.get_attention("flash_pallas_int"))
    assert callable(dispatch.get_softmax("dualmode_snap"))


def test_resolve_auto_dualmode_routes_to_int_paths():
    # short rows: whole-row unit on the naive path
    assert dispatch.resolve_attention(
        "auto", 64, 64, softmax_impl="dualmode") == "naive"
    # blocked shapes: the int kernel, NEVER the float blocked paths
    assert dispatch.resolve_attention(
        "auto", 4096, 4096, softmax_impl="dualmode") == "flash_pallas_int"
    # float softmax keeps the float auto rule untouched
    assert dispatch.resolve_attention("auto", 4096, 4096) == "flash"


@pytest.mark.parametrize("impl", ["flash", "flash_pallas"])
def test_explicit_float_blocked_plus_dualmode_raises(impl):
    with pytest.raises(ValueError, match="dualmode"):
        dispatch.resolve_attention(impl, 4096, 4096,
                                   softmax_impl="dualmode")


def test_int_impl_requires_dualmode():
    with pytest.raises(ValueError, match="dualmode"):
        dispatch.resolve_attention("flash_pallas_int", 64, 64,
                                   softmax_impl="float")
    with pytest.raises(ValueError):
        flash = dispatch.get_attention("flash_pallas_int")
        q, kk, v = _mk(1, 8, 8, 1, 1, 8)
        flash(q, kk, v, q_pos=jnp.zeros((1, 8), jnp.int32),
              kv_valid=jnp.ones((1, 8), bool), causal=True, scale=None,
              softmax_impl="float")


@pytest.mark.parametrize("impl", ["flash", "flash_pallas"])
def test_float_blocked_entries_refuse_dualmode_directly(impl):
    """Even bypassing resolve_attention, the registered float entries
    refuse to silently run fp32 in place of the unit."""
    q, kk, v = _mk(1, 8, 8, 1, 1, 8)
    with pytest.raises(ValueError, match="dualmode"):
        dispatch.get_attention(impl)(
            q, kk, v, q_pos=jnp.zeros((1, 8), jnp.int32),
            kv_valid=jnp.ones((1, 8), bool), causal=True, scale=None,
            softmax_impl="dualmode")


def test_naive_plus_dualmode_still_resolves():
    assert dispatch.resolve_attention(
        "naive", 4096, 4096, softmax_impl="dualmode") == "naive"


def test_model_end_to_end_int_kernel_matches_naive_dualmode():
    """configs -> transformer -> dispatch -> int kernel, full vertical
    slice: a dualmode LM forward through the blocked int kernel must
    match the same model on the naive whole-row unit (the snapped unit
    up to f32 reduction order; the classic unit within the
    max-quantization bound)."""
    import jax
    from repro.configs import registry
    from repro.models.transformer import init_lm, lm_apply

    cfg = registry.reduced_config("qwen1.5-0.5b").replace(
        softmax_impl="dualmode", attn_impl="flash_pallas_int")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    logits, _, _ = lm_apply(params, cfg, toks, pos=0)
    snap_cfg = cfg.replace(softmax_impl="dualmode_snap", attn_impl="naive")
    want_s, _, _ = lm_apply(params, snap_cfg, toks, pos=0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_s),
                               atol=1e-5)
    ref_cfg = cfg.replace(attn_impl="naive")
    want, _, _ = lm_apply(params, ref_cfg, toks, pos=0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=5e-3)
