"""The shared datapath library (ISSUE 1 tentpole).

Covers: (a) the single-definition acceptance criterion — the log2e /
GELU-cubic ROM constants exist in exactly one float (kernels/datapath.py)
and one int (core/softmax_unit.py) home in src/; (b) bit-identical parity
of the refactored kernel bodies with the pre-refactor arithmetic (spelled
out literally here, frozen at the pre-refactor state); (c) the streamed
online-softmax step telescoping back to the row softmax; (d) the unified
mask constant."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st
from jax.experimental import pallas as pl

from repro.kernels import datapath as dp
from repro.kernels import tiling
from repro.kernels.dualmode_softmax import pair_act_pallas, softmax_pallas
from repro.kernels.fused_ffn import fused_glu_pallas

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
ALLOWED = {os.path.join("repro", "kernels", "datapath.py"),
           os.path.join("repro", "core", "softmax_unit.py")}

RNG = np.random.default_rng(11)


# ---------------- (a) single-definition criterion ----------------

@pytest.mark.parametrize("rom_word", ["1.4426950408889634", "0.044715"])
def test_datapath_constants_have_one_definition(rom_word):
    offenders = []
    for root, _, files in os.walk(SRC):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, SRC)
            if rel in ALLOWED:
                continue
            with open(path) as fh:
                if rom_word in fh.read():
                    offenders.append(rel)
    assert not offenders, (
        f"ROM constant {rom_word} duplicated outside the datapath: "
        f"{offenders}")


def test_no_stray_mask_literals_in_models():
    """The -30.0 / -1e30 mask split is gone: models use dp.MASK_VALUE."""
    models = os.path.join(SRC, "repro", "models")
    pat = re.compile(r"-\s*(30\.0|1e30)\b")
    offenders = []
    for root, _, files in os.walk(models):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as fh:
                    if pat.search(fh.read()):
                        offenders.append(fn)
    assert not offenders, offenders


# ---------------- (b) pre-refactor bit parity ----------------
# The frozen seed-commit bodies, run through pallas_call with the same
# block shapes as the refactored kernels, must produce the same BITS —
# the refactor moved the arithmetic, it did not change it.  (The int path
# is covered bit-exactly against repro.core.softmax_unit in
# tests/test_kernels.py.)

def _pre_refactor_float_softmax_body(x_ref, o_ref):
    """kernels/dualmode_softmax.py float body as of the seed commit."""
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    t = (x - m) * 1.4426950408889634
    e = jnp.exp2(t)
    s = jnp.sum(e, axis=-1, keepdims=True)
    w = t - jnp.log2(s)
    o_ref[...] = jnp.exp2(w).astype(o_ref.dtype)


def _pre_refactor_epilogue(g, mode):
    """kernels/fused_ffn.py / dualmode_softmax.py epilogue as of the seed."""
    if mode == "gelu":
        k = 0.7978845608028654 * (g + 0.044715 * g * g * g)
    else:
        k = 0.5 * g
    amax = jnp.abs(k)
    l2e = 1.4426950408889634
    t1 = (k - amax) * l2e
    t2 = (-k - amax) * l2e
    sig = jnp.exp2(t1 - jnp.log2(jnp.exp2(t1) + jnp.exp2(t2)))
    return g * sig


def _whole_array_call(body, x):
    return pl.pallas_call(
        body, grid=(1,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)


def test_float_softmax_body_bit_identical_to_pre_refactor():
    x = jnp.asarray(RNG.normal(size=(16, 256)) * 4, jnp.float32)
    got = softmax_pallas(x, precision="float", interpret=True)
    want = _whole_array_call(_pre_refactor_float_softmax_body, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["gelu", "silu"])
def test_pair_act_body_bit_identical_to_pre_refactor(mode):
    z = jnp.asarray(RNG.normal(size=(16, 256)) * 3, jnp.float32)
    got = pair_act_pallas(z, mode=mode, precision="float", interpret=True)

    def body(z_ref, o_ref):     # seed-commit _pair_act_body, float branch
        zz = z_ref[...].astype(jnp.float32)
        if mode == "gelu":
            k = 0.7978845608028654 * (zz + 0.044715 * zz * zz * zz)
        else:
            k = 0.5 * zz
        amax = jnp.abs(k)
        l2e = 1.4426950408889634
        t1 = (k - amax) * l2e
        t2 = (-k - amax) * l2e
        s = jnp.exp2(t1) + jnp.exp2(t2)
        sig = jnp.exp2(t1 - jnp.log2(s))
        o_ref[...] = (zz * sig).astype(o_ref.dtype)

    want = _whole_array_call(body, z)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["gelu", "silu"])
def test_fused_ffn_epilogue_bit_identical_to_pre_refactor(mode):
    x = jnp.asarray(RNG.normal(size=(32, 64)) * 0.5, jnp.float32)
    wg = jnp.asarray(RNG.normal(size=(64, 128)) * 0.1, jnp.float32)
    wu = jnp.asarray(RNG.normal(size=(64, 128)) * 0.1, jnp.float32)
    got = fused_glu_pallas(x, wg, wu, mode=mode, interpret=True,
                           bm=32, bf=128)

    def body(x_ref, wg_ref, wu_ref, o_ref):   # seed-commit _ffn_body
        xx = x_ref[...]
        g = jnp.dot(xx, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(xx, wu_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (_pre_refactor_epilogue(g, mode) * u).astype(o_ref.dtype)

    want = pl.pallas_call(
        body, grid=(1, 1),
        in_specs=[pl.BlockSpec((32, 64), lambda i, j: (0, 0)),
                  pl.BlockSpec((64, 128), lambda i, j: (0, 0)),
                  pl.BlockSpec((64, 128), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((32, 128), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((32, 128), x.dtype),
        interpret=True)(x, wg, wu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------- (c) online softmax telescopes to Eq. 10 ----------------

@pytest.mark.parametrize("block", [4, 16, 64])
def test_online_update_telescopes_to_row_softmax(block):
    s = jnp.asarray(RNG.normal(size=(8, 64)) * 4, jnp.float32)
    m = jnp.full((8, 1), dp.MASK_VALUE, jnp.float32)
    l = jnp.zeros((8, 1), jnp.float32)
    ps = []
    for i in range(0, 64, block):
        m, l, p, corr = dp.online_softmax_update(m, l, s[:, i:i + block])
        ps = [q * corr for q in ps] + [p]
    probs = jnp.concatenate(ps, axis=-1) / l
    np.testing.assert_allclose(np.asarray(probs),
                               np.asarray(dp.row_softmax(s)), atol=1e-6)


def test_pair_sigmoid_is_sigmoid_of_2k():
    k = jnp.linspace(-10, 10, 513)
    import jax
    np.testing.assert_allclose(np.asarray(dp.pair_sigmoid(k)),
                               np.asarray(jax.nn.sigmoid(2.0 * k)),
                               atol=1e-6)


# ---------------- (d) mask + tiling policy ----------------

def test_mask_value_is_s510_saturation_regime():
    """-30 sits inside the S5.10 saturation band: exp already underflows."""
    assert dp.MASK_VALUE == -30.0
    from repro.core.fixedpoint import quantize
    assert int(quantize(jnp.asarray(dp.MASK_VALUE))) == -30 * 1024


@pytest.mark.parametrize("n,mult,want", [(37, 128, 128), (128, 128, 128),
                                         (129, 128, 256)])
def test_tiling_pad_unpad_roundtrip(n, mult, want):
    x = jnp.asarray(RNG.normal(size=(3, n)), jnp.float32)
    xp, _ = tiling.pad_dim(x, 1, mult)
    assert xp.shape == (3, want)
    np.testing.assert_array_equal(np.asarray(tiling.unpad(xp, 1, n)),
                                  np.asarray(x))


def test_tiling_blocks_never_degenerate():
    """Odd/prime shapes keep lane-aligned blocks (the old divisor search
    collapsed to 1-wide)."""
    bm, bn = tiling.tile2d(997, 131)
    assert bm % tiling.SUBLANE == 0 and bn % tiling.LANE == 0
    assert tiling.row_block(7, 100) % tiling.SUBLANE == 0
    bm, bf = tiling.matmul_blocks(48, 72)
    assert bm % tiling.SUBLANE == 0 and bf % tiling.LANE == 0


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, want):
    """Kernels interpret on the CPU backend, compile on TPU, and refuse
    any other backend instead of silently interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            tiling.interpret_mode()
    else:
        assert tiling.interpret_mode() is want


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", tiling.DECODE_MAX_SPLITS),
    ("tpu", "TPU v5 lite", 1),
    ("tpu", "TPU v99", None),
])
def test_device_core_count_knows_v5e_and_refuses_guesses(
        monkeypatch, platform, kind, want):
    class Dev:
        device_kind = kind
    Dev.platform = platform
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    if want is None:
        with pytest.raises(RuntimeError, match="TPU v99"):
            tiling.device_core_count()
    else:
        assert tiling.device_core_count() == want


# ---------------- (e) online_softmax_merge: the ring monoid ----------------
# The algebraic fact sequence-parallel ring attention relies on: partial
# (m, l, acc) states form a commutative monoid under the merge, with the
# empty-shard sentinel (MASK_VALUE, 0, 0) — the float twin of the int
# path's PHANTOM_Q — as identity, and the fold is invariant to HOW the
# key set was split (kernels/ring_attention.py is this fold across
# devices; models/flash.flash_attention_merged is it on one host).

def _partials(seed: int, n_chunks: int, chunk: int, d: int = 4,
              spread: float = 4.0):
    """n_chunks independent (m, l, acc) partial states of one 2-row set."""
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.normal(size=(2, n_chunks * chunk)) * spread,
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, n_chunks * chunk, d)), jnp.float32)
    parts = [dp.online_softmax_partial(s[:, i * chunk:(i + 1) * chunk],
                                       v[:, i * chunk:(i + 1) * chunk])
             for i in range(n_chunks)]
    return s, v, parts


def _finish(part):
    return np.asarray(dp.online_softmax_finish(part[1], part[2]))


@given(st.integers(0, 6), st.integers(1, 8), st.floats(0.5, 8.0))
@settings(max_examples=24, deadline=None)
def test_merge_is_associative(seed, chunk, spread):
    _, _, (a, b, c) = _partials(seed, 3, chunk, spread=spread)
    left = dp.online_softmax_merge(dp.online_softmax_merge(a, b), c)
    right = dp.online_softmax_merge(a, dp.online_softmax_merge(b, c))
    np.testing.assert_allclose(_finish(left), _finish(right), atol=1e-6)
    np.testing.assert_allclose(np.asarray(left[0]), np.asarray(right[0]))


@given(st.integers(0, 6), st.integers(1, 8))
@settings(max_examples=24, deadline=None)
def test_merge_is_commutative_bitwise(seed, chunk):
    """max and IEEE addition are symmetric, so a<->b is EXACT, not just
    close — the ring may merge hops in any arrival order."""
    _, _, (a, b) = _partials(seed, 2, chunk)
    ab = dp.online_softmax_merge(a, b)
    ba = dp.online_softmax_merge(b, a)
    for x, y in zip(ab, ba):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(st.integers(0, 6), st.integers(1, 8), st.booleans())
@settings(max_examples=24, deadline=None)
def test_merge_identity_is_empty_shard_sentinel(seed, chunk, left_side):
    """(MASK_VALUE, 0, 0) — what a fully-phantom shard produces — merges
    as a bit-exact no-op: every streamed path starts its running max at
    MASK_VALUE, so real partials never carry a smaller max."""
    _, _, (a,) = _partials(seed, 1, chunk)
    ident = (jnp.full_like(a[0], dp.MASK_VALUE), jnp.zeros_like(a[1]),
             jnp.zeros_like(a[2]))
    got = (dp.online_softmax_merge(ident, a) if left_side
           else dp.online_softmax_merge(a, ident))
    for x, y in zip(got, a):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_all_phantom_block_produces_identity():
    """online_softmax_partial of an all--inf (tiling-phantom) block IS the
    sentinel — no NaNs from exp2(-inf + inf)."""
    s = jnp.full((2, 8), -jnp.inf, jnp.float32)
    v = jnp.ones((2, 8, 4), jnp.float32)
    m, l, acc = dp.online_softmax_partial(s, v)
    assert float(m.min()) == dp.MASK_VALUE
    np.testing.assert_array_equal(np.asarray(l), 0.0)
    np.testing.assert_array_equal(np.asarray(acc), 0.0)


@given(st.integers(0, 6), st.sampled_from([1, 2, 3, 4, 6, 8, 12, 24]))
@settings(max_examples=24, deadline=None)
def test_merge_invariant_to_kv_split_points(seed, n_chunks):
    """Folding ANY split of the key set reproduces the whole-row softmax
    combine — the exact invariance ring attention needs when the shard
    count (mesh size) changes."""
    chunk = 24 // n_chunks
    s, v, parts = _partials(seed, n_chunks, chunk)
    acc = parts[0]
    for p in parts[1:]:
        acc = dp.online_softmax_merge(acc, p)
    want = jnp.einsum("rn,rnd->rd", dp.row_softmax(s), v)
    np.testing.assert_allclose(_finish(acc), np.asarray(want), atol=1e-6)


@given(st.integers(0, 6), st.sampled_from([1, 2, 3, 4, 6, 8]))
@settings(max_examples=24, deadline=None)
def test_merge_n_matches_pairwise_fold(seed, n_chunks):
    """The vectorized n-way fold (the split-KV decode combine) computes
    the same finished output as folding the partials pairwise with
    online_softmax_merge — same monoid, one max + one rescaled sum."""
    chunk = 24 // n_chunks
    s, v, parts = _partials(seed, n_chunks, chunk)
    pair = parts[0]
    for p in parts[1:]:
        pair = dp.online_softmax_merge(pair, p)
    m = jnp.stack([p[0] for p in parts], 0)
    l = jnp.stack([p[1] for p in parts], 0)
    acc = jnp.stack([p[2] for p in parts], 0)
    m_n, l_n, acc_n = dp.online_softmax_merge_n(m, l, acc, axis=0)
    # the max is order-independent: exact
    np.testing.assert_array_equal(np.asarray(m_n[0]), np.asarray(pair[0]))
    np.testing.assert_allclose(_finish((m_n[0], l_n[0], acc_n[0])),
                               _finish(pair), atol=1e-6)
    np.testing.assert_allclose(_finish((m_n[0], l_n[0], acc_n[0])),
                               np.asarray(jnp.einsum(
                                   "rn,rnd->rd", dp.row_softmax(s), v)),
                               atol=1e-6)


@given(st.integers(0, 6), st.integers(1, 4))
@settings(max_examples=24, deadline=None)
def test_merge_n_sentinel_splits_are_bit_exact_noops(seed, n_sentinels):
    """Empty splits (every key skipped/phantom) contribute exact IEEE
    zeros to the n-way fold — padding the split axis with sentinels
    changes no bits, which is why the decode kernel may run more splits
    than the cache has tiles."""
    _, _, parts = _partials(seed, 2, 8)
    m = jnp.stack([p[0] for p in parts], 0)
    l = jnp.stack([p[1] for p in parts], 0)
    acc = jnp.stack([p[2] for p in parts], 0)
    want = dp.online_softmax_merge_n(m, l, acc, axis=0)
    sent_m = jnp.full((n_sentinels,) + parts[0][0].shape, dp.MASK_VALUE)
    pad = lambda x, s: jnp.concatenate([x, s], 0)
    got = dp.online_softmax_merge_n(
        pad(m, sent_m), pad(l, jnp.zeros_like(sent_m)),
        pad(acc, jnp.zeros((n_sentinels,) + parts[0][2].shape)), axis=0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_block_minimizes_padding():
    """Block choice never inflates padding beyond hardware alignment:
    513 cols pad to 640 with 128-wide blocks, not to 1024 with a blind
    512 block."""
    assert tiling.fit_block(513, 128, 512) == 128       # 640 = 5*128
    assert tiling.fit_block(1024, 128, 512) == 512      # exact
    assert tiling.fit_block(1408, 128, 512) == 128      # 11*128, 11 prime
    assert tiling.fit_block(16, 8, 128) == 16
    assert tiling.fit_block(7, 8, 4096) == 8
    for n in (1, 37, 127, 128, 129, 513, 640, 1000):
        b = tiling.fit_block(n, 128, 512)
        assert tiling.round_up(n, 128) % b == 0
        assert b % 128 == 0 and b <= 512
