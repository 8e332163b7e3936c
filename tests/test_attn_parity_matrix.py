"""Cross-implementation attention parity matrix (ISSUE 4 satellite).

THE single contract: every attention implementation in the dispatch
registry — ``naive`` / ``flash`` / ``flash_pallas`` / ``flash_ring``
(and ``flash_pallas_int`` where dualmode applies, ``flash_decode`` at
its s_q=1 decode rows) — must agree on outputs AND gradients across
GQA / MLA-style head dims / ragged validity / bf16 / non-divisible
shapes.  This matrix supersedes the
per-file parity checks (test_flash*.py keep their targeted
regressions; agreement itself is asserted here, once, for all impls).

``flash_ring`` runs over the largest power-of-two device ring dividing
the case's sequence dims: a size-1 ring in the plain tier-1 run, the
real 8-wide rotation under the CI multi-device lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.launch.mesh import auto_mesh

RNG_SEED = 23

CASES = {
    "gqa": dict(b=2, s=64, t=64, k=2, g=3, h=16),
    "mla_hv": dict(b=1, s=32, t=32, k=4, g=1, h=24, hv=12),
    "ragged": dict(b=2, s=48, t=96, k=1, g=2, h=8, ragged=True),
    "noncausal": dict(b=2, s=32, t=64, k=2, g=2, h=16, causal=False),
    "bf16": dict(b=2, s=48, t=64, k=2, g=2, h=32, dtype="bfloat16"),
    "non_divisible": dict(b=1, s=17, t=33, k=2, g=2, h=8),
}
# the float contract; 'naive' is the oracle the others are pinned against
FLOAT_IMPLS = ("flash", "flash_pallas", "flash_ring")
# forward tolerance: f32 reduction-order noise vs bf16 output rounding
ATOL = {"float32": 1e-5, "bfloat16": 2e-2}
GRAD_ATOL = {"float32": 2e-5, "bfloat16": 3e-2}


@functools.lru_cache(maxsize=None)
def _case(name):
    c = dict(CASES[name])
    rng = np.random.default_rng(RNG_SEED)
    b, s, t = c["b"], c["s"], c["t"]
    k, g, h = c["k"], c["g"], c["h"]
    hv = c.get("hv", h)
    dtype = jnp.dtype(c.get("dtype", "float32"))
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), dtype)
    kk = jnp.asarray(rng.normal(size=(b, t, k, h)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, k, hv)), dtype)
    q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
    if c.get("ragged"):
        kv_valid = jnp.asarray(rng.random((b, t)) > 0.3).at[:, 0].set(True)
    else:
        kv_valid = jnp.ones((b, t), bool)
    return (q, kk, v, q_pos, kv_valid, c.get("causal", True),
            str(dtype))


def _ring_mesh(impl, q, k):
    """The mesh context ``impl`` runs under: a ring as wide as the visible
    devices allow (both sequence dims must divide) for flash_ring, none
    otherwise.  Entered outside any trace — jax.set_mesh is refused
    inside jit/grad."""
    if impl != "flash_ring":
        return contextlib.nullcontext()
    s, t = q.shape[1], k.shape[1]
    n = len(jax.devices())
    while n > 1 and (s % n or t % n):
        n //= 2
    return jax.set_mesh(auto_mesh((n,), ("model",)))


def _run(impl, q, k, v, q_pos, kv_valid, causal):
    fn = dispatch.get_attention(impl)
    call = functools.partial(fn, q_pos=q_pos, kv_valid=kv_valid,
                             causal=causal, scale=None,
                             softmax_impl="float", ring_axis="model")
    return call(q, k, v)


@pytest.mark.parametrize("impl", FLOAT_IMPLS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_outputs_match_naive(case, impl):
    q, k, v, q_pos, kv_valid, causal, dtype = _case(case)
    want = _run("naive", q, k, v, q_pos, kv_valid, causal)
    with _ring_mesh(impl, q, k):
        got = _run(impl, q, k, v, q_pos, kv_valid, causal)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype])


@pytest.mark.parametrize("impl", FLOAT_IMPLS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_grads_match_naive(case, impl):
    q, k, v, q_pos, kv_valid, causal, dtype = _case(case)

    def g_of(f):
        return jax.grad(
            lambda q_, k_, v_: f(q_, k_, v_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    with _ring_mesh(impl, q, k):
        got = g_of(lambda *a: _run(impl, *a, q_pos, kv_valid, causal))
    want = g_of(lambda *a: _run("naive", *a, q_pos, kv_valid, causal))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=GRAD_ATOL[dtype],
                                   err_msg=f"{case}/{impl}/{name}")


# ---------------- flash_decode: the s_q=1 split-KV rows ----------------
# Decode attends one query row against the whole cache, so the matrix
# cases are re-run at s_q=1 (the LAST query row of each case, keeping its
# position/validity/causality) across split counts.  The split-count
# invariance — output independent of WHERE the cache was split — is the
# partial-merge contract, pinned here against both the naive oracle and
# the one-host fold home flash_attention_merged.

DECODE_SPLITS = (1, 2, 4, 8)


def _decode_case(name):
    q, k, v, q_pos, kv_valid, causal, dtype = _case(name)
    return q[:, -1:], k, v, q_pos[:, -1:], kv_valid, causal, dtype


def _run_decode(q, k, v, q_pos, kv_valid, causal, n_splits):
    from repro.kernels.flash_decode import flash_decode_pallas
    return flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                               causal=causal, num_splits=n_splits)


@pytest.mark.parametrize("n_splits", DECODE_SPLITS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_decode_outputs_match_naive(case, n_splits):
    q, k, v, q_pos, kv_valid, causal, dtype = _decode_case(case)
    want = _run("naive", q, k, v, q_pos, kv_valid, causal)
    got = _run_decode(q, k, v, q_pos, kv_valid, causal, n_splits)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype])


@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_decode_split_count_invariance(case):
    """The fold is invariant to the split count: every n_splits produces
    the same words (to f32 sum-order noise), and where the cache length
    divides, the kernel's split partials merge to exactly what the
    one-host oracle fold (models/flash.flash_attention_merged) merges."""
    from repro.models.flash import flash_attention_merged
    q, k, v, q_pos, kv_valid, causal, dtype = _decode_case(case)
    ref = _run_decode(q, k, v, q_pos, kv_valid, causal, 1)
    for n_splits in DECODE_SPLITS[1:]:
        got = _run_decode(q, k, v, q_pos, kv_valid, causal, n_splits)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=ATOL[dtype],
                                   err_msg=f"n_splits={n_splits}")
        if k.shape[1] % n_splits == 0:
            merged = flash_attention_merged(
                q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
                n_splits=n_splits)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(merged, np.float32),
                                       atol=ATOL[dtype],
                                       err_msg=f"merged n_splits={n_splits}")


DUALMODE_CASES = [c for c in sorted(CASES) if "dtype" not in CASES[c]]


@pytest.mark.parametrize("case", DUALMODE_CASES)
def test_dualmode_words_int_kernels_vs_naive(case):
    """Where dualmode applies (f32 operands): the three-sweep oracle
    carries the whole-row CLASSIC unit's words, the one-sweep snapped
    kernel the whole-row SNAPPED unit's words; each residual vs its own
    naive reference is pure numerator@v reduction-order noise, and the
    two units agree within the max-quantization bound."""
    q, k, v, q_pos, kv_valid, causal, _ = _case(case)
    naive = dispatch.get_attention("naive")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode")
    got3 = dispatch.get_attention("flash_pallas_int3")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode")
    np.testing.assert_allclose(np.asarray(got3), np.asarray(naive),
                               atol=1e-5)
    naive_snap = dispatch.get_attention("naive")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode_snap")
    got1 = dispatch.get_attention("flash_pallas_int")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode")
    np.testing.assert_allclose(np.asarray(got1), np.asarray(naive_snap),
                               atol=1e-5)
    # vs the CLASSIC unit the slack is the max-quantization octave
    # fraction — relative in the prob words, so a touch over 2e-3 on
    # O(1) outputs at the matrix's score scales
    np.testing.assert_allclose(np.asarray(got1), np.asarray(naive),
                               atol=4e-3)


@pytest.mark.parametrize("case", DUALMODE_CASES)
def test_dualmode_decode_row(case):
    """ISSUE 7 decode row: the int split-KV path at the matrix's s_q=1
    rows vs the whole-row snapped unit, across split counts (the int
    monoid's split invariance on real shapes)."""
    from repro.kernels.flash_decode import flash_decode_pallas
    q, k, v, q_pos, kv_valid, causal, _ = _decode_case(case)
    want = dispatch.get_attention("naive")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode_snap")
    for n_splits in (1, 4):
        got = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                  causal=causal, num_splits=n_splits,
                                  softmax_impl="dualmode")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5,
                                   err_msg=f"n_splits={n_splits}")


@pytest.mark.parametrize("case", [c for c in DUALMODE_CASES
                                  if CASES[c]["s"] % 2 == 0
                                  and CASES[c]["t"] % 2 == 0])
def test_dualmode_ring_row(case):
    """ISSUE 7 ring row: hop partials folded with the int monoid match
    the single-device one-sweep kernel on the matrix cases (ring width =
    largest power-of-two dividing the sequence dims)."""
    from repro.kernels.ring_attention import ring_flash_attention
    q, k, v, q_pos, kv_valid, causal, _ = _case(case)
    s, t = q.shape[1], k.shape[1]
    n = len(jax.devices())
    while n > 1 and (s % n or t % n):
        n //= 2
    with jax.set_mesh(auto_mesh((n,), ("model",))):
        got = ring_flash_attention(q, k, v, q_pos=q_pos,
                                   kv_valid=kv_valid, causal=causal,
                                   softmax_impl="dualmode")
    want = dispatch.get_attention("flash_pallas_int")(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=None, softmax_impl="dualmode")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


# ---------------- paged decode: block-table gather rows ----------------
# The same matrix cases re-run at s_q=1 through the BLOCK-TABLE kernel:
# the dense cache is scattered into a shuffled physical pool and read
# back through per-row tables.  Parity vs the naive oracle (dense cache)
# pins that the gather-by-table is invisible to the numerics: masking is
# logical-position-only, pad blocks carry no mass.

PAGED_BS = 16


def _paged_case(name):
    q, k, v, q_pos, kv_valid, causal, dtype = _decode_case(name)
    b, t = k.shape[0], k.shape[1]
    nblk = -(-t // PAGED_BS)
    t_pad = nblk * PAGED_BS
    kp = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    valid = jnp.pad(kv_valid, ((0, 0), (0, t_pad - t)))
    rng = np.random.default_rng(RNG_SEED + 1)
    ids = rng.permutation(np.arange(1, 1 + b * nblk))
    tables = jnp.asarray(ids.reshape(b, nblk).astype(np.int32))
    n_pool = 1 + b * nblk
    shp = lambda x: (n_pool, PAGED_BS) + x.shape[2:]
    k_pool = jnp.zeros(shp(kp), kp.dtype)
    v_pool = jnp.zeros(shp(vp), vp.dtype)
    flat = (jnp.take_along_axis(
        tables, jnp.arange(t_pad)[None, :] // PAGED_BS, axis=1)
        * PAGED_BS + jnp.arange(t_pad)[None, :] % PAGED_BS)
    k_pool = k_pool.reshape((n_pool * PAGED_BS,) + kp.shape[2:]).at[
        flat.reshape(-1)].set(kp.reshape((-1,) + kp.shape[2:])
                              ).reshape(shp(kp))
    v_pool = v_pool.reshape((n_pool * PAGED_BS,) + vp.shape[2:]).at[
        flat.reshape(-1)].set(vp.reshape((-1,) + vp.shape[2:])
                              ).reshape(shp(vp))
    return (q, k_pool, v_pool, tables, q_pos, valid, causal, dtype,
            k, v, kv_valid)


@pytest.mark.parametrize("n_splits", (1, 2, 4))
@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_decode_paged_outputs_match_naive(case, n_splits):
    from repro.kernels.flash_decode import flash_decode_paged
    (q, k_pool, v_pool, tables, q_pos, valid, causal, dtype,
     k, v, kv_valid) = _paged_case(case)
    want = _run("naive", q, k, v, q_pos, kv_valid, causal)
    got = flash_decode_paged(q, k_pool, v_pool, block_tables=tables,
                             q_pos=q_pos, kv_valid=valid, causal=causal,
                             num_splits=n_splits)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype])


@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_decode_paged_matches_fold_oracle(case):
    """Block-table kernel vs the pure-JAX paged fold
    (models/flash.flash_attention_paged_ref) — the paged twin of the
    merged-fold contract, exercised on the SAME shuffled tables."""
    from repro.kernels.flash_decode import flash_decode_paged
    from repro.models.flash import flash_attention_paged_ref
    (q, k_pool, v_pool, tables, q_pos, valid, causal, dtype,
     *_ ) = _paged_case(case)
    got = flash_decode_paged(q, k_pool, v_pool, block_tables=tables,
                             q_pos=q_pos, kv_valid=valid, causal=causal)
    ref = flash_attention_paged_ref(q, k_pool, v_pool,
                                    block_tables=tables, q_pos=q_pos,
                                    kv_valid=valid, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=ATOL[dtype])


# ---------------- norm seams: the normalization resident's rows --------
# PR 9 makes RMSNorm/LayerNorm the third resident of the exp/log unit
# and fuses the block's norm seams (kernels/fused_norm.py).  The same
# matrix cases, re-read as token streams (m = b*s tokens of width
# d = k*g*h), pin the fused residual-add+norm epilogue against the dense
# pinned contract — outputs AND every gradient leg (dx, dr, dg, db) —
# including ragged (whole zero rows: the eps guard carries them) and
# non-divisible row counts vs the kernel's bm grid.

NORM_EPS = 1e-6
NORM_KINDS = ("rms", "layer")
NORM_CASES = ("gqa", "ragged", "bf16", "non_divisible")
# dead ragged rows ride the eps guard: dx there is O(1/sqrt(eps)), so
# the f32 leg needs a (tiny) rtol; bf16 weight-grads accumulate input
# rounding over the m rows, hence the wider atol
NORM_RTOL = {"float32": 1e-5, "bfloat16": 2e-2}
NORM_GRAD_ATOL = {"float32": 2e-5, "bfloat16": 1e-1}


def _norm_case(name, kind):
    c = CASES[name]
    m, d = c["b"] * c["s"], c["k"] * c["g"] * c["h"]
    dtype = jnp.dtype(c.get("dtype", "float32"))
    rng = np.random.default_rng(RNG_SEED)
    x = rng.normal(size=(m, d))
    r = rng.normal(size=(m, d))
    if c.get("ragged"):
        dead = rng.random(m) > 0.7      # padded token rows, x + r == 0
        x[dead] = 0.0
        r[dead] = 0.0
    x, r = jnp.asarray(x, dtype), jnp.asarray(r, dtype)
    g = jnp.asarray(1.0 + 0.1 * rng.normal(size=(d,)), dtype)
    b = (jnp.asarray(0.1 * rng.normal(size=(d,)), dtype)
         if kind == "layer" else None)
    co = jnp.asarray(rng.normal(size=(2, m, d)), jnp.float32)
    return x, r, g, b, co, str(dtype)


def _norm_pair(kind):
    from repro.kernels import datapath as dp
    from repro.kernels.fused_norm import fused_residual_norm

    def dense(x, r, g, b):
        s = x + r
        y = (dp.rmsnorm(s, g, NORM_EPS) if kind == "rms"
             else dp.layernorm(s, g, b, NORM_EPS))
        return s, y.astype(x.dtype)

    def fused(x, r, g, b):
        return fused_residual_norm(x, r, g, b, kind=kind, eps=NORM_EPS,
                                   interpret=True, bm=8)

    return dense, fused


@pytest.mark.parametrize("kind", NORM_KINDS)
@pytest.mark.parametrize("case", NORM_CASES)
def test_norm_epilogue_outputs_match_dense(case, kind):
    x, r, g, b, _, dtype = _norm_case(case, kind)
    dense, fused = _norm_pair(kind)
    want, got = dense(x, r, g, b), fused(x, r, g, b)
    for i in range(2):
        assert got[i].shape == want[i].shape
        assert got[i].dtype == want[i].dtype
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want[i], np.float32),
                                   atol=ATOL[dtype], rtol=NORM_RTOL[dtype],
                                   err_msg=f"{case}/{kind}[{i}]")


@pytest.mark.parametrize("kind", NORM_KINDS)
@pytest.mark.parametrize("case", NORM_CASES)
def test_norm_epilogue_grads_match_dense(case, kind):
    x, r, g, b, co, dtype = _norm_case(case, kind)
    dense, fused = _norm_pair(kind)
    args = (x, r, g) + ((b,) if kind == "layer" else ())
    names = ("dx", "dr", "dg") + (("db",) if kind == "layer" else ())

    def g_of(f):
        def loss(*a):
            xb = a + (None,) if kind == "rms" else a
            s, y = f(*xb)
            return (jnp.vdot(s.astype(jnp.float32), co[0])
                    + jnp.vdot(y.astype(jnp.float32), co[1]))
        return jax.grad(loss, argnums=tuple(range(len(args))))(*args)

    got, want = g_of(fused), g_of(dense)
    for name, a_, b_ in zip(names, got, want):
        assert bool(jnp.all(jnp.isfinite(a_.astype(jnp.float32)))), name
        np.testing.assert_allclose(np.asarray(a_, np.float32),
                                   np.asarray(b_, np.float32),
                                   atol=NORM_GRAD_ATOL[dtype],
                                   rtol=NORM_RTOL[dtype],
                                   err_msg=f"{case}/{kind}/{name}")
