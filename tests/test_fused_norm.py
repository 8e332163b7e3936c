"""Normalization joins the unit: the dense contract, its VJP homes, and
the fused Pallas seams (PR 9).

The dense contract (models/layers.py -> kernels/datapath.py): moments AND
gain/bias entirely in f32, ONE downcast on the finished result, ``eps``
always threaded from config (never a default).  These tests pin that
contract (bf16-vs-f32 regression, eps-required, call-site audit), prove
the datapath VJP homes against autodiff, and hold every fused seam
(kernels/fused_norm.py) to dense parity — outputs AND gradients — across
norm kind, dtype and non-divisible shapes, in interpret mode.  The int
counterpart (core/softmax_unit.rmsnorm_int/layernorm_int: SOLE-style
guaranteed normalization, rsqrt as the unit's exp2/log2 traversal) is
pinned against the float home at lattice tolerance.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import softmax_unit as unit
from repro.kernels import datapath as dp
from repro.kernels import dispatch
from repro.kernels.fused_norm import (fused_norm_glu, fused_norm_linear,
                                      fused_residual_norm)
from repro.models import layers

EPS = 1e-6
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS = ("rms", "layer")
# (name, dtype, m, d, f): even tiles, everything-ragged, bf16 stream
SHAPES = [
    ("f32_even", "float32", 64, 128, 256),
    ("f32_ragged", "float32", 23, 72, 120),
    ("bf16", "bfloat16", 32, 96, 192),
]
ATOL = {"float32": 1e-5, "bfloat16": 2e-2}
GRAD_ATOL = {"float32": 2e-5, "bfloat16": 1e-1}
# bf16 rounds at ~2**-8 relative; large-magnitude grads need the rtol leg
RTOL = {"float32": 0.0, "bfloat16": 2e-2}


def _data(m, d, f, dtype, kind, seed=3):
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(m, d)), dt)
    r = jnp.asarray(rng.normal(size=(m, d)), dt)
    g = jnp.asarray(1.0 + 0.1 * rng.normal(size=(d,)), dt)
    b = (jnp.asarray(0.1 * rng.normal(size=(d,)), dt)
         if kind == "layer" else None)
    w = jnp.asarray(rng.normal(size=(d, f)) / d ** 0.5, dt)
    wu = jnp.asarray(rng.normal(size=(d, f)) / d ** 0.5, dt)
    return x, r, g, b, w, wu


def _dense_norm(x, g, b, kind):
    y = (dp.rmsnorm(x, g, EPS) if kind == "rms"
         else dp.layernorm(x, g, b, EPS))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# the pinned dense contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_norm_op_order_bf16_matches_f32_reference(kind):
    """The op-order contract: a bf16 input must produce BITWISE the f32
    computation downcast once at the end — moments and gain/bias never
    run in bf16 (the regression this pins: g applied after the downcast,
    or a bf16 mean, breaks the equality)."""
    rng = np.random.default_rng(11)
    m, d = 24, 96
    x16 = jnp.asarray(rng.normal(size=(m, d)) * 8.0, jnp.bfloat16)
    g16 = jnp.asarray(1.0 + 0.5 * rng.normal(size=(d,)), jnp.bfloat16)
    b16 = jnp.asarray(0.5 * rng.normal(size=(d,)), jnp.bfloat16)
    if kind == "rms":
        got = layers.rmsnorm({"g": g16}, x16, EPS)
        want = dp.rmsnorm(x16.astype(jnp.float32), g16, EPS)
    else:
        got = layers.layernorm({"g": g16, "b": b16}, x16, EPS)
        want = dp.layernorm(x16.astype(jnp.float32), g16, b16, EPS)
    assert got.dtype == jnp.bfloat16
    assert want.dtype == jnp.float32          # the single downcast is ours
    assert jnp.array_equal(got, want.astype(jnp.bfloat16))


@pytest.mark.parametrize("kind", KINDS)
def test_layernorm_onepass_var_never_negative(kind):
    """Constant rows make E[x^2] - mu^2 slightly negative in floats; the
    one-pass clamp keeps the rsqrt argument at eps, not NaN."""
    x = jnp.full((4, 64), 3.14159, jnp.float32)
    g = jnp.ones((64,), jnp.float32)
    y = (dp.rmsnorm(x, g, EPS) if kind == "rms"
         else dp.layernorm(x, g, jnp.zeros((64,)), EPS))
    assert bool(jnp.all(jnp.isfinite(y)))


def test_eps_is_required_not_defaulted():
    """No 1e-6 default anywhere: a call that forgets to thread
    cfg.norm_eps must fail loudly, not silently normalize with a
    hard-coded epsilon."""
    p = {"g": jnp.ones((8,), jnp.float32), "b": jnp.zeros((8,), jnp.float32)}
    x = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(TypeError):
        layers.rmsnorm(p, x)
    with pytest.raises(TypeError):
        layers.layernorm(p, x)
    with pytest.raises(TypeError):
        dp.rmsnorm(x, p["g"])
    with pytest.raises(TypeError):
        dp.layernorm(x, p["g"], p["b"])


def _call_sites(text, name):
    """Argument text of every bare ``name(...)`` call (defs excluded)."""
    sites = []
    for m in re.finditer(rf"(?<![\w.])({name})\(", text):
        line_start = text.rfind("\n", 0, m.start()) + 1
        if text[line_start:m.start()].lstrip().startswith("def "):
            continue
        depth, i = 1, m.end()
        while depth:
            if text[i] == "(":
                depth += 1
            elif text[i] == ")":
                depth -= 1
            i += 1
        sites.append(text[m.end():i - 1])
    return sites


def test_every_model_norm_call_threads_eps():
    """Source audit of src/repro/models: every rmsnorm/layernorm call
    site passes an eps expression (qk-norm, the MLA latent norms, block
    norms, the final LM norm) — the companion to eps having no default."""
    models = os.path.join(REPO, "src", "repro", "models")
    found = 0
    for fname in sorted(os.listdir(models)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(models, fname)) as fh:
            text = fh.read()
        for name in ("rmsnorm", "layernorm"):
            for args in _call_sites(text, name):
                found += 1
                assert "eps" in args, (
                    f"{fname}: {name}({args}) does not thread an eps — "
                    "norm eps must come from config, never a default")
    assert found >= 4        # qk-norm x2 + MLA latent norms at minimum


# ---------------------------------------------------------------------------
# datapath VJP homes vs autodiff
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_datapath_norm_vjp_matches_autodiff(kind):
    rng = np.random.default_rng(5)
    m, d = 12, 40
    x = jnp.asarray(rng.normal(size=(m, d)) * 2.0, jnp.float32)
    g = jnp.asarray(1.0 + 0.3 * rng.normal(size=(d,)), jnp.float32)
    b = jnp.asarray(0.3 * rng.normal(size=(d,)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    if kind == "rms":
        dx_ad, dg_ad = jax.grad(
            lambda x_, g_: jnp.vdot(dp.rmsnorm(x_, g_, EPS), dy),
            argnums=(0, 1))(x, g)
        dx, dg_hat = dp.rmsnorm_vjp(x, g, EPS, dy)
        db, db_ad = None, None
    else:
        dx_ad, dg_ad, db_ad = jax.grad(
            lambda x_, g_, b_: jnp.vdot(dp.layernorm(x_, g_, b_, EPS), dy),
            argnums=(0, 1, 2))(x, g, b)
        dx, dg_hat, db_hat = dp.layernorm_vjp(x, g, EPS, dy)
        db = jnp.sum(db_hat, axis=0)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ad), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.sum(dg_hat, axis=0)),
                               np.asarray(dg_ad), atol=1e-5)
    if db is not None:
        np.testing.assert_allclose(np.asarray(db), np.asarray(db_ad),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# fused seams vs the dense contract: outputs AND gradients
# ---------------------------------------------------------------------------


def _grads(loss_fn, args):
    return jax.grad(loss_fn, argnums=tuple(range(len(args))))(*args)


def _assert_tree_close(got, want, atol, tag, rtol=0.0):
    for i, (a, b) in enumerate(zip(got, want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=atol,
                                   rtol=rtol, err_msg=f"{tag}[{i}]")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name,dtype,m,d,f", SHAPES)
def test_fused_residual_norm_matches_dense(kind, name, dtype, m, d, f):
    x, r, g, b, _, _ = _data(m, d, f, dtype, kind)
    co = jnp.asarray(np.random.default_rng(9).normal(size=(2, m, d)),
                     jnp.float32)

    def dense(*a):
        x_, r_, g_ = a[:3]
        s = x_ + r_
        return s, _dense_norm(s, g_, a[3] if kind == "layer" else None, kind)

    def fused(*a):
        return fused_residual_norm(
            a[0], a[1], a[2], a[3] if kind == "layer" else None,
            kind=kind, eps=EPS, interpret=True, bm=8)

    args = (x, r, g) + ((b,) if kind == "layer" else ())
    out_d, out_f = dense(*args), fused(*args)
    atol = ATOL[dtype]
    assert out_f[0].dtype == out_f[1].dtype == jnp.dtype(dtype)
    _assert_tree_close(out_f, out_d, atol, f"{name}/{kind}/out")

    def loss(fn):
        return lambda *a: (
            jnp.vdot(fn(*a)[0].astype(jnp.float32), co[0])
            + jnp.vdot(fn(*a)[1].astype(jnp.float32), co[1]))

    _assert_tree_close(_grads(loss(fused), args), _grads(loss(dense), args),
                       GRAD_ATOL[dtype], f"{name}/{kind}/grad",
                       rtol=RTOL[dtype])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name,dtype,m,d,f", SHAPES)
def test_fused_norm_linear_matches_dense(kind, name, dtype, m, d, f):
    x, _, g, b, w, _ = _data(m, d, f, dtype, kind)
    co = jnp.asarray(np.random.default_rng(9).normal(size=(m, f)),
                     jnp.float32)

    def dense(*a):
        x_, g_, w_ = a[0], a[1], a[-1]
        h = _dense_norm(x_, g_, a[2] if kind == "layer" else None, kind)
        return h @ w_

    def fused(*a):
        return fused_norm_linear(
            a[0], a[1], a[2] if kind == "layer" else None, a[-1],
            kind=kind, eps=EPS, interpret=True, bm=8, bf=128)

    args = (x, g) + ((b,) if kind == "layer" else ()) + (w,)
    out_d, out_f = dense(*args), fused(*args)
    assert out_f.dtype == jnp.dtype(dtype)
    _assert_tree_close([out_f], [out_d], ATOL[dtype], f"{name}/{kind}/out",
                       rtol=RTOL[dtype])

    def loss(fn):
        return lambda *a: jnp.vdot(fn(*a).astype(jnp.float32), co)

    _assert_tree_close(_grads(loss(fused), args), _grads(loss(dense), args),
                       GRAD_ATOL[dtype], f"{name}/{kind}/grad",
                       rtol=RTOL[dtype])


@pytest.mark.parametrize("mode", ["gelu", "silu"])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name,dtype,m,d,f", SHAPES)
def test_fused_norm_glu_matches_dense(kind, name, dtype, m, d, f, mode):
    x, _, g, b, wg, wu = _data(m, d, f, dtype, kind)
    co = jnp.asarray(np.random.default_rng(9).normal(size=(m, f)),
                     jnp.float32)

    def dense(*a):
        x_, g_ = a[0], a[1]
        wg_, wu_ = a[-2], a[-1]
        h = _dense_norm(x_, g_, a[2] if kind == "layer" else None, kind)
        h32 = h.astype(jnp.float32)
        return (dp.pair_act(h32 @ wg_.astype(jnp.float32), mode)
                * (h32 @ wu_.astype(jnp.float32))).astype(x_.dtype)

    def fused(*a):
        return fused_norm_glu(
            a[0], a[1], a[2] if kind == "layer" else None, a[-2], a[-1],
            kind=kind, eps=EPS, mode=mode, interpret=True, bm=8, bf=128)

    args = (x, g) + ((b,) if kind == "layer" else ()) + (wg, wu)
    out_d, out_f = dense(*args), fused(*args)
    assert out_f.dtype == jnp.dtype(dtype)
    _assert_tree_close([out_f], [out_d], ATOL[dtype], f"{name}/{kind}/out",
                       rtol=RTOL[dtype])

    def loss(fn):
        return lambda *a: jnp.vdot(fn(*a).astype(jnp.float32), co)

    _assert_tree_close(_grads(loss(fused), args), _grads(loss(dense), args),
                       GRAD_ATOL[dtype], f"{name}/{kind}/grad",
                       rtol=RTOL[dtype])


# ---------------------------------------------------------------------------
# the provider registry + end-to-end block threading
# ---------------------------------------------------------------------------


def test_norm_provider_carries_every_seam():
    prov = dispatch.get_norm("fused_pallas")
    assert prov is not None
    for seam in dispatch.NORM_SEAMS:
        assert callable(prov.get(seam)), seam
    assert dispatch.get_norm("dense") is None
    assert dispatch.resolve_norm("auto") in dispatch._NORM
    with pytest.raises(ValueError, match="unknown norm impl"):
        dispatch.get_norm("nope")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "yi-6b"])
def test_block_fused_norm_impl_matches_dense_end_to_end(arch):
    """The whole stack through models/transformer.block_apply: logits and
    parameter gradients with norm_impl='fused_pallas' (every seam fused:
    norm->QKV prologue, residual+norm epilogue, norm->GLU prologue) vs
    the dense reference."""
    import dataclasses

    from repro.configs import registry
    from repro.models.transformer import init_lm, lm_apply

    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)

    def logits_of(c):
        return lm_apply(params, c, toks)[0]

    def loss_of(c):
        return lambda p: lm_apply(p, c, toks)[0].astype(jnp.float32).sum()

    fused_cfg = dataclasses.replace(cfg, norm_impl="fused_pallas")
    out_d, out_f = logits_of(cfg), logits_of(fused_cfg)
    np.testing.assert_allclose(np.asarray(out_f, np.float32),
                               np.asarray(out_d, np.float32), atol=5e-4)
    from jax.flatten_util import ravel_pytree
    gd = jax.grad(loss_of(cfg))(params)
    gf = jax.grad(loss_of(fused_cfg))(params)
    flat_d, _ = ravel_pytree(gd)
    flat_f, _ = ravel_pytree(gf)
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_d),
                               atol=5e-3)


def test_block_fused_respects_megatron_pins():
    """With Megatron inner pins active (ctx.pin_full/pin_sp), the block
    must NOT fuse: the pins need the residual stream and the normed
    stream as SEPARATE shardable values.  So under pins the fused config
    runs the IDENTICAL dense graph — bitwise, not just close."""
    import dataclasses

    from jax.sharding import Mesh, PartitionSpec as P

    from repro.configs import registry
    from repro.models.transformer import init_lm, lm_apply

    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    fused_cfg = dataclasses.replace(cfg, norm_impl="fused_pallas")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("model",))
    pspec = P(None, "model", None)
    with jax.set_mesh(mesh):
        out_d = lm_apply(params, cfg, toks,
                         act_pspec=pspec, inner_pins=True)[0]
        out_f = lm_apply(params, fused_cfg, toks,
                         act_pspec=pspec, inner_pins=True)[0]
    assert jnp.array_equal(out_f, out_d)


# ---------------------------------------------------------------------------
# the int counterpart: guaranteed normalization on the word lattice
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_int_norm_tracks_the_float_home(kind):
    """rmsnorm_int/layernorm_int run rsqrt as the unit's log2 -> shift ->
    exp2 traversal, entirely in int32 (the purity pass audits the path);
    vs the float home the residual is lattice quantization + PWL error —
    well under one S5.10 step times the gain."""
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(16, 128)) * 2.0, jnp.float32)
    g = jnp.asarray(1.0 + 0.1 * rng.normal(size=(128,)), jnp.float32)
    b = jnp.asarray(0.1 * rng.normal(size=(128,)), jnp.float32)
    if kind == "rms":
        got = unit.rmsnorm_dualmode(x, g, eps=EPS)
        want = dp.rmsnorm(x, g, EPS)
    else:
        got = unit.layernorm_dualmode(x, g, b, eps=EPS)
        want = dp.layernorm(x, g, b, EPS)
    err = float(jnp.abs(got - want).max())
    assert err <= 8e-3, err
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("kind", KINDS)
def test_int_norm_output_is_unit_scale(kind):
    """Guaranteed normalization: even a wildly mis-scaled input comes out
    at unit RMS (the property eps exists to protect in float — on the
    lattice the clamp + saturation rails play that role)."""
    rng = np.random.default_rng(23)
    for scale in (0.05, 1.0, 10.0):
        x = jnp.asarray(rng.normal(size=(8, 128)) * scale, jnp.float32)
        fn = unit.rmsnorm_int if kind == "rms" else unit.layernorm_int
        y = unit.dequantize(fn(unit.quantize(x)), unit.IN_FRAC)
        ms = float(jnp.sqrt(jnp.mean(jnp.square(y))))
        assert 0.8 <= ms <= 1.2, (scale, ms)
