"""Pallas kernels for the block's norm seams — normalization joins the
shared unit (ROADMAP item 3; SOLE / Choi et al. in PAPERS.md).

The transformer block has three memory-bound seams where a norm sits
between a residual stream and a matmul, each costing an HBM round trip of
the (tokens, d_model) activation in the unfused graph:

  residual_norm   (x, r)        -> (x + r, norm(x + r))
                  the attention-output / FFN epilogue: the residual add
                  and the next sublayer's norm happen in VMEM, so the
                  normalized stream never round-trips HBM between them.
  norm_linear     x @ W seams   -> norm(x) @ W
                  the norm -> QKV-projection prologue: the normalized
                  activations are consumed by the matmul tile in VMEM
                  instead of being written out and read back.
  norm_glu        gated FFN     -> act(norm(x) @ Wg) * (norm(x) @ Wu)
                  the norm -> gate/up prologue, extending the fused-GLU
                  epilogue kernel (fused_ffn.py) one seam upstream.

All three inline the datapath's norm arithmetic (``kernels/datapath``:
rsqrt as exp2(-0.5*log2(v)) — one more traversal of the unit's log-domain
hardware), with moments and gain/bias entirely in f32 and a single
downcast on the finished result — the exact contract of the dense norms
in ``models/layers.py``, so fused-vs-dense parity is a <=1e-5 tolerance
(reduction order differs; see tests/test_fused_norm.py).

Backward: each kernel carries a custom VJP whose gradients route through
the datapath's single VJP homes (``rmsnorm_vjp``/``layernorm_vjp``); the
norm_glu backward reuses the fused GLU backward kernel
(``fused_ffn._glu_bwd_call``) for the in-VMEM d_gate/d_up tiles.  The
surrounding dots are plain XLA, mirroring fused_ffn's fwd-fused /
bwd-hybrid split.

Tiling follows the package policy: blocks resolve BEFORE the jit
boundary (``tiling.norm_rows`` / ``tiling.matmul_blocks``), the token
axis pads up to the block grid, and the feature/contraction dim stays
whole per tile (same as fused_ffn's unblocked K) — which also means the
row moments are computed over the TRUE feature width, never a padded
one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import datapath as dp
from . import dispatch, tiling
from .fused_ffn import _glu_bwd_call


def _hat(xn, *, kind: str, eps: float):
    """Normalized rows (no gain/bias) — the in-kernel moment datapath.

    ``xn`` is f32 (rows, d) with d the TRUE feature width (the tiles
    keep the feature dim whole, so no padded columns pollute the means).
    """
    inv_n = 1.0 / xn.shape[-1]
    if kind == "rms":
        ms = jnp.sum(xn * xn, axis=-1, keepdims=True) * inv_n
        return xn * jnp.exp2(-0.5 * jnp.log2(ms + eps))
    if kind == "layer":
        mu = jnp.sum(xn, axis=-1, keepdims=True) * inv_n
        var = jnp.maximum(
            jnp.sum(xn * xn, axis=-1, keepdims=True) * inv_n - mu * mu, 0.0)
        return (xn - mu) * jnp.exp2(-0.5 * jnp.log2(var + eps))
    raise ValueError(f"unknown norm kind {kind!r}")


def _dense_h(x, g, b, *, kind: str, eps: float):
    """The dense f32 normalized-and-scaled stream (datapath reference) —
    what the backward recomputes instead of saving h."""
    if kind == "rms":
        return dp.rmsnorm(x, g, eps)
    return dp.layernorm(x, g, b, eps)


def _norm_vjp(x, g, b, dy, *, kind: str, eps: float):
    """(dx, dg, db) through the datapath VJP homes; leading axes of the
    elementwise dg-hat/db-hat are reduced here.  db is None for rms."""
    if kind == "rms":
        dx, dg_hat = dp.rmsnorm_vjp(x, g, eps, dy)
        return dx, jnp.sum(dg_hat, axis=0), None
    dx, dg_hat, db_hat = dp.layernorm_vjp(x, g, eps, dy)
    return dx, jnp.sum(dg_hat, axis=0), jnp.sum(db_hat, axis=0)


# --------------------------------------------------------------------------
# residual-add + norm epilogue
# --------------------------------------------------------------------------

def _resnorm_body(x_ref, r_ref, g_ref, b_ref, xo_ref, ho_ref, *,
                  kind: str, eps: float):
    xn = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    h = (_hat(xn, kind=kind, eps=eps) * g_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    xo_ref[...] = xn.astype(xo_ref.dtype)
    ho_ref[...] = h.astype(ho_ref.dtype)


def fused_residual_norm(x, r, g, b=None, *, kind: str, eps: float,
                        interpret: bool = False, bm: int | None = None):
    """(x + r, norm(x + r) * g + b) with both outputs produced in VMEM.

    ``x``/``r`` are (..., d); ``b=None`` means rms (no bias).  Returns
    both outputs in x's dtype — the epilogue's h IS the next sublayer's
    input, downcast once, exactly like the dense contract.
    """
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    r2 = r.reshape(-1, d)
    m = x2.shape[0]
    rbm = tiling.norm_rows(m, d)
    bm = rbm if bm is None else tiling.round_up(bm, tiling.SUBLANE)
    has_b = b is not None
    xo, ho = _resnorm_jit(x2, r2, g, b if has_b else jnp.zeros_like(g),
                          kind=kind, eps=eps, interpret=interpret, bm=bm,
                          has_b=has_b)
    return xo.reshape(shape), ho.reshape(shape)


@functools.partial(jax.jit, static_argnames=("kind", "eps", "interpret",
                                             "bm", "has_b"))
def _resnorm_jit(x, r, g, b, *, kind: str, eps: float, interpret: bool,
                 bm: int, has_b: bool):
    m, d = x.shape

    def forward(x_, r_, g_, b_):
        xp, _ = tiling.pad_dim(x_, 0, bm)
        rp, _ = tiling.pad_dim(r_, 0, bm)
        xo, ho = pl.pallas_call(
            functools.partial(_resnorm_body, kind=kind, eps=eps),
            grid=(xp.shape[0] // bm,),
            in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                      pl.BlockSpec((bm, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))] * 2,
            out_shape=[jax.ShapeDtypeStruct((xp.shape[0], d), x_.dtype)] * 2,
            interpret=interpret,
        )(xp, rp, g_.reshape(1, d), b_.reshape(1, d))
        return tiling.unpad(xo, 0, m), tiling.unpad(ho, 0, m)

    @jax.custom_vjp
    def run(x_, r_, g_, b_):
        return forward(x_, r_, g_, b_)

    def fwd(x_, r_, g_, b_):
        return forward(x_, r_, g_, b_), (x_, r_, g_, b_)

    def bwd(res, gy):
        x_, r_, g_, b_ = res
        d_xnew, dh = gy
        xn = x_.astype(jnp.float32) + r_.astype(jnp.float32)
        dxn, dg, db = _norm_vjp(xn, g_, b_, dh, kind=kind, eps=eps)
        dxn = dxn + d_xnew.astype(jnp.float32)
        db = (db if db is not None else jnp.zeros_like(b_, jnp.float32))
        if not has_b:           # placeholder bias: no gradient flows out
            db = jnp.zeros_like(db)
        return (dxn.astype(x_.dtype), dxn.astype(r_.dtype),
                dg.astype(g_.dtype), db.astype(b_.dtype))

    run.defvjp(fwd, bwd)
    return run(x, r, g, b)


# --------------------------------------------------------------------------
# norm -> linear prologue (QKV projection)
# --------------------------------------------------------------------------

def _norm_linear_body(x_ref, g_ref, b_ref, w_ref, o_ref, *, kind: str,
                      eps: float):
    xn = x_ref[...].astype(jnp.float32)
    h = (_hat(xn, kind=kind, eps=eps) * g_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.dot(h, w_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def fused_norm_linear(x, g, b, w, *, kind: str, eps: float,
                      interpret: bool = False, bm: int | None = None,
                      bf: int | None = None):
    """norm(x) @ w without materializing the normalized stream.

    ``x`` (..., d), ``w`` (d, F) -> (..., F).  ``b=None`` for rms.
    The x tile is read once and both the moments and the matmul consume
    it in VMEM — the prologue's HBM saving: the normalized stream is
    never written to HBM and read back.
    """
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    m, f = x2.shape[0], w.shape[1]
    rbm, rbf = tiling.matmul_blocks(m, f)
    bm = rbm if bm is None else tiling.round_up(bm, tiling.SUBLANE)
    bf = rbf if bf is None else tiling.round_up(bf, tiling.LANE)
    has_b = b is not None
    o = _norm_linear_jit(x2, g, b if has_b else jnp.zeros_like(g), w,
                         kind=kind, eps=eps, interpret=interpret, bm=bm,
                         bf=bf, has_b=has_b)
    return o.reshape(shape[:-1] + (f,))


@functools.partial(jax.jit, static_argnames=("kind", "eps", "interpret",
                                             "bm", "bf", "has_b"))
def _norm_linear_jit(x, g, b, w, *, kind: str, eps: float, interpret: bool,
                     bm: int, bf: int, has_b: bool):
    m, d = x.shape
    f = w.shape[1]

    def forward(x_, g_, b_, w_):
        xp, _ = tiling.pad_dim(x_, 0, bm)
        wp, _ = tiling.pad_dim(w_, 1, bf)
        o = pl.pallas_call(
            functools.partial(_norm_linear_body, kind=kind, eps=eps),
            grid=(xp.shape[0] // bm, wp.shape[1] // bf),
            in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                      pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      pl.BlockSpec((d, bf), lambda i, j: (0, j))],
            out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                           x_.dtype),
            interpret=interpret,
        )(xp, g_.reshape(1, d), b_.reshape(1, d), wp)
        return tiling.unpad(tiling.unpad(o, 0, m), 1, f)

    @jax.custom_vjp
    def run(x_, g_, b_, w_):
        return forward(x_, g_, b_, w_)

    def fwd(x_, g_, b_, w_):
        return forward(x_, g_, b_, w_), (x_, g_, b_, w_)

    def bwd(res, do):
        x_, g_, b_, w_ = res
        do32 = do.astype(jnp.float32)
        dh = jnp.dot(do32, w_.astype(jnp.float32).T)
        h = _dense_h(x_, g_, b_, kind=kind, eps=eps)
        dw = jnp.dot(h.T, do32)
        dx, dg, db = _norm_vjp(x_, g_, b_, dh, kind=kind, eps=eps)
        db = (db if db is not None else jnp.zeros_like(b_, jnp.float32))
        if not has_b:
            db = jnp.zeros_like(db)
        return (dx.astype(x_.dtype), dg.astype(g_.dtype),
                db.astype(b_.dtype), dw.astype(w_.dtype))

    run.defvjp(fwd, bwd)
    return run(x, g, b, w)


# --------------------------------------------------------------------------
# norm -> gated-GLU prologue (fused_ffn one seam upstream)
# --------------------------------------------------------------------------

def _norm_glu_body(x_ref, g_ref, b_ref, wg_ref, wu_ref, o_ref, *,
                   kind: str, eps: float, mode: str):
    xn = x_ref[...].astype(jnp.float32)
    h = (_hat(xn, kind=kind, eps=eps) * g_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    gm = jnp.dot(h, wg_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    um = jnp.dot(h, wu_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    o_ref[...] = (dp.pair_act(gm, mode) * um).astype(o_ref.dtype)


def fused_norm_glu(x, g, b, wg, wu, *, kind: str, eps: float, mode: str,
                   interpret: bool = False, bm: int | None = None,
                   bf: int | None = None):
    """act(norm(x) @ wg) * (norm(x) @ wu) — norm prologue + the fused GLU
    epilogue in one kernel.  ``x`` (..., d) -> (..., F); ``b=None`` rms."""
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    m, f = x2.shape[0], wg.shape[1]
    rbm, rbf = tiling.matmul_blocks(m, f)
    bm = rbm if bm is None else tiling.round_up(bm, tiling.SUBLANE)
    bf = rbf if bf is None else tiling.round_up(bf, tiling.LANE)
    has_b = b is not None
    o = _norm_glu_jit(x2, g, b if has_b else jnp.zeros_like(g), wg, wu,
                      kind=kind, eps=eps, mode=mode, interpret=interpret,
                      bm=bm, bf=bf, has_b=has_b)
    return o.reshape(shape[:-1] + (f,))


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode",
                                             "interpret", "bm", "bf",
                                             "has_b"))
def _norm_glu_jit(x, g, b, wg, wu, *, kind: str, eps: float, mode: str,
                  interpret: bool, bm: int, bf: int, has_b: bool):
    m, d = x.shape
    f = wg.shape[1]

    def forward(x_, g_, b_, wg_, wu_):
        xp, _ = tiling.pad_dim(x_, 0, bm)
        wgp, _ = tiling.pad_dim(wg_, 1, bf)
        wup, _ = tiling.pad_dim(wu_, 1, bf)
        o = pl.pallas_call(
            functools.partial(_norm_glu_body, kind=kind, eps=eps,
                              mode=mode),
            grid=(xp.shape[0] // bm, wgp.shape[1] // bf),
            in_specs=[pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                      pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      pl.BlockSpec((1, d), lambda i, j: (0, 0)),
                      pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                      pl.BlockSpec((d, bf), lambda i, j: (0, j))],
            out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((xp.shape[0], wgp.shape[1]),
                                           x_.dtype),
            interpret=interpret,
        )(xp, g_.reshape(1, d), b_.reshape(1, d), wgp, wup)
        return tiling.unpad(tiling.unpad(o, 0, m), 1, f)

    @jax.custom_vjp
    def run(x_, g_, b_, wg_, wu_):
        return forward(x_, g_, b_, wg_, wu_)

    def fwd(x_, g_, b_, wg_, wu_):
        return forward(x_, g_, b_, wg_, wu_), (x_, g_, b_, wg_, wu_)

    def bwd(res, dy):
        x_, g_, b_, wg_, wu_ = res
        h = _dense_h(x_, g_, b_, kind=kind, eps=eps)
        # the fused GLU backward kernel emits d_gate/d_up in VMEM — the
        # norm prologue only changes what the surrounding dots contract
        dgm, dum = _glu_bwd_call(h, wg_, wu_, dy, mode=mode, bm=bm, bf=bf,
                                 interpret=interpret)
        dh = (jnp.dot(dgm, wg_.astype(jnp.float32).T)
              + jnp.dot(dum, wu_.astype(jnp.float32).T))
        dwg = jnp.dot(h.T, dgm)
        dwu = jnp.dot(h.T, dum)
        dx, dg, db = _norm_vjp(x_, g_, b_, dh, kind=kind, eps=eps)
        db = (db if db is not None else jnp.zeros_like(b_, jnp.float32))
        if not has_b:
            db = jnp.zeros_like(db)
        return (dx.astype(x_.dtype), dg.astype(g_.dtype),
                db.astype(b_.dtype), dwg.astype(wg_.dtype),
                dwu.astype(wu_.dtype))

    run.defvjp(fwd, bwd)
    return run(x, g, b, wg, wu)


# --------------------------------------------------------------------------
# audit surface + registration
# --------------------------------------------------------------------------

def vmem_plan(m: int, d: int, f: int):
    """Static VMEM residency of the three fused-norm kernels (audited by
    repro.analysis.vmem against VMEM_CORE_BUDGET).  The feature dim ``d``
    is unblocked in every kernel — the moments need whole rows — which is
    exactly the residency worth auditing."""
    bm_r = tiling.norm_rows(m, d)
    bm, bf = tiling.matmul_blocks(m, f)
    resnorm = {
        "in:x": ((bm_r, d), jnp.float32),
        "in:r": ((bm_r, d), jnp.float32),
        "in:g": ((1, d), jnp.float32),
        "in:b": ((1, d), jnp.float32),
        "out:x_new": ((bm_r, d), jnp.float32),
        "out:h": ((bm_r, d), jnp.float32),
    }
    norm_linear = {
        "in:x": ((bm, d), jnp.float32),
        "in:g": ((1, d), jnp.float32),
        "in:b": ((1, d), jnp.float32),
        "in:w": ((d, bf), jnp.float32),
        "out:o": ((bm, bf), jnp.float32),
    }
    norm_glu = {
        "in:x": ((bm, d), jnp.float32),
        "in:g": ((1, d), jnp.float32),
        "in:b": ((1, d), jnp.float32),
        "in:wg": ((d, bf), jnp.float32),
        "in:wu": ((d, bf), jnp.float32),
        "out:o": ((bm, bf), jnp.float32),
    }
    return {"resnorm_fwd": resnorm, "norm_linear_fwd": norm_linear,
            "norm_glu_fwd": norm_glu}


dispatch.register_norm("fused_pallas", {
    "residual_norm": lambda x, r, g, b, *, kind, eps: fused_residual_norm(
        x, r, g, b, kind=kind, eps=eps, interpret=tiling.interpret_mode()),
    "norm_linear": lambda x, g, b, w, *, kind, eps: fused_norm_linear(
        x, g, b, w, kind=kind, eps=eps, interpret=tiling.interpret_mode()),
    "norm_glu": lambda x, g, b, wg, wu, *, kind, eps, mode: fused_norm_glu(
        x, g, b, wg, wu, kind=kind, eps=eps, mode=mode,
        interpret=tiling.interpret_mode()),
})
