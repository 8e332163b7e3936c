"""Primitive layers — pure-JAX pytree modules (init fn + apply fn).

Conventions:
  * params are nested dicts of jnp arrays; init fns take (key, ...) and a
    dtype; apply fns are pure.
  * activations / softmax go through ``repro.core`` selections so the
    paper's dual-mode unit is a config switch, not a code fork.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.activations import get_activation
from repro.kernels import datapath as dp
from repro.kernels import dispatch
from repro.kernels import fused_ffn as _fused_ffn  # noqa: F401  (registers)

Params = dict[str, Any]


# ---------------- init helpers ----------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / math.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------- linear ----------------

def linear_init(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    p = {"w": dense_init(key, d_in, d_out, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------- norms ----------------
#
# Thin wrappers over the datapath's single float definitions
# (kernels/datapath.rmsnorm / .layernorm).  The numeric contract lives
# there: moments AND gain/bias entirely in f32, ONE downcast on the
# finished result (applied here).  ``eps`` is required — call sites must
# thread cfg.norm_eps so nothing drifts from the config value.

def rmsnorm_init(d: int, dtype) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x, eps: float):
    return dp.rmsnorm(x, p["g"], eps).astype(x.dtype)


def layernorm_init(d: int, dtype) -> Params:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x, eps: float):
    return dp.layernorm(x, p["g"], p["b"], eps).astype(x.dtype)


def make_norm(kind: str):
    if kind == "rms":
        return rmsnorm_init, rmsnorm
    if kind == "layer":
        return layernorm_init, layernorm
    raise ValueError(kind)


# ---------------- rotary embedding ----------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, ``0.1 mscale ln(factor) + 1``
    (1 when the positions are not stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, yarn):
    """DeepSeek-V2's YaRN frequencies (``DeepseekV2YarnRotaryEmbedding``):
    ``1/theta^(2i/d)`` kept where a dimension turns more than
    ``beta_fast`` times in ``original_max_pos`` positions, divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, blended by
    a linear ramp in between.  ``yarn`` is a ``configs.base.YarnCfg``."""
    def dim_of(rotations):
        return (head_dim * math.log(yarn.original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    keep = 1.0 - ramp
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def apply_rope(x, positions, theta: float = 10000.0, yarn=None):
    """x: (..., S, H, hd) rotate-half RoPE; positions: (..., S).  With
    ``yarn`` (a ``configs.base.YarnCfg``) the frequencies are YaRN's and
    cos/sin carry ``mscale(mscale) / mscale(mscale_all_dim)``."""
    hd = x.shape[-1]
    inv = (rope_freqs(hd, theta) if yarn is None
           else yarn_freqs(hd, theta, yarn))                      # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv       # (..,S,hd/2)
    cos = jnp.cos(ang)[..., None, :]                              # (..,S,1,hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(n_pos: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------- softmax selection ----------------

def softmax_fn(impl: str):
    """Attention-softmax implementation switch (kernels/dispatch registry)."""
    return dispatch.get_softmax(impl)


# ---------------- MLPs ----------------

def mlp_init(key, d: int, d_ff: int, dtype, gated: bool = True,
             bias: bool = False) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up": linear_init(ks[0], d, d_ff, dtype, bias=bias),
         "down": linear_init(ks[1], d_ff, d, dtype, bias=bias)}
    if gated:
        p["gate"] = linear_init(ks[2], d, d_ff, dtype, bias=bias)
    return p


# activations the fused epilogue (datapath.pair_act, float log-domain
# form) agrees with MATHEMATICALLY — gelu_tanh is the tanh-form identity
# tanh(k) = 2*sigma(2k)-1 of the same curve, not the same instruction
# sequence, so fused-vs-dense parity is a small-ULP tolerance, not
# bitwise (pinned per entry in tests/test_fused_ffn.py).  Anything else —
# relu2, the bit-accurate dualmode/igelu variants, erf-exact GELU — must
# stay on the dense path rather than be silently approximated.
_FUSABLE_ACT = {"gelu_tanh": "gelu", "gelu_via_softmax": "gelu",
                "silu": "silu", "silu_via_softmax": "silu"}


def mlp(p: Params, x, activation: str = "silu", impl: str = "dense",
        prenorm=None, norm_impl: str = "dense"):
    """(Gated) MLP.  For gated GLU the activation applies to the gate path —
    this is where the dual-mode unit's GELU/SiLU mode is used.

    ``impl`` resolves through the kernel registry: 'dense' is the plain
    XLA graph; 'fused_pallas' runs the bias-free gated pair through the
    fused matmul+epilogue kernel (kernels/fused_ffn.py) when the
    activation is one the fused epilogue computes exactly; 'auto' picks
    'fused_pallas' on TPU and 'dense' elsewhere (dispatch.resolve_ffn).

    ``prenorm=(norm_params, kind, eps)`` makes this sublayer own its norm
    seam: with a fused norm provider (``norm_impl``, fusable activation,
    bias-free gate/up) the norm->gate/up prologue runs as ONE Pallas
    kernel (kernels/fused_norm.norm_glu); otherwise the dense norm is
    applied here and the body proceeds unchanged."""
    fused = dispatch.get_ffn(dispatch.resolve_ffn(impl))
    mode = _FUSABLE_ACT.get(activation)
    if prenorm is not None:
        np_, kind, eps = prenorm
        nprov = dispatch.get_norm(dispatch.resolve_norm(norm_impl))
        if (nprov is not None and mode is not None and "gate" in p
                and "b" not in p["gate"] and "b" not in p["up"]):
            h = nprov["norm_glu"](x, np_["g"], np_.get("b"),
                                  p["gate"]["w"], p["up"]["w"],
                                  kind=kind, eps=eps, mode=mode)
            return linear(p["down"], h)
        x = (rmsnorm if kind == "rms" else layernorm)(np_, x, eps)
    if (fused is not None and mode is not None and "gate" in p
            and "b" not in p["gate"] and "b" not in p["up"]):
        x2 = x.reshape(-1, x.shape[-1])
        h = fused(x2, p["gate"]["w"], p["up"]["w"], mode)
        return linear(p["down"], h.reshape(*x.shape[:-1], h.shape[-1]))
    act = get_activation(activation)
    up = linear(p["up"], x)
    if "gate" in p:
        h = act(linear(p["gate"], x)) * up
    else:
        h = act(up)
    return linear(p["down"], h)
