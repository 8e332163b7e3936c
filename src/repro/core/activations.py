"""Activation registry — every GELU/SiLU variant the paper compares, plus
the framework's standard activations.

Variants (paper Table I naming):
  'gelu_exact'        FP32 erf GELU                       (the 'FP32' model)
  'gelu_tanh'         tanh-approximated GELU (Eq. 4)
  'gelu_via_softmax'  Eq. 8 in float — algorithm-faithful, no quantization
  'gelu_dualmode'     Eq. 8 through the bit-accurate int32 dual-mode unit
                      (the 'Proposed' model)
  'igelu'             I-BERT integer GELU                 (the 'i-GELU' model)
  'silu' / 'silu_via_softmax' / 'silu_dualmode'
                      exact-identity SiLU through the same unit (beyond-paper)
  'relu2'             squared ReLU (RWKV-6 channel mix; technique N/A)

Quantized variants use a straight-through estimator so they are trainable
drop-ins (forward = unit bits, backward = float surrogate gradient).
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import datapath as _dp

from . import igelu as _igelu
from . import softmax_unit as _unit


def gelu_exact(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(_dp.gelu_k(x)))


def gelu_via_softmax(x):
    """Eq. (8): z * softmax_1^2([k, -k]) == z * sigmoid(2k), float."""
    return _dp.gelu(x)


def silu(x):
    return x * jax.nn.sigmoid(x)


def silu_via_softmax(x):
    """Exact identity: z * softmax_1^2([z/2, -z/2])."""
    return _dp.silu(x)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _ste(fwd_quant: Callable, surrogate: Callable, name: str) -> Callable:
    """Straight-through wrapper: forward bits, backward surrogate grad.
    The unit computes in f32 words; the result keeps the input's dtype.
    Its ops carry ``name`` in their ``op_name`` (a profiler trace shows
    them under it)."""
    def f(x):
        with jax.named_scope(name):
            q = fwd_quant(x).astype(x.dtype)
            return surrogate(x) + jax.lax.stop_gradient(q - surrogate(x))
    return f


gelu_dualmode = _ste(_unit.gelu_dualmode, gelu_tanh, "unit.gelu_dualmode")
silu_dualmode = _ste(_unit.silu_dualmode, silu, "unit.silu_dualmode")
igelu_st = _ste(_igelu.igelu_quant, gelu_tanh, "unit.igelu")


ACTIVATIONS: dict[str, Callable] = {
    "gelu_exact": gelu_exact,
    "gelu_tanh": gelu_tanh,
    "gelu_via_softmax": gelu_via_softmax,
    "gelu_dualmode": gelu_dualmode,
    "igelu": igelu_st,
    "igelu_float": _igelu.igelu_float,
    "silu": silu,
    "silu_via_softmax": silu_via_softmax,
    "silu_dualmode": silu_dualmode,
    "relu2": relu2,
}


def get_activation(name: str) -> Callable:
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"unknown activation {name!r}; have {sorted(ACTIVATIONS)}")
