"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses — block shapes
off the (8, 128) tiling, kernels that overrun the scoped VMEM, lowerings
Mosaic lacks.  These tests lower and compile each kernel with
``interpret=False`` against a ``v5e:2x2`` topology description, which
needs the TPU compiler but no chip, at the published widths of the models
the serving and training paths run: qwen1.5-0.5b (16 kv heads, head dim
64, d_model 1024, FFN 2816), qwen3-14b for grouped queries (40 heads over
8 kv heads, head dim 128) and bert-base for the unit's GELU mode (FFN
3072).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.  All chip descriptions live in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (flash_attention_bwd, flash_decode, fused_ffn,
                           fused_norm)
from repro.kernels.dualmode_softmax import pair_act_pallas, softmax_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention_int import flash_attention_pallas_int

# qwen1.5-0.5b attention / FFN widths
KV, G, HD = 16, 1, 64
D, FFN = 1024, 2816
# qwen3-14b grouped-query attention
GQA_KV, GQA_G, GQA_HD = 8, 5, 128

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip; the
    persistent compile cache is off around these compiles, whose entries
    could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _attn(s, b, sq, t, kh, g, hd, dtype):
    return (s((b, sq, kh, g, hd), dtype), s((b, t, kh, hd), dtype),
            s((b, t, kh, hd), dtype), s((b, sq), I32), s((b, t), jnp.bool_))


def _flash(dtype, kh=KV, g=G, hd=HD, sq=2048, t=4096, b=1):
    def build(s):
        def fn(q, k, v, q_pos, valid):
            return flash_attention_pallas(q, k, v, q_pos=q_pos,
                                          kv_valid=valid, interpret=False)
        return fn, _attn(s, b, sq, t, kh, g, hd, dtype)
    return build


def _flash_grad(s):
    """Training step of bert-base attention: forward + both backward
    kernels through the custom VJP (12 heads, seq 512, non-causal)."""
    def fn(q, k, v, q_pos, valid):
        def loss(q_, k_, v_):
            return flash_attention_pallas(
                q_, k_, v_, q_pos=q_pos, kv_valid=valid, causal=False,
                interpret=False).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return fn, _attn(s, 8, 512, 512, 12, 1, 64, BF16)


def _flash_bwd(s):
    """The dq and dk/dv kernels alone, at qwen1.5-0.5b widths."""
    sq = t = 2048

    def fn(q, k, v, q_pos, valid, o, m, l, do):
        return flash_attention_bwd.flash_attention_bwd_pallas(
            q, k, v, o, m, l, do, q_pos=q_pos, kv_valid=valid, causal=True,
            block_q=128, block_kv=512, interpret=False)
    q, k, v, q_pos, valid = _attn(s, 1, sq, t, KV, G, HD, F32)
    stat = s((1, KV, G, sq), F32)
    return fn, (q, k, v, q_pos, valid, s((1, sq, KV, G, HD), F32), stat,
                stat, s((1, sq, KV, G, HD), F32))


def _flash_int(s):
    def fn(q, k, v, q_pos, valid):
        return flash_attention_pallas_int(q, k, v, q_pos=q_pos,
                                          kv_valid=valid, interpret=False)
    return fn, _attn(s, 1, 2048, 4096, KV, G, HD, BF16)


def _decode(mode, kh=KV, g=G, hd=HD):
    def build(s):
        def fn(q, k, v, q_pos, valid):
            return flash_decode.flash_decode_pallas(
                q, k, v, q_pos=q_pos, kv_valid=valid, num_splits=1,
                softmax_impl=mode, interpret=False)
        return fn, (s((8, 1, kh, g, hd), F32), s((8, 4096, kh, hd), BF16),
                    s((8, 4096, kh, hd), BF16), s((8, 1), I32),
                    s((8, 4096), jnp.bool_))
    return build


def _decode_paged(mode, kh=KV, g=G, hd=HD):
    """The serve engine's paged pool at max_seq 4096: 8 slots x 32 blocks
    of 128 tokens plus the sentinel block."""
    def build(s):
        def fn(q, k_pool, v_pool, tables, q_pos, valid):
            return flash_decode.flash_decode_paged(
                q, k_pool, v_pool, block_tables=tables, q_pos=q_pos,
                kv_valid=valid, num_splits=1, softmax_impl=mode,
                interpret=False)
        return fn, (s((8, 1, kh, g, hd), F32), s((257, 128, kh, hd), BF16),
                    s((257, 128, kh, hd), BF16), s((8, 32), I32),
                    s((8, 1), I32), s((8, 4096), jnp.bool_))
    return build


def _residual_norm(s):
    def fn(x, r, g):
        return fused_norm.fused_residual_norm(x, r, g, None, kind="rms",
                                              eps=1e-6, interpret=False)
    return fn, (s((2048, D), BF16), s((2048, D), BF16), s((D,), BF16))


def _norm_linear(s):
    def fn(x, g, w):
        return fused_norm.fused_norm_linear(x, g, None, w, kind="rms",
                                            eps=1e-6, interpret=False)
    return fn, (s((2048, D), BF16), s((D,), BF16), s((D, 3 * D), BF16))


def _norm_glu(s):
    def fn(x, g, wg, wu):
        return fused_norm.fused_norm_glu(x, g, None, wg, wu, kind="rms",
                                         eps=1e-6, mode="silu",
                                         interpret=False)
    return fn, (s((2048, D), BF16), s((D,), BF16), s((D, FFN), BF16),
                s((D, FFN), BF16))


def _glu(s):
    def fn(x, wg, wu):
        return fused_ffn.fused_glu_pallas(x, wg, wu, mode="silu",
                                          interpret=False)
    return fn, (s((2048, D), BF16), s((D, FFN), BF16), s((D, FFN), BF16))


def _unit_softmax(s):
    def fn(x):
        return softmax_pallas(x, precision="int", interpret=False)
    return fn, (s((4096, 2048), F32),)


def _unit_gelu(s):
    """The paper's GELU mode at bert-base FFN width."""
    def fn(z):
        return pair_act_pallas(z, mode="gelu", precision="int",
                               interpret=False)
    return fn, (s((4096, 3072), F32),)


CASES = {
    "flash_fwd_f32": _flash(F32),
    "flash_fwd_bf16": _flash(BF16, b=2),
    "flash_fwd_gqa": _flash(BF16, kh=GQA_KV, g=GQA_G, hd=GQA_HD, sq=1024,
                            t=2048),
    "flash_fwd_bwd_bert": _flash_grad,
    "flash_bwd_dq_dkdv": _flash_bwd,
    "flash_int": _flash_int,
    "decode_float": _decode("float"),
    "decode_dualmode": _decode("dualmode"),
    "decode_paged_float": _decode_paged("float"),
    "decode_paged_dualmode": _decode_paged("dualmode"),
    "decode_paged_gqa": _decode_paged("float", kh=GQA_KV, g=GQA_G,
                                      hd=GQA_HD),
    "fused_residual_norm": _residual_norm,
    "fused_norm_linear": _norm_linear,
    "fused_norm_glu": _norm_glu,
    "fused_glu": _glu,
    "unit_softmax_int": _unit_softmax,
    "unit_gelu_int": _unit_gelu,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(spec, name):
    fn, args = CASES[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
