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

The serve engine's paged step programs are compiled whole as well, with
their caches donated, to pin that a step keeps the KV pool in place; and
DeepSeek-V2-Lite's at its benchmark cell's size (27 layers, 8 held
experts, 16 slots of 8192 tokens), to pin that they fit the chip and that
every kernel its dispatch picks compiles at MLA's head dims.
"""
from __future__ import annotations

import math
import os
import re

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
    of 128 tokens plus the sentinel block, lane-dense and stacked over 2
    layers, read at a traced layer index."""
    def build(s):
        def fn(q, k_pool, v_pool, tables, layer, q_pos, valid):
            return flash_decode.flash_decode_paged(
                q, k_pool, v_pool, block_tables=tables, layer=layer,
                q_pos=q_pos, kv_valid=valid, num_splits=1,
                softmax_impl=mode, interpret=False)
        pool = s((2, 257, 128, kh * hd), BF16)
        return fn, (s((8, 1, kh, g, hd), F32), pool, pool, s((8, 32), I32),
                    s((), I32), s((8, 1), I32), s((8, 4096), jnp.bool_))
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


# ---------------- the serve engine's step programs ----------------

# a small pool that still exceeds the chip's 128 MiB of VMEM, as every
# deployed pool does (the compiler stages a smaller one through VMEM
# whole): 8 slots x 32 blocks of 128 tokens plus the sentinel, at
# qwen1.5-0.5b widths over 2 layers, 135 MB for K and for V
STEP_BLOCKS, STEP_SLOTS = 257, 8
_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice")


def _step_program(phase, mode):
    """(step fn, args) of the engine's paged chunk-prefill or decode
    program at the impls it resolves on the chip."""
    from repro.configs import registry
    from repro.models.transformer import init_lm, init_paged_caches
    from repro.serve.engine import (make_chunk_prefill_step,
                                    make_paged_decode_step)
    cfg = registry.get_config("qwen1.5-0.5b").replace(
        n_layers=2, ffn_impl="fused_pallas", norm_impl="fused_pallas",
        softmax_impl=mode)

    def build(s):
        def shapes(tree):
            return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
        params = shapes(jax.eval_shape(
            lambda: init_lm(jax.random.PRNGKey(0), cfg, BF16)))
        caches = shapes(jax.eval_shape(
            lambda: init_paged_caches(cfg, STEP_BLOCKS, 128, BF16)))
        if phase == "prefill":
            impl = "flash_pallas_int" if mode == "dualmode" else \
                "flash_pallas"
            fn = make_chunk_prefill_step(cfg.replace(attn_impl=impl))
            return fn, (params, caches, s((1, 2048), I32), s((), I32),
                        s((1, 32), I32), s((1,), I32))
        fn = make_paged_decode_step(cfg.replace(attn_impl="flash_decode"))
        return fn, (params, caches, s((STEP_SLOTS, 1), I32),
                    s((STEP_SLOTS,), I32), s((STEP_SLOTS, 32), I32))
    return build


def _pool_moves(hlo: str, sizes: set) -> list:
    """HLO instructions that yield a buffer of one of ``sizes`` elements
    by a copy, a dynamic slice or a dynamic update (their fusions are
    named after them)."""
    bad = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (.*?) ([\w-]+)\(", line)
        if not m:
            continue
        name, typ, op = m.groups()
        got = {math.prod(int(d) for d in dims.split(",") if d)
               for dims in re.findall(r"\[([\d,]*)\]", typ)}
        if got & sizes and (op in _MOVES or any(
                k in name for k in ("copy", "dynamic-slice",
                                    "dynamic-update-slice"))):
            bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("phase,mode", [("decode", "float"),
                                        ("decode", "dualmode"),
                                        ("prefill", "float")])
def test_paged_step_keeps_the_pool_in_place(spec, monkeypatch, phase,
                                            mode):
    """The paged step programs as ``ServeEngine`` jits them, caches
    donated: every cache buffer is aliased to the output, and no
    instruction copies, slices or updates a whole pool or a whole layer
    of one — a step writes only its new rows and reads only live
    blocks."""
    from repro.kernels import tiling
    monkeypatch.setattr(tiling, "interpret_mode", lambda: False)
    fn, args = _step_program(phase, mode)(spec)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    pools = jax.tree.leaves(args[1])
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in pools)
    sizes = {a.size for a in pools} | {a.size // a.shape[0] for a in pools}
    assert _pool_moves(hlo, sizes) == []


# ---------------- deepseek-v2-lite at its cell's size ----------------

# the bench cell deepseek-v2-lite.doc_chat: all 27 layers at published
# widths, 8 of 64 experts held per MoE layer, 16 slots of 8192 tokens,
# 1025 blocks of 128 tokens, prefill chunks of 2048
DS_SLOTS, DS_BLOCKS, DS_BS, DS_SEQ, DS_CHUNK = 16, 1025, 128, 8192, 2048
HBM_BYTES = int(15.75 * 2 ** 30)       # a v5e's usable device memory


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_deepseek_step_programs_fit_and_compile(spec, monkeypatch, phase):
    """DeepSeek-V2-Lite's paged step programs as ``ServeEngine`` builds
    them on the chip (the impls its dispatch resolves there, caches
    donated, held-row counts on): the pools are aliased to the output,
    the compiled arguments plus temporaries fit the chip, and every
    kernel the dispatch picked is in the program — the Pallas flash
    kernel for the chunk's attention at q.k head dim 192 and v head dim
    128, the fused GLU at the dense layer's 10944 and the shared experts'
    2816, the fused residual norm, and the ragged expert matmuls.  The
    decode tick attends against the latent in the absorbed form, with no
    attention kernel and no expanded keys."""
    import dataclasses

    from repro.configs import registry
    from repro.kernels import tiling
    from repro.models import flash
    from repro.models.transformer import init_lm, init_paged_caches
    from repro.serve.engine import (make_chunk_prefill_step,
                                    make_paged_decode_step)
    monkeypatch.setattr(tiling, "interpret_mode", lambda: False)
    cfg = registry.get_config("deepseek-v2-lite-16b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=8),
                      ffn_impl="fused_pallas", norm_impl="fused_pallas")
    nblk = DS_SEQ // DS_BS

    def shapes(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg, BF16)))
    caches = shapes(jax.eval_shape(
        lambda: init_paged_caches(cfg, DS_BLOCKS, DS_BS, BF16)))
    if phase == "prefill":
        # the auto rule as on the chip: a 2048 x 8192 score tile streams
        # through the blocked kernel
        impl = (flash.blocked_impl("tpu") if flash.use_flash(DS_CHUNK,
                                                             DS_SEQ)
                else "naive")
        fn = make_chunk_prefill_step(cfg.replace(attn_impl=impl),
                                     counts=True)
        args = (params, caches, spec((1, DS_CHUNK), I32), spec((), I32),
                spec((1, nblk), I32), spec((1,), I32))
        assert impl == "flash_pallas"
    else:
        fn = make_paged_decode_step(cfg, counts=True)
        args = (params, caches, spec((DS_SLOTS, 1), I32),
                spec((DS_SLOTS,), I32), spec((DS_SLOTS, nblk), I32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert mem.alias_size_in_bytes == sum(a.size * a.dtype.itemsize
                                          for a in pools)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    hlo = compiled.as_text()
    kernels = set(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo))
    wants = ["moe.shared/jit(_fused_glu_jit)", "_resnorm_jit", "ragged-dot"]
    if phase == "prefill":
        wants.append("_flash_pallas_jit")
    else:
        # no key is expanded: no buffer holds H x (nope + v) values for
        # every position of the slots' tables
        expanded = DS_SLOTS * DS_SEQ * cfg.n_heads * (
            cfg.mla.nope_dim + cfg.mla.v_dim)
        assert f"{DS_SLOTS},{DS_SEQ},{cfg.n_heads}," not in hlo
        assert mem.temp_size_in_bytes < expanded * 2
    for want in wants:
        assert any(want in k for k in kernels), (want, sorted(kernels))
    assert sum("_fused_glu_jit" in k for k in kernels) == 2
