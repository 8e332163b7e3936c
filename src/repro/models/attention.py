"""Attention variants: GQA/MHA (qk-norm, qkv-bias), MLA, cross-attention.

All variants share one scores->softmax->combine core so the attention
softmax goes through the configured implementation (float or the paper's
dual-mode unit).  KV caches are explicit pytrees so the serving engine and
the scan-over-layers stack can thread them.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import datapath as dp
from repro.kernels import dispatch
from repro.kernels import flash_attention as _pallas_flash      # noqa: F401
from repro.kernels import flash_attention_int as _pallas_int    # noqa: F401
from repro.kernels import flash_decode as _pallas_decode        # noqa: F401
from repro.kernels import ring_attention as _pallas_ring        # noqa: F401
from . import flash as _flash                                   # noqa: F401
from .layers import (Params, apply_rope, layernorm, linear, linear_init,
                     rmsnorm, rmsnorm_init, yarn_mscale)


class AttnSpec(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    softmax_impl: str = "float"
    causal: bool = True
    use_rope: bool = True     # Jamba attends without positional encoding
    # auto|naive|flash|flash_pallas|flash_pallas_int|flash_ring
    attn_impl: str = "auto"
    # mesh axis the sequence-parallel ring rotates over ("" = ring off):
    # opts 'auto' into resolving flash_ring when the ambient mesh shards
    # the KV sequence dim over this axis
    ring_axis: str = ""
    # eps for the qk-norm rmsnorms — MUST carry cfg.norm_eps (the spec
    # builders thread it; norms themselves take eps with no default)
    norm_eps: float = 1e-6


class MLASpec(NamedTuple):
    d_model: int
    n_heads: int
    q_lora_rank: int      # 0 = full-rank q projection
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    softmax_impl: str = "float"
    attn_impl: str = "auto"
    ring_axis: str = ""
    # eps for the q/kv latent rmsnorms — carries cfg.norm_eps
    norm_eps: float = 1e-6
    # YaRN scaling of the rope key (configs.base.YarnCfg) or None
    yarn: object = None


# ---------------- shared core ----------------

def _naive_sdpa(q, k, v, *, q_pos, kv_valid, causal=True,
                scale: float | None = None, softmax_impl: str = "float",
                ring_axis: str = ""):
    """Materialized-scores attention (the short-T / dual-mode path)."""
    b, s_q, t = q.shape[0], q.shape[1], k.shape[1]
    scale = (1.0 / q.shape[-1] ** 0.5) if scale is None else scale
    # accumulate QK^T in f32 with the scale folded into q BEFORE the dot,
    # exactly like the blocked paths — accumulating in the input dtype and
    # casting after made bf16 naive attention diverge from flash
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("bskgh,btkh->bkgst", qf, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    t_pos = jnp.arange(t)[None, :]                          # (1,T) cache idx
    mask = kv_valid[:, None, :]                             # (B,1,T)
    if causal:
        mask = mask & (t_pos[:, None, :] <= q_pos[:, :, None])  # (B,S,T)
    else:
        mask = jnp.broadcast_to(mask, (b, s_q, t))
    scores = jnp.where(mask[:, None, None, :, :], scores, dp.MASK_VALUE)
    probs = dispatch.get_softmax(softmax_impl)(scores).astype(v.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", probs, v)


dispatch.register_attention(
    "naive",
    lambda q, k, v, *, q_pos, kv_valid, causal, scale,
    softmax_impl="float", ring_axis="": _naive_sdpa(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal, scale=scale,
        softmax_impl=softmax_impl),
    # whole-row scores through get_softmax: every registered softmax
    # mode is honored verbatim; a plain einsum graph, so XLA shards it
    # cleanly against a sequence-sharded KV cache (mesh_safe)
    modes=("float", "dualmode", "dualmode_snap"), grad=True,
    mesh_safe=True, note="whole-row scores; honors any softmax_impl")


def _sdpa(q, k, v, *, q_pos, kv_valid, softmax_impl, causal=True,
          scale: float | None = None, attn_impl: str = "auto",
          ring_axis: str = ""):
    """q: (B,S,K,G,h)  k/v: (B,T,K,hk)/(B,T,K,hv)  q_pos: (B,S)
    kv_valid: (B,T) bool.

    Returns (B,S,K,G,hv).  Causality: kv position t attends iff
    kv_valid[t] and (not causal or t_pos <= q_pos).  kv positions are
    their cache indices (prefill writes at [0..S), decode appends).

    Dispatch goes through the kernel registry (kernels/dispatch.py):
    'auto' streams KV through the blocked online-softmax path when the
    (S,T) score tile is too large to materialize (models/flash.py, or the
    Pallas kernel with attn_impl='flash_pallas') — same log-domain
    arithmetic as the paper's unit, in streaming form.  Resolution is
    softmax-aware: softmax_impl='dualmode' runs the bit-accurate unit
    whole-row on the naive path (short T: encoder blocks), through the
    snapped one-sweep int kernel (attn_impl='flash_pallas_int') when
    streamed, the int split-KV path inside 'flash_decode' at decode
    shapes, and the int monoid ring under a mesh — it is never silently
    dropped to the float datapath on ANY phase.
    """
    s_q, t = q.shape[1], k.shape[1]
    impl = dispatch.resolve_attention(attn_impl, s_q, t,
                                      softmax_impl=softmax_impl,
                                      ring_axis=ring_axis)
    return dispatch.get_attention(impl)(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal,
        scale=scale, softmax_impl=softmax_impl, ring_axis=ring_axis)


def _sdpa_paged(q, k_pool, v_pool, *, block_tables, layer, q_pos,
                kv_valid, softmax_impl, causal=True,
                scale: float | None = None, attn_impl: str = "auto",
                ring_axis: str = ""):
    """The paged twin of :func:`_sdpa`: K/V live in the stacked
    lane-dense pools (L, N, bs, K*h) addressed through (B, max_blocks)
    block tables, read at layer ``layer``.

    Resolution is the SAME dense rule at the logical cache extent —
    paged changes the memory layout, never the numerics pick.  When the
    resolved impl has a block-table native mode in the paged registry
    (flash_decode's scalar-prefetch gather) the whole stacked pools and
    the layer index go to the kernel untouched; otherwise this layer's
    blocks of the rows' tables are gathered dense once and the dense impl
    runs — identical words either way, the gather is pure data movement.
    """
    b, s_q, kh = q.shape[:3]
    t = block_tables.shape[1] * k_pool.shape[2]
    impl = dispatch.resolve_attention(attn_impl, s_q, t,
                                      softmax_impl=softmax_impl,
                                      ring_axis=ring_axis)
    fn = dispatch.get_paged_attention(impl) if s_q == 1 else None
    if fn is not None:
        return fn(q, k_pool, v_pool, block_tables=block_tables, layer=layer,
                  q_pos=q_pos, kv_valid=kv_valid, causal=causal, scale=scale,
                  softmax_impl=softmax_impl, ring_axis=ring_axis)
    k = paged_gather(k_pool, block_tables, layer).reshape(b, t, kh, -1)
    v = paged_gather(v_pool, block_tables, layer).reshape(b, t, kh, -1)
    return dispatch.get_attention(impl)(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=causal, scale=scale,
        softmax_impl=softmax_impl, ring_axis=ring_axis)


def paged_write(pool, new, pos, block_tables, layer):
    """Scatter ``new`` (B,S,...) into layer ``layer`` of the stacked
    lane-dense pool (L, N, bs, W) at logical offset ``pos`` through each
    row's block table; each new row's trailing dims flatten to the W
    lanes (K*h for attention K/V).

    Logical position p of row b lands in pool block
    ``block_tables[b, p // bs]`` at offset ``p % bs``.  Positions past
    the table's extent — and table entries that ARE the sentinel — clamp
    into sentinel block 0, which is never referenced by a valid key, so
    pad rows scatter harmlessly instead of corrupting live blocks.
    ``pos`` may be scalar or (B,), same contract as :func:`_write_seq`.
    Only the B*S rows are written: inside the engine's layer scan, where
    the pool is the carry, the scatter updates the buffer in place.
    """
    bs = pool.shape[2]
    b, sl = new.shape[:2]
    nblk = block_tables.shape[1]
    off0 = pos[:, None] if jnp.ndim(pos) else pos
    logpos = jnp.broadcast_to(off0 + jnp.arange(sl)[None, :], (b, sl))
    blk, off = logpos // bs, logpos % bs
    phys = jnp.take_along_axis(block_tables, jnp.clip(blk, 0, nblk - 1),
                               axis=1)
    phys = jnp.where((blk >= 0) & (blk < nblk), phys, 0)
    rows = new.astype(pool.dtype).reshape(b * sl, pool.shape[3])
    return pool.at[layer, phys.reshape(-1), off.reshape(-1)].set(rows)


def paged_gather(pool, block_tables, layer):
    """Materialize the dense (B, max_blocks*bs, W) view of layer
    ``layer`` of a stacked (L, N, bs, W) paged pool — only the rows'
    table blocks are read.  The fallback for impls without a native
    block-table mode (and the whole story for MLA, whose latent must
    expand densely anyway before attention)."""
    b, nblk = block_tables.shape
    dense = pool[layer, block_tables]          # (B, nblk, bs, W)
    return dense.reshape(b, nblk * pool.shape[2], pool.shape[3])


def _write_seq(buf, new, pos):
    """Write `new` (B,S,...) into `buf` (B,Smax,...) at offset `pos`.

    pos may be a scalar (lockstep prefill/decode) or a (B,) vector
    (continuous batching: every slot is at its own depth)."""
    new = new.astype(buf.dtype)
    if jnp.ndim(pos) == 0:
        idx = (0, pos) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, new, idx)
    def row(b_, n_, p_):
        return jax.lax.dynamic_update_slice(
            b_, n_, (p_,) + (0,) * (b_.ndim - 1))
    return jax.vmap(row)(buf, new, pos)


def _kv_valid_mask(t: int, pos, sl: int, b: int):
    """(B,T) validity: cache rows [0, pos+sl) hold data."""
    t_idx = jnp.arange(t)[None, :]
    end = (pos + sl if jnp.ndim(pos) == 0 else pos[:, None] + sl)
    return jnp.broadcast_to(t_idx < end, (b, t))


def _update_cache(cache, k_new, v_new, pos):
    """Write (B,S,K,h) at sequence offset pos into (B,Smax,K,h) buffers."""
    return {"k": _write_seq(cache["k"], k_new, pos),
            "v": _write_seq(cache["v"], v_new, pos)}


# ---------------- GQA ----------------

def gqa_init(key, s: AttnSpec, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p = {"wq": linear_init(ks[0], s.d_model, s.n_heads * s.head_dim, dtype,
                           bias=s.qkv_bias),
         "wk": linear_init(ks[1], s.d_model, s.n_kv_heads * s.head_dim, dtype,
                           bias=s.qkv_bias),
         "wv": linear_init(ks[2], s.d_model, s.n_kv_heads * s.head_dim, dtype,
                           bias=s.qkv_bias),
         "wo": linear_init(ks[3], s.n_heads * s.head_dim, s.d_model, dtype)}
    if s.qk_norm:
        p["qn"] = rmsnorm_init(s.head_dim, dtype)
        p["kn"] = rmsnorm_init(s.head_dim, dtype)
    return p


def gqa_cache_init(s: AttnSpec, batch: int, max_seq: int, dtype) -> Params:
    shape = (batch, max_seq, s.n_kv_heads, s.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_apply(p: Params, s: AttnSpec, x, *, positions, cache=None, pos=0,
              paged=None, layer=0, prenorm=None):
    """x: (B,S,d).  If cache given: write new kv at `pos`, attend over cache.
    Returns (out, new_cache_or_None).

    ``paged`` (B, max_blocks) int32 block tables switches the cache from
    contiguous (B, Smax, K, h) rows to the stacked lane-dense pools
    (L, N, bs, K*h), used at layer ``layer``: writes scatter through the
    table, attention runs :func:`_sdpa_paged`.

    ``prenorm=(norm_params, kind, eps, provider)`` hands this sublayer
    its own input norm (the block's norm1): with a fused provider and
    bias-free projections the norm->QKV seam runs as ONE Pallas kernel
    over the concatenated [wq|wk|wv] panel (kernels/fused_norm
    .norm_linear); otherwise the dense norm applies here and the three
    projections proceed unchanged."""
    b, sl, _ = x.shape
    g = s.n_heads // s.n_kv_heads
    fused_qkv = None
    if prenorm is not None:
        np_, kind, eps, nprov = prenorm
        if nprov is not None and not s.qkv_bias:
            w_cat = jnp.concatenate(
                [p["wq"]["w"], p["wk"]["w"], p["wv"]["w"]], axis=1)
            fused_qkv = nprov["norm_linear"](x, np_["g"], np_.get("b"),
                                             w_cat, kind=kind, eps=eps)
        else:
            x = (rmsnorm if kind == "rms" else layernorm)(np_, x, eps)
    if fused_qkv is not None:
        nq = s.n_heads * s.head_dim
        nk = s.n_kv_heads * s.head_dim
        q = fused_qkv[..., :nq].reshape(b, sl, s.n_heads, s.head_dim)
        k = fused_qkv[..., nq:nq + nk].reshape(b, sl, s.n_kv_heads,
                                               s.head_dim)
        v = fused_qkv[..., nq + nk:].reshape(b, sl, s.n_kv_heads,
                                             s.head_dim)
    else:
        q = linear(p["wq"], x).reshape(b, sl, s.n_heads, s.head_dim)
        k = linear(p["wk"], x).reshape(b, sl, s.n_kv_heads, s.head_dim)
        v = linear(p["wv"], x).reshape(b, sl, s.n_kv_heads, s.head_dim)
    if s.qk_norm:
        q = rmsnorm(p["qn"], q, s.norm_eps)
        k = rmsnorm(p["kn"], k, s.norm_eps)
    if s.use_rope:
        q = apply_rope(q, positions, s.rope_theta)
        k = apply_rope(k, positions, s.rope_theta)
    if paged is not None:
        cache = {"k": paged_write(cache["k"], k, pos, paged, layer),
                 "v": paged_write(cache["v"], v, pos, paged, layer)}
        t = paged.shape[1] * cache["k"].shape[2]
        kv_valid = _kv_valid_mask(t, pos, sl, b)
        qg = q.reshape(b, sl, s.n_kv_heads, g, s.head_dim)
        o = _sdpa_paged(qg, cache["k"], cache["v"], block_tables=paged,
                        layer=layer, q_pos=positions, kv_valid=kv_valid,
                        softmax_impl=s.softmax_impl, causal=s.causal,
                        attn_impl=s.attn_impl, ring_axis=s.ring_axis)
        o = o.reshape(b, sl, s.n_heads * s.head_dim)
        return linear(p["wo"], o), cache
    if cache is not None:
        cache = _update_cache(cache, k, v, pos)
        k_all, v_all = cache["k"], cache["v"]
        kv_valid = _kv_valid_mask(k_all.shape[1], pos, sl, b)
    else:
        k_all, v_all = k, v
        kv_valid = jnp.ones((b, sl), dtype=bool)
    qg = q.reshape(b, sl, s.n_kv_heads, g, s.head_dim)
    o = _sdpa(qg, k_all, v_all, q_pos=positions, kv_valid=kv_valid,
              softmax_impl=s.softmax_impl, causal=s.causal,
              attn_impl=s.attn_impl, ring_axis=s.ring_axis)
    o = o.reshape(b, sl, s.n_heads * s.head_dim)
    return linear(p["wo"], o), cache


# ---------------- MLA (DeepSeek-V2 / MiniCPM3 style) ----------------

def mla_init(key, s: MLASpec, dtype) -> Params:
    ks = jax.random.split(key, 6)
    qk_head = s.nope_dim + s.rope_dim
    p: Params = {}
    if s.q_lora_rank:
        p["wq_a"] = linear_init(ks[0], s.d_model, s.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(s.q_lora_rank, dtype)
        p["wq_b"] = linear_init(ks[1], s.q_lora_rank, s.n_heads * qk_head, dtype)
    else:
        p["wq"] = linear_init(ks[0], s.d_model, s.n_heads * qk_head, dtype)
    p["wkv_a"] = linear_init(ks[2], s.d_model, s.kv_lora_rank + s.rope_dim, dtype)
    p["kv_norm"] = rmsnorm_init(s.kv_lora_rank, dtype)
    p["wkv_b"] = linear_init(ks[3], s.kv_lora_rank,
                             s.n_heads * (s.nope_dim + s.v_dim), dtype)
    p["wo"] = linear_init(ks[4], s.n_heads * s.v_dim, s.d_model, dtype)
    return p


def mla_cache_init(s: MLASpec, batch: int, max_seq: int, dtype) -> Params:
    """MLA caches the *compressed* latent + shared rope key — the memory win."""
    return {"ckv": jnp.zeros((batch, max_seq, s.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_seq, s.rope_dim), dtype)}


def mla_softmax_scale(s: MLASpec) -> float:
    """``1/sqrt(q.k head dim)``, times ``mscale(factor, mscale_all_dim)**2``
    under YaRN (DeepSeek-V2's ``softmax_scale``)."""
    scale = (s.nope_dim + s.rope_dim) ** -0.5
    if s.yarn is not None and s.yarn.mscale_all_dim:
        scale *= yarn_mscale(s.yarn.factor, s.yarn.mscale_all_dim) ** 2
    return scale


def _mla_absorbed(p: Params, s: MLASpec, q_nope, q_rope, ckv, krope, *,
                  q_pos, kv_valid):
    """Latent attention without expanding the latent: q_nope (B,S,H,n),
    q_rope (B,S,H,r), ckv (B,T,rank), krope (B,T,r) -> (B,S,H*v).

    ``wkv_b`` splits per head into a key half W_k (rank, n) and a value
    half W_v (rank, v).  Since q_nope . (c W_k) = (q_nope W_k^T) . c, the
    query goes through W_k once and scores the T latent rows directly;
    the probabilities combine the latent rows and W_v applies once to the
    result.  Scores and softmax are whole-row, as on the naive path, so
    every ``softmax_impl`` applies verbatim; the latent is read once per
    tick instead of expanding to H*(n+v) values per position."""
    b, sl, h = q_nope.shape[:3]
    f32 = jnp.float32
    w = p["wkv_b"]["w"].reshape(s.kv_lora_rank, h, s.nope_dim + s.v_dim)
    w_k, w_v = w[..., :s.nope_dim], w[..., s.nope_dim:]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_k,
                       preferred_element_type=f32).astype(ckv.dtype)
    scores = (jnp.einsum("bshr,btr->bsht", q_lat, ckv,
                         preferred_element_type=f32)
              + jnp.einsum("bshr,btr->bsht", q_rope.astype(krope.dtype),
                           krope, preferred_element_type=f32))
    scores = scores * mla_softmax_scale(s)
    t_pos = jnp.arange(ckv.shape[1])[None, None, :]
    mask = kv_valid[:, None, :] & (t_pos <= q_pos[:, :, None])  # (B,S,T)
    scores = jnp.where(mask[:, :, None], scores, dp.MASK_VALUE)
    probs = dispatch.get_softmax(s.softmax_impl)(scores).astype(ckv.dtype)
    o_lat = jnp.einsum("bsht,btr->bshr", probs, ckv,
                       preferred_element_type=f32).astype(ckv.dtype)
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w_v,
                   preferred_element_type=f32)
    return o.reshape(b, sl, h * s.v_dim)


def mla_apply(p: Params, s: MLASpec, x, *, positions, cache=None, pos=0,
              paged=None, layer=0):
    """Latent attention: the cache holds the normed latent and one shared
    rope key per position.  Prefill expands the latent through ``wkv_b``
    (under the named scope ``mla.expand``) and attends through the shared
    core (``mla.attend``); a decode tick (one query a row, with a cache)
    attends against the latent in the absorbed form
    (:func:`_mla_absorbed`, ``mla.attend``)."""
    b, sl, _ = x.shape
    qk_head = s.nope_dim + s.rope_dim
    if s.q_lora_rank:
        q = linear(p["wq_b"],
                   rmsnorm(p["q_norm"], linear(p["wq_a"], x), s.norm_eps))
    else:
        q = linear(p["wq"], x)
    q = q.reshape(b, sl, s.n_heads, qk_head)
    q_nope, q_rope = q[..., : s.nope_dim], q[..., s.nope_dim:]
    q_rope = apply_rope(q_rope, positions, s.rope_theta, s.yarn)

    kv_a = linear(p["wkv_a"], x)                       # (B,S,kv_lora+rope)
    ckv = rmsnorm(p["kv_norm"], kv_a[..., : s.kv_lora_rank], s.norm_eps)
    k_rope_new = apply_rope(kv_a[..., s.kv_lora_rank:][:, :, None, :],
                            positions, s.rope_theta, s.yarn)[:, :, 0, :]

    if paged is not None:
        # MLA pages the COMPRESSED latent + rope key: this layer's blocks
        # of the rows' tables are gathered dense once, then attention
        # runs as on the contiguous cache.
        cache = {"ckv": paged_write(cache["ckv"], ckv, pos, paged, layer),
                 "krope": paged_write(cache["krope"], k_rope_new, pos,
                                      paged, layer)}
        ckv_all = paged_gather(cache["ckv"], paged, layer)
        krope_all = paged_gather(cache["krope"], paged, layer)
        t = ckv_all.shape[1]
        kv_valid = _kv_valid_mask(t, pos, sl, b)
    elif cache is not None:
        ckv_all = _write_seq(cache["ckv"], ckv, pos)
        krope_all = _write_seq(cache["krope"], k_rope_new, pos)
        cache = {"ckv": ckv_all, "krope": krope_all}
        t = ckv_all.shape[1]
        kv_valid = _kv_valid_mask(t, pos, sl, b)
    else:
        ckv_all, krope_all = ckv, k_rope_new
        t = sl
        kv_valid = jnp.ones((b, sl), dtype=bool)

    if sl == 1 and cache is not None:
        # a decode tick: attend against the latent itself (ROADMAP A5)
        with jax.named_scope("mla.attend"):
            o = _mla_absorbed(p, s, q_nope, q_rope, ckv_all, krope_all,
                              q_pos=positions, kv_valid=kv_valid)
        return linear(p["wo"], o.astype(x.dtype)), cache

    # prefill: expand latent -> per-head k_nope / v over every cached
    # position, then the shared attention core
    with jax.named_scope("mla.expand"):
        kv = linear(p["wkv_b"], ckv_all).reshape(b, t, s.n_heads,
                                                 s.nope_dim + s.v_dim)
        k_nope, v = kv[..., : s.nope_dim], kv[..., s.nope_dim:]

    # route through the shared core: concat rope/nope halves so MLA uses
    # the same naive/flash dispatch as GQA (K=n_heads, G=1)
    with jax.named_scope("mla.attend"):
        q_cat = jnp.concatenate([q_nope, q_rope], axis=-1) \
            .reshape(b, sl, s.n_heads, 1, qk_head)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                      (b, t, s.n_heads, s.rope_dim))],
            axis=-1)
        o = _sdpa(q_cat, k_cat, v, q_pos=positions, kv_valid=kv_valid,
                  softmax_impl=s.softmax_impl, causal=True,
                  scale=mla_softmax_scale(s), attn_impl=s.attn_impl,
                  ring_axis=s.ring_axis)
    o = o.reshape(b, sl, s.n_heads * s.v_dim)
    return linear(p["wo"], o), cache


# ---------------- cross attention (VLM / enc-dec) ----------------

def cross_init(key, s: AttnSpec, dtype) -> Params:
    ks = jax.random.split(key, 4)
    return {"wq": linear_init(ks[0], s.d_model, s.n_heads * s.head_dim, dtype),
            "wk": linear_init(ks[1], s.d_model, s.n_kv_heads * s.head_dim, dtype),
            "wv": linear_init(ks[2], s.d_model, s.n_kv_heads * s.head_dim, dtype),
            "wo": linear_init(ks[3], s.n_heads * s.head_dim, s.d_model, dtype)}


def cross_kv(p: Params, s: AttnSpec, enc):
    """Precompute cross K/V from encoder states (prefill-time, cached)."""
    b, t, _ = enc.shape
    k = linear(p["wk"], enc).reshape(b, t, s.n_kv_heads, s.head_dim)
    v = linear(p["wv"], enc).reshape(b, t, s.n_kv_heads, s.head_dim)
    return {"k": k, "v": v}


def cross_apply(p: Params, s: AttnSpec, x, kv: Params):
    b, sl, _ = x.shape
    g = s.n_heads // s.n_kv_heads
    q = linear(p["wq"], x).reshape(b, sl, s.n_kv_heads, g, s.head_dim)
    t = kv["k"].shape[1]
    valid = jnp.ones((b, t), dtype=bool)
    o = _sdpa(q, kv["k"], kv["v"], q_pos=jnp.zeros((b, sl), jnp.int32),
              kv_valid=valid, softmax_impl=s.softmax_impl, causal=False,
              attn_impl=s.attn_impl, ring_axis=s.ring_axis)
    return linear(p["wo"], o.reshape(b, sl, s.n_heads * s.head_dim))
