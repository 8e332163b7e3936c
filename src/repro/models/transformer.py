"""Unified transformer stack: every assigned architecture runs through this
one scan-over-periods decoder (plus an encoder stack for enc-dec models).

The repeating unit is `cfg.pattern` (a tuple of LayerSpec); parameters for
the `n_periods` repetitions are stacked on a leading axis and consumed by
`jax.lax.scan`, which keeps HLO size O(period) instead of O(layers) — this
is what makes 62-layer MiniCPM3 / 40-layer Qwen3 lower-and-compile fast for
the 80-cell dry-run matrix.

Modes:
  train   — no caches, full causal (or bidirectional for encoders)
  prefill — writes KV/state caches from position 0, returns caches
  decode  — consumes one new token per call at traced position `pos`
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels import dispatch
from .attention import (AttnSpec, MLASpec, cross_apply, cross_init, cross_kv,
                        gqa_apply, gqa_cache_init, gqa_init, mla_apply,
                        mla_cache_init, mla_init)
from .layers import (Params, embed_init, linear_init, make_norm, mlp,
                     mlp_init, sinusoidal_pos_emb)
from .mamba import MambaSpec, mamba_apply, mamba_init, mamba_state_init
from .moe import MoESpec, moe_apply, moe_init
from .rwkv import (RWKVSpec, rwkv_channel_mix, rwkv_cm_init, rwkv_state_init,
                   rwkv_time_mix, rwkv_tm_init)


# ---------------- spec builders ----------------

def attn_spec(cfg: ModelConfig, causal: bool | None = None) -> AttnSpec:
    return AttnSpec(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    qk_norm=cfg.qk_norm, qkv_bias=cfg.qkv_bias,
                    rope_theta=cfg.rope_theta, softmax_impl=cfg.softmax_impl,
                    causal=cfg.causal if causal is None else causal,
                    use_rope=cfg.use_rope, attn_impl=cfg.attn_impl,
                    ring_axis=cfg.ring_axis, norm_eps=cfg.norm_eps)


def mla_spec(cfg: ModelConfig) -> MLASpec:
    m = cfg.mla
    return MLASpec(cfg.d_model, cfg.n_heads, m.q_lora_rank, m.kv_lora_rank,
                   m.nope_dim, m.rope_dim, m.v_dim,
                   rope_theta=cfg.rope_theta, softmax_impl=cfg.softmax_impl,
                   attn_impl=cfg.attn_impl, ring_axis=cfg.ring_axis,
                   norm_eps=cfg.norm_eps, yarn=cfg.rope_yarn)


def mamba_spec(cfg: ModelConfig) -> MambaSpec:
    m = cfg.mamba
    return MambaSpec(cfg.d_model, m.d_inner, m.d_state, m.d_conv, m.dt_rank)


def rwkv_spec(cfg: ModelConfig) -> RWKVSpec:
    return RWKVSpec(cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.rwkv_lora_r)


def moe_spec(cfg: ModelConfig) -> MoESpec:
    m = cfg.moe
    return MoESpec(cfg.d_model, m.d_ff, m.n_experts, m.top_k, m.n_shared,
                   m.capacity_factor, cfg.activation, cfg.ffn_impl,
                   cfg.moe_dispatch, ep_pad=m.ep_pad,
                   norm_topk_prob=m.norm_topk_prob,
                   routed_scale=m.routed_scale, router_f32=m.router_f32,
                   first_held=m.first_held, n_held=m.n_held)


# ---------------- block ----------------

def block_init(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> Params:
    norm_init, _ = make_norm(cfg.norm)
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": norm_init(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["mixer"] = gqa_init(ks[0], attn_spec(cfg), dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla_init(ks[0], mla_spec(cfg), dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_init(ks[0], mamba_spec(cfg), dtype)
    elif spec.mixer == "rwkv":
        p["mixer"] = rwkv_tm_init(ks[0], rwkv_spec(cfg), dtype)
    elif spec.mixer != "none":
        raise ValueError(spec.mixer)
    if spec.cross:
        p["cross_norm"] = norm_init(cfg.d_model, dtype)
        p["cross"] = cross_init(ks[1], attn_spec(cfg, causal=False), dtype)
        p["cross_gate"] = jnp.zeros((), dtype)     # tanh-gated (llama-vision)
    if spec.ffn != "none":
        p["norm2"] = norm_init(cfg.d_model, dtype)
    if spec.ffn == "mlp":
        p["ffn"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype,
                            gated=cfg.gated_mlp)
    elif spec.ffn == "moe":
        p["ffn"] = moe_init(ks[2], moe_spec(cfg), dtype)
    elif spec.ffn == "rwkv_cm":
        p["ffn"] = rwkv_cm_init(ks[2], rwkv_spec(cfg), dtype)
    elif spec.ffn != "none":
        raise ValueError(spec.ffn)
    return p


def block_cache_init(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int, dtype) -> Params:
    c: Params = {}
    if spec.mixer == "attn":
        c["kv"] = gqa_cache_init(attn_spec(cfg), batch, max_seq, dtype)
    elif spec.mixer == "mla":
        c["kv"] = mla_cache_init(mla_spec(cfg), batch, max_seq, dtype)
    elif spec.mixer == "mamba":
        c["state"] = mamba_state_init(mamba_spec(cfg), batch, dtype)
    elif spec.mixer == "rwkv":
        c["state"] = rwkv_state_init(rwkv_spec(cfg), batch, dtype)
    if spec.cross:
        n_ctx = cfg.n_img_tokens or cfg.n_frames
        shape = (batch, n_ctx, cfg.n_kv_heads, cfg.hd)
        c["cross_kv"] = {"k": jnp.zeros(shape, dtype),
                         "v": jnp.zeros(shape, dtype)}
    return c


class Ctx(NamedTuple):
    positions: Any            # (B, S) absolute positions
    pos: Any                  # scalar: cache write offset
    cross_src: Any = None     # (B, T_ctx, d) encoder/image states, or None
    cached: bool = False      # prefill/decode (threads caches)
    # Megatron-style sequence parallelism: the residual stream lives
    # S-sharded over 'model' (pin_sp); sublayer inputs are gathered to
    # full-S so tensor-parallel weights apply cleanly (pin_full).  GSPMD
    # realizes the pair as the classic all-gather/reduce-scatter schedule.
    pin_sp: Any = None        # callable | None: (dp, 'model', None)
    pin_full: Any = None      # callable | None: (dp, None, None)
    moe_axes: Any = None      # (dp_axis, ep_axis) for MoE dispatch pins
    # paged KV: (B, max_blocks) int32 block tables, or None (contiguous).
    # When set, attention caches are (L, N, bs, W) pools stacked over
    # layers, shared across requests; writes/reads route through the
    # table (serve engine) at layer index `layer` of the stack.
    paged: Any = None
    layer: Any = 0            # int32 scalar: this block's pool layer


def _pin(ctx: Ctx, x, kind: str):
    fn = ctx.pin_sp if kind == "sp" else ctx.pin_full
    return fn(x) if fn is not None else x


def block_apply(p: Params, cfg: ModelConfig, spec: LayerSpec, x, cache,
                ctx: Ctx):
    """-> (x, new_cache, aux); aux is the MoE layer's (``moe_apply``):
    the load-balance loss in train mode, the rows its held experts
    computed when ``ctx.cached``; 0.0 for any other layer."""
    _, norm = make_norm(cfg.norm)
    new_cache: Params = {}
    aux = jnp.zeros((), jnp.float32)
    b = x.shape[0]

    # Fused norm seams (cfg.norm_impl -> kernels/fused_norm.py): gated to
    # pins-off — the Megatron inner pins must observe the residual stream
    # and the normed stream as SEPARATE shardable values, which is exactly
    # what fusing removes.  With a provider:
    #   * mixer 'attn': norm1 fuses into the QKV projection (prologue),
    #   * residual-add + norm2 fuse into one epilogue after the mixer
    #     (covers mlp AND moe — the epilogue is activation-independent),
    #   * a cross-less 'none'-mixer block fuses norm2 into the gate/up
    #     prologue inside mlp() instead.
    # The FFN-residual + NEXT block's norm1 seam is covered by that next
    # block's prologue, so every seam is one HBM round-trip shorter.
    nprov = dispatch.get_norm(dispatch.resolve_norm(cfg.norm_impl))
    fuse = (nprov is not None and ctx.pin_full is None
            and ctx.pin_sp is None)

    o = None
    if spec.mixer == "attn":
        if fuse:
            h, pn = x, (p["norm1"], cfg.norm, cfg.norm_eps, nprov)
        else:
            h, pn = _pin(ctx, norm(p["norm1"], x, cfg.norm_eps), "full"), None
        o, kv = gqa_apply(p["mixer"], attn_spec(cfg), h,
                          positions=ctx.positions,
                          cache=cache.get("kv") if ctx.cached else None,
                          pos=ctx.pos, paged=ctx.paged, layer=ctx.layer,
                          prenorm=pn)
        if ctx.cached:
            new_cache["kv"] = kv
    elif spec.mixer == "mla":
        h = _pin(ctx, norm(p["norm1"], x, cfg.norm_eps), "full")
        o, kv = mla_apply(p["mixer"], mla_spec(cfg), h,
                          positions=ctx.positions,
                          cache=cache.get("kv") if ctx.cached else None,
                          pos=ctx.pos, paged=ctx.paged, layer=ctx.layer)
        if ctx.cached:
            new_cache["kv"] = kv
    elif spec.mixer == "mamba":
        h = _pin(ctx, norm(p["norm1"], x, cfg.norm_eps), "full")
        st = (cache["state"] if ctx.cached
              else mamba_state_init(mamba_spec(cfg), b, x.dtype))
        # NOTE: axes-pins measured NEUTRAL-to-negative here (EXPERIMENTS.md
        # §Perf jamba iterations) — GSPMD's own choice wins; knob retained.
        o, st = mamba_apply(p["mixer"], mamba_spec(cfg), h, state=st)
        if ctx.cached:
            new_cache["state"] = st
    elif spec.mixer == "rwkv":
        h = _pin(ctx, norm(p["norm1"], x, cfg.norm_eps), "full")
        st = (cache["state"] if ctx.cached
              else rwkv_state_init(rwkv_spec(cfg), b, x.dtype))
        o, tm_st = rwkv_time_mix(p["mixer"], rwkv_spec(cfg), h, state=st)
        if ctx.cached:
            new_cache["state"] = {**st, **tm_st}

    # mixer residual add — fused with norm2 when the next consumer is the
    # FFN norm (no cross sublayer in between)
    h_ffn = None
    if o is not None:
        if fuse and spec.ffn != "none" and not spec.cross:
            x, h_ffn = nprov["residual_norm"](
                x, o, p["norm2"]["g"], p["norm2"].get("b"),
                kind=cfg.norm, eps=cfg.norm_eps)
        else:
            x = x + o
        x = _pin(ctx, x, "sp")

    if spec.cross:
        h = _pin(ctx, norm(p["cross_norm"], x, cfg.norm_eps), "full")
        if ctx.cross_src is not None:
            ckv = cross_kv(p["cross"], attn_spec(cfg, causal=False),
                           ctx.cross_src)
        else:
            ckv = cache["cross_kv"]
        if ctx.cached:
            new_cache["cross_kv"] = jax.tree.map(
                lambda a, b_: a.astype(b_.dtype), ckv, cache["cross_kv"])
        o = cross_apply(p["cross"], attn_spec(cfg, causal=False), h, ckv)
        x = _pin(ctx, x + jnp.tanh(p["cross_gate"]) * o, "sp")

    if spec.ffn != "none":
        if h_ffn is None and spec.ffn == "mlp" and fuse:
            # no epilogue produced h (mixer 'none' or a cross sublayer
            # re-touched x): fuse norm2 into the gate/up prologue instead
            x = x + mlp(p["ffn"], x, cfg.activation, impl=cfg.ffn_impl,
                        prenorm=(p["norm2"], cfg.norm, cfg.norm_eps),
                        norm_impl=cfg.norm_impl)
        else:
            h = (h_ffn if h_ffn is not None
                 else _pin(ctx, norm(p["norm2"], x, cfg.norm_eps), "full"))
            if spec.ffn == "mlp":
                x = x + mlp(p["ffn"], h, cfg.activation, impl=cfg.ffn_impl)
            elif spec.ffn == "moe":
                o, aux = moe_apply(p["ffn"], moe_spec(cfg), h,
                                   dropless=ctx.cached, axes=ctx.moe_axes)
                x = x + o
            elif spec.ffn == "rwkv_cm":
                st = (cache["state"] if ctx.cached
                      else rwkv_state_init(rwkv_spec(cfg), b, x.dtype))
                o, cm_st = rwkv_channel_mix(p["ffn"], rwkv_spec(cfg), h,
                                            state=st)
                if ctx.cached:
                    new_cache["state"] = {**new_cache.get("state", st),
                                          **cm_st}
                x = x + o
        x = _pin(ctx, x, "sp")
    return x, new_cache, aux


# ---------------- full model ----------------

def init_lm(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    norm_init, _ = make_norm(cfg.norm)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(keys[1], cfg.d_model, cfg.vocab, dtype)
    if cfg.pos_emb == "learned":
        params["pos"] = embed_init(keys[2], min(cfg.max_seq, 1 << 16),
                                   cfg.d_model, dtype)
    if cfg.prefix:
        pk = jax.random.split(keys[3], len(cfg.prefix))
        params["prefix"] = [block_init(pk[i], cfg, s, dtype)
                            for i, s in enumerate(cfg.prefix)]
    period_keys = jax.random.split(keys[4], cfg.n_periods)

    def one_period(k):
        sk = jax.random.split(k, len(cfg.pattern))
        return [block_init(sk[j], cfg, s, dtype)
                for j, s in enumerate(cfg.pattern)]

    params["periods"] = jax.vmap(one_period)(period_keys)
    if cfg.enc_layers:
        ek = jax.random.split(keys[5], cfg.enc_layers)
        enc_spec = LayerSpec(mixer="attn", ffn="mlp")
        params["encoder"] = {
            "blocks": jax.vmap(
                lambda k: block_init(k, _enc_cfg(cfg), enc_spec, dtype))(ek),
            "norm": norm_init(cfg.d_model, dtype),
        }
    return params


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return cfg.replace(causal=False, pattern=(LayerSpec(),), prefix=())


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.float32) -> Params:
    caches: Params = {}
    if cfg.prefix:
        caches["prefix"] = [block_cache_init(cfg, s, batch, max_seq, dtype)
                            for s in cfg.prefix]
    one = [block_cache_init(cfg, s, batch, max_seq, dtype)
           for s in cfg.pattern]
    caches["periods"] = jax.tree.map(
        lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype), one)
    return caches


def paged_supported(cfg: ModelConfig) -> bool:
    """Whether every cached layer of ``cfg`` can live in a paged pool.

    Paged KV covers the attention caches (GQA rows, MLA latents); mixers
    whose state is NOT a per-position sequence (mamba conv/ssm state,
    rwkv time-mix state) and cross-attention context caches have nothing
    to page — those architectures stay on the contiguous engine."""
    specs = tuple(cfg.prefix) + tuple(cfg.pattern)
    return (not cfg.enc_layers and
            all(s.mixer in ("attn", "mla", "none") and not s.cross
                for s in specs))


def _block_paged_cache_init(cfg: ModelConfig, spec: LayerSpec,
                            n_layers: int, num_blocks: int,
                            block_size: int, dtype) -> Params:
    c: Params = {}
    shape = (n_layers, num_blocks, block_size)
    if spec.mixer == "attn":
        s = attn_spec(cfg)
        kv = shape + (s.n_kv_heads * s.head_dim,)
        c["kv"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    elif spec.mixer == "mla":
        m = mla_spec(cfg)
        c["kv"] = {"ckv": jnp.zeros(shape + (m.kv_lora_rank,), dtype),
                   "krope": jnp.zeros(shape + (m.rope_dim,), dtype)}
    elif spec.mixer != "none" or spec.cross:
        raise ValueError(f"mixer {spec.mixer!r} has no paged cache form")
    return c


def init_paged_caches(cfg: ModelConfig, num_blocks: int, block_size: int,
                      dtype=jnp.float32) -> Params:
    """Paged twin of :func:`init_caches`: lane-dense pools stacked over
    layers, (L, N, bs, W).

    Attention K and V pools hold W = K*h lanes per token (MLA: the latent
    and the rope key), so the TPU's default layout is row-major and the
    paged decode kernel reads a (bs, K*h) block as it is stored.  Each
    pattern position stacks its ``n_periods`` layers (a prefix layer is a
    stack of one); ``lm_apply`` carries the stacks through its layer scan
    and hands each block its layer index, so a step writes only its new
    rows in place.  All layers share ONE block table per request
    (allocation is in lockstep across the stack), so the serve engine
    threads a single (B, max_blocks) table through
    ``lm_apply(..., paged=tables)``.  Block 0 of every pool is the write
    sentinel — the allocator never hands it out."""
    if not paged_supported(cfg):
        raise ValueError(
            "paged KV requires attention-only cached layers (no "
            "mamba/rwkv state, no cross-attention, no encoder)")
    caches: Params = {}
    if cfg.prefix:
        caches["prefix"] = [
            _block_paged_cache_init(cfg, s, 1, num_blocks, block_size, dtype)
            for s in cfg.prefix]
    caches["periods"] = [
        _block_paged_cache_init(cfg, s, cfg.n_periods, num_blocks,
                                block_size, dtype)
        for s in cfg.pattern]
    return caches


def encoder_apply(params: Params, cfg: ModelConfig, frames):
    """Whisper-style encoder over stub frame embeddings (B, T, d)."""
    _, norm = make_norm(cfg.norm)
    x = frames + sinusoidal_pos_emb(frames.shape[1], cfg.d_model,
                                    frames.dtype)
    ecfg = _enc_cfg(cfg)
    spec = LayerSpec(mixer="attn", ffn="mlp")
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None, :],
                           frames.shape[:2])
    ctx = Ctx(positions=pos, pos=0)

    def body(x, bp):
        x, _, _ = block_apply(bp, ecfg, spec, x, {}, ctx)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    return norm(params["encoder"]["norm"], x, cfg.norm_eps)


def _best_group(n: int) -> int:
    """Divisor of n nearest sqrt(n) — two-level remat group count."""
    best, target = 1, max(int(n ** 0.5), 1)
    for g in range(1, n + 1):
        if n % g == 0 and abs(g - target) < abs(best - target):
            best = g
    return best


def lm_apply(params: Params, cfg: ModelConfig, tokens, *, pos=0,
             caches: Params | None = None, cross_src=None,
             remat: bool = False, last_pos=None, act_pspec=None,
             return_hidden: bool = False, inner_pins: bool = False,
             remat_mode: str = "period", paged=None):
    """tokens (B,S) -> (logits, new_caches, aux).

    aux          : summed over the MoE layers (0.0 without any): their
                   load-balance loss in train mode; with caches (serving,
                   where there is no loss) the routed rows that landed on
                   the layers' held experts, a float32 count

    caches=None  : train mode (full forward, no state threading)
    caches given : prefill (pos=0, S=seq) or decode (S=1, pos=offset)
    remat        : activation-checkpoint each scan period (train mode) —
                   activations are recomputed in backward, so live memory
                   is O(1 period) instead of O(n_layers)
    last_pos     : optional (B,) positions — compute logits ONLY at these
                   rows (prefill: avoids the (B,S,vocab) logits tensor,
                   which at 32k×150k vocab would dwarf the model itself)
    act_pspec    : optional PartitionSpec pinned onto the (B,S,d) residual
                   stream at every period boundary — sequence parallelism:
                   the remat'd scan carry is stored S/|model|-sharded, and
                   GSPMD all-gathers only transiently inside blocks
    return_hidden: skip the LM head, return final-norm hidden states (the
                   chunked-CE loss applies the head itself)
    paged        : optional (B, max_blocks) int32 block tables — caches
                   are :func:`init_paged_caches` pools and attention
                   writes/reads route through the tables (serve engine's
                   zero-copy admission path); the stacked pools ride the
                   layer scan's carry, not its xs/ys, so each layer
                   updates its rows in place
    """
    _, norm = make_norm(cfg.norm)
    b, sl = tokens.shape
    x = params["embed"][tokens]
    # pos may be scalar (lockstep) or (B,) (continuous batching)
    off = pos if jnp.ndim(pos) == 0 else pos[:, None]
    positions = jnp.broadcast_to(off + jnp.arange(sl)[None, :], (b, sl))
    if cfg.pos_emb == "learned":
        x = x + params["pos"][jnp.clip(positions, 0,
                                       params["pos"].shape[0] - 1)]
    elif cfg.pos_emb == "sinusoid":
        x = x + sinusoidal_pos_emb(sl, cfg.d_model, x.dtype)[None]

    cached = caches is not None
    pin_sp = pin_full = None
    if act_pspec is not None and inner_pins:
        # Megatron-style AG/RS pins inside blocks.  Measured on this
        # toolchain they LOSE to the boundary-only pin (EXPERIMENTS.md
        # §Perf: jamba 153 vs 127 GiB/chip) — kept as an opt-in knob.
        full_spec = type(act_pspec)(act_pspec[0], None, None)
        pin_sp = lambda h: jax.lax.with_sharding_constraint(h, act_pspec)
        pin_full = lambda h: jax.lax.with_sharding_constraint(h, full_spec)
    moe_axes = None
    if act_pspec is not None:
        dp_ax = act_pspec[0]
        in_dp = ("model" in dp_ax) if isinstance(dp_ax, tuple) else \
            (dp_ax == "model")
        if not in_dp:                # 'model' free to serve as the EP axis
            moe_axes = (dp_ax, act_pspec[1] if len(act_pspec) > 1
                        and act_pspec[1] else "model")
    ctx = Ctx(positions=positions, pos=pos, cross_src=cross_src,
              cached=cached, pin_sp=pin_sp, pin_full=pin_full,
              moe_axes=moe_axes, paged=paged)
    pin = ((lambda h: jax.lax.with_sharding_constraint(h, act_pspec))
           if act_pspec is not None else (lambda h: h))
    x = pin(x)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Params = {}

    if cfg.prefix:
        new_caches["prefix"] = []
        for i, spec in enumerate(cfg.prefix):
            c = caches["prefix"][i] if cached else {}
            x, nc, aux = block_apply(params["prefix"][i], cfg, spec, x, c, ctx)
            new_caches["prefix"].append(nc)
            aux_total = aux_total + aux

    if cached and paged is not None:
        def body(carry, xs):
            x, aux_acc, pools = carry
            pp, layer = xs
            lctx = ctx._replace(layer=layer)
            new = []
            for j, spec in enumerate(cfg.pattern):
                x, nc, aux = block_apply(pp[j], cfg, spec, x, pools[j], lctx)
                new.append(nc)
                aux_acc = aux_acc + aux
            return (pin(x), aux_acc, new), None

        (x, aux_total, pools), _ = jax.lax.scan(
            body, (x, aux_total, caches["periods"]),
            (params["periods"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
        new_caches["periods"] = pools
    elif cached:
        def body(carry, xs):
            x, aux_acc = carry
            pp, pc = xs
            ncs = []
            for j, spec in enumerate(cfg.pattern):
                bp = jax.tree.map(lambda a: a, pp[j])
                x, nc, aux = block_apply(bp, cfg, spec, x, pc[j], ctx)
                ncs.append(nc)
                aux_acc = aux_acc + aux
            return (pin(x), aux_acc), ncs

        (x, aux_total), period_caches = jax.lax.scan(
            body, (x, aux_total), (params["periods"], caches["periods"]))
        new_caches["periods"] = period_caches
    else:
        def body(carry, pp):
            x, aux_acc = carry
            for j, spec in enumerate(cfg.pattern):
                x, _, aux = block_apply(pp[j], cfg, spec, x, {}, ctx)
                aux_acc = aux_acc + aux
            return (pin(x), aux_acc), None

        n_p = cfg.n_periods
        g = _best_group(n_p) if remat_mode == "two_level" else 1
        if remat and 1 < g < n_p:
            # two-level (sqrt-L) remat: outer scan saves G boundaries, the
            # inner scan recomputes its P/G periods during backward —
            # stored residual-stream copies drop from P to G + P/G without
            # sequence-sharding the activations (EXPERIMENTS.md §Perf)
            stacked = jax.tree.map(
                lambda a: a.reshape(g, n_p // g, *a.shape[1:]),
                params["periods"])
            inner = jax.checkpoint(body)

            @jax.checkpoint
            def outer(carry, pg):
                c, _ = jax.lax.scan(inner, carry, pg)
                return c, None

            (x, aux_total), _ = jax.lax.scan(outer, (x, aux_total), stacked)
        else:
            if remat:
                body = jax.checkpoint(body)
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                             params["periods"])

    if last_pos is not None:
        x = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)
    x = norm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, (new_caches if cached else None), aux_total
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]["w"]
    return logits, (new_caches if cached else None), aux_total


def lm_head_weight(params: Params, cfg: ModelConfig):
    """(d, vocab) head matrix (transposed embed when tied)."""
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]["w"])
