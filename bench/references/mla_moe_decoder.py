"""Plain float32 reference of a latent-attention, mixture-of-experts
decoder: DeepSeek-V2 as published (hf:deepseek-ai/DeepSeek-V2-Lite,
``DeepseekV2ForCausalLM``), for the experts one chip holds.

Per layer, on the residual stream ``x`` (H heads, no q compression)::

    h = RMSNorm(x) * g1                         eps = rms_norm_eps
    q = h Wq                 (S, H, nope + rope); q_pe = its last rope dims
    c, k_pe = split(h Wkv_a, [kv_lora_rank, rope])   one k_pe for all heads
    k_nope, v = split(RMSNorm(c) * gkv Wkv_b, [nope, v])   per head
    q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)         interleaved pairs, YaRN
    x = x + softmax(q.k * scale + causal mask) v Wo
             scale = (nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2
    h = RMSNorm(x) * g2
    first_k_dense_replace layers:  x = x + (silu(h Wg) * (h Wu)) Wd
    the others:  s = softmax(h Wr)              float32, router_experts outputs
                 w, e = top-k(s)                greedy, num_experts_per_tok
                 w = w / sum(w) if norm_topk_prob; w = w * routed_scaling_factor
                 x = x + sum_j [e_j held] w_j Expert_{e_j}(h) + Shared(h)
    logits = RMSNorm(x) * gf @ W_head           untied head

YaRN (``DeepseekV2YarnRotaryEmbedding``): the frequencies ``1/theta^(2i/d)``
and the same over ``factor`` are blended by a linear ramp between the
dimensions that turn ``beta_fast`` and ``beta_slow`` times in
``original_max_position_embeddings`` positions; cos and sin carry
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, where
``mscale(f, m) = 0.1 m ln f + 1``.  Interleaved rope: the pairs (2i, 2i+1)
are de-interleaved to (i, i + d/2) and rotated by halves, as the published
``apply_rotary_pos_emb`` does.  Shared experts are one SiLU MLP of width
``moe_intermediate_size * n_shared_experts``.

Written in ``jax.numpy``, every matmul at ``Precision.HIGHEST``, with no
kernels, cache or batching: each sequence runs whole, its attention in
blocks of query rows.  It imports nothing of the program.  Departures
from the published model:
- the chip's share of an expert-parallel deployment: the router scores
  all ``assumed.router_experts`` experts, but only experts
  ``[first_held_expert, first_held_expert + n_routed_experts)`` exist here,
  and a token's slots routed elsewhere add nothing (the program is given
  the same share);
- with ``norm_topk_prob`` the published V2 code leaves the scaling
  factor out; here it always multiplies (the factor is 1 in V2-Lite);
- weights are random from the seed (normal, 1/sqrt(fan-in) for
  projections, 0.02 for the embedding, gains 1 + 0.1 N(0, 1)).

``quant='fp8'`` is the control: every matmul operand, the router's too,
is rounded to float8 e4m3 (per-tensor scale for weights, per-row for
activations) before the float32 product, the precision step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

CONTROL = "fp8"
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def dims(cfg: dict) -> dict:
    a = cfg["assumed"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "heads": cfg["num_attention_heads"],
            "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"],
            "eff": cfg["moe_intermediate_size"],
            "shared": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "held": cfg["n_routed_experts"],
            "first": a["first_held_expert"],
            "router": a["router_experts"], "k": cfg["num_experts_per_tok"],
            "vocab": cfg["vocab_size"]}


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Random weights from ``key``: the dense layers and the MoE layers,
    each stacked over its layers."""
    m = dims(cfg)
    d, hq = m["d"], m["heads"] * (m["nope"] + m["rope"])
    kvb, hv = m["heads"] * (m["nope"] + m["v"]), m["heads"] * m["v"]
    ks = iter(jax.random.split(key, 32))

    def normal(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

    def attention(n):
        return {"ln1": gain((n, d)), "ln2": gain((n, d)),
                "wq": normal((n, d, hq), d),
                "wkv_a": normal((n, d, m["rank"] + m["rope"]), d),
                "kv_norm": gain((n, m["rank"])),
                "wkv_b": normal((n, m["rank"], kvb), m["rank"]),
                "wo": normal((n, hv, d), hv)}

    nd, ne = m["dense"], m["layers"] - m["dense"]
    e, f, fs = m["held"], m["eff"], m["shared"]
    dense = {**attention(nd), "wg": normal((nd, d, m["ff"]), d),
             "wu": normal((nd, d, m["ff"]), d),
             "wd": normal((nd, m["ff"], d), m["ff"])}
    moe = {**attention(ne), "router": normal((ne, d, m["router"]), d),
           "eg": normal((ne, e, d, f), d), "eu": normal((ne, e, d, f), d),
           "ed": normal((ne, e, f, d), f),
           "sg": normal((ne, d, fs), d), "su": normal((ne, d, fs), d),
           "sd": normal((ne, fs, d), fs)}
    return {"embed": (jax.random.normal(next(ks), (m["vocab"], d))
                      * 0.02).astype(dtype),
            "final_norm": gain((d,)), "head": normal((d, m["vocab"]), d),
            "dense": dense, "moe": moe}


def n_active(cfg: dict) -> dict:
    """Parameters a token passes through in matmuls on this chip: the
    attention of every layer, the dense layers' MLP, the MoE layers'
    router and shared experts (``body``); the untied ``head``, which a
    prompt token skips unless its logits are read; and one routed
    ``expert``'s parameters, which a token passes once for each of its
    ``top_k`` slots in each of the ``moe_layers`` that lands on a held
    expert."""
    m = dims(cfg)
    d, h = m["d"], m["heads"]
    attn = (d * h * (m["nope"] + m["rope"]) + d * (m["rank"] + m["rope"])
            + m["rank"] * h * (m["nope"] + m["v"]) + h * m["v"] * d)
    moe_layers = m["layers"] - m["dense"]
    body = (m["layers"] * attn + m["dense"] * 3 * d * m["ff"]
            + moe_layers * (3 * d * m["shared"] + d * m["router"]))
    return {"body": float(body), "head": float(m["vocab"] * d),
            "expert": float(3 * d * m["eff"]),
            "moe_layers": float(moe_layers), "top_k": float(m["k"])}


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant, spec="...i,ij->...j"):
    """A product in float32 at full precision; under the control, both
    operands rounded to fp8 first (rows of a, the whole of b)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, None)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict):
    """The published YaRN inverse frequencies (rope dims / 2,)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2,
                                                 dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, cfg):
    """Interleaved-pair RoPE with YaRN; x (S, H, rope), pos (S,)."""
    rs = cfg["rope_scaling"]
    m = (yarn_mscale(float(rs["factor"]), rs["mscale"])
         / yarn_mscale(float(rs["factor"]), rs["mscale_all_dim"]))
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin], -1)


def _attend(q, k, v, scale, quant):
    """Causal attention of q (S, H, hk) over k (S, H, hk), v (S, H, hv),
    in blocks of query rows."""
    s, h, _ = q.shape
    kt = jnp.transpose(k, (1, 2, 0))                        # (H, hk, S)
    vh = jnp.transpose(v, (1, 0, 2))                        # (H, S, hv)
    keys = jnp.arange(s)
    qblk = min(Q_BLOCK, s)

    def block(i):
        rows = i * qblk + jnp.arange(qblk)
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblk, qblk, 0)
        qb = jnp.transpose(qb, (1, 0, 2)) * scale
        sc = _mm(qb, kt, quant, "hqd,hds->hqs")
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = _mm(p, vh, quant, "hqs,hsd->hqd")
        return jnp.transpose(o, (1, 0, 2))                  # (Qb, H, hv)

    out = jax.lax.map(block, jnp.arange(s // qblk))
    return out.reshape(s, -1)


def _mla(x, p, cfg, m, pos, quant):
    s, h = x.shape[0], m["heads"]
    eps = cfg["rms_norm_eps"]
    hn = _rms(x, p["ln1"], eps)
    q = _mm(hn, p["wq"], quant).reshape(s, h, m["nope"] + m["rope"])
    kv_a = _mm(hn, p["wkv_a"], quant)
    c = _rms(kv_a[:, :m["rank"]], p["kv_norm"], eps)
    k_pe = _rope(kv_a[:, None, m["rank"]:], pos, cfg)       # (S, 1, rope)
    kv = _mm(c, p["wkv_b"], quant).reshape(s, h, m["nope"] + m["v"])
    q = jnp.concatenate([q[..., :m["nope"]],
                         _rope(q[..., m["nope"]:], pos, cfg)], -1)
    k = jnp.concatenate([kv[..., :m["nope"]],
                         jnp.broadcast_to(k_pe, (s, h, m["rope"]))], -1)
    o = _attend(q, k, kv[..., m["nope"]:], softmax_scale(cfg), quant)
    return x + _mm(o, p["wo"], quant)


def _glu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _experts(h, p, cfg, m, quant):
    """The held experts' part of the routed MoE output, plus the shared
    experts."""
    scores = jax.nn.softmax(_mm(h, p["router"], quant), axis=-1)
    w, e = jax.lax.top_k(scores, m["k"])                    # greedy
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    held = jnp.arange(m["held"]) + m["first"]
    gate = jnp.sum(jnp.where(e[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)                                  # (S, held)
    y = _mm(jax.nn.silu(_mm(h, p["eg"], quant, "si,eif->esf"))
            * _mm(h, p["eu"], quant, "si,eif->esf"), p["ed"], quant,
            "esf,efd->esd")                                 # (held, S, d)
    return (jnp.einsum("esd,se->sd", y, gate, precision=HI)
            + _glu(h, p["sg"], p["su"], p["sd"], quant))


def hidden(w: dict, cfg: dict, tokens, quant: str = "none"):
    """Final-norm hidden states (S, d) of one sequence; S at most Q_BLOCK
    or a multiple of it (pad the tail: causality keeps it from the real
    rows)."""
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def f32(p):
        return jax.tree.map(lambda a: a.astype(jnp.float32), p)

    def dense(x, p):
        p = f32(p)
        x = _mla(x, p, cfg, m, pos, quant)
        return x + _glu(_rms(x, p["ln2"], eps), p["wg"], p["wu"], p["wd"],
                        quant), None

    def moe(x, p):
        p = f32(p)
        x = _mla(x, p, cfg, m, pos, quant)
        return x + _experts(_rms(x, p["ln2"], eps), p, cfg, m, quant), None

    x, _ = jax.lax.scan(dense, x, w["dense"])
    x, _ = jax.lax.scan(moe, x, w["moe"])
    return _rms(x, w["final_norm"].astype(jnp.float32), eps)


def logits_at(w: dict, cfg: dict, tokens, rows, quant: str = "none"):
    """Logits (R, vocab) at positions ``rows`` of one sequence."""
    h = hidden(w, cfg, tokens, quant)[rows]
    return _mm(h, w["head"], quant)
