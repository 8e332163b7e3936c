"""Plain float32 reference of a dense decoder: Qwen1.5 / Qwen2 as
published (hf:Qwen/Qwen1.5-0.5B, ``Qwen2ForCausalLM``).

Per layer, on the residual stream ``x``::

    h = RMSNorm(x) * g1                    eps = rms_norm_eps
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (QKV bias)
    q, k = RoPE(q), RoPE(k)                rotate-half, theta = rope_theta
    x = x + softmax(q k^T / sqrt(hd) + causal mask) v Wo
    h = RMSNorm(x) * g2
    x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = RMSNorm(x) * gf @ E^T         tied embeddings

Written in ``jax.numpy``, every matmul at ``Precision.HIGHEST`` (a TPU
otherwise rounds float32 operands to bfloat16), with no kernels, cache
or batching: each sequence runs whole, its attention in blocks of query
rows so that it fits.  It imports nothing of the program.  Departures
from the published model: none in the mathematics; the weights are
random from the seed (normal, 1/sqrt(fan-in) for projections, 0.02 for
the embedding, gains and biases random around 1 and 0 so that a program
that dropped either would show).

``quant='fp8'`` is the control: every matmul operand is rounded to
float8 e4m3 (per-tensor scale for weights, per-row for activations)
before the float32 product, the precision step below the bfloat16 the
configuration serves in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CONTROL = "fp8"
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "layers": cfg["num_hidden_layers"], "heads": h,
            "kv_heads": cfg["num_key_value_heads"], "head_dim": d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Random weights from ``key``, stacked over layers."""
    m = dims(cfg)
    d, L, hq, hk, f, v = (m["d"], m["layers"], m["heads"] * m["head_dim"],
                          m["kv_heads"] * m["head_dim"], m["ff"], m["vocab"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

    return {
        "embed": normal((v, d), 0.02),
        "final_norm": gain((d,)),
        "layers": {
            "ln1": gain((L, d)), "ln2": gain((L, d)),
            "wq": normal((L, d, hq), d ** -0.5), "bq": normal((L, hq), 0.1),
            "wk": normal((L, d, hk), d ** -0.5), "bk": normal((L, hk), 0.1),
            "wv": normal((L, d, hk), d ** -0.5), "bv": normal((L, hk), 0.1),
            "wo": normal((L, hq, d), hq ** -0.5),
            "wg": normal((L, d, f), d ** -0.5),
            "wu": normal((L, d, f), d ** -0.5),
            "wd": normal((L, f, d), f ** -0.5),
        },
    }


def n_active(cfg: dict) -> dict:
    """Parameters a token passes through in matmuls: the blocks' every
    projection (``body``) and the tied embedding as the output ``head``,
    which a prompt token skips unless its logits are read."""
    m = dims(cfg)
    hq, hk = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    per_layer = m["d"] * (hq + 2 * hk) + hq * m["d"] + 3 * m["d"] * m["ff"]
    return {"body": float(m["layers"] * per_layer),
            "head": float(m["vocab"] * m["d"])}


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


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, quant):
    """Causal attention of q (S, H, hd) over k, v (S, K, hd), in blocks
    of query rows."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kt = jnp.transpose(k, (1, 2, 0))                        # (H, hd, S)
    vh = jnp.transpose(v, (1, 0, 2))                        # (H, S, hd)
    keys = jnp.arange(s)
    qblk = min(Q_BLOCK, s)

    def block(i):
        rows = i * qblk + jnp.arange(qblk)
        qb = jax.lax.dynamic_slice_in_dim(q, i * qblk, qblk, 0)
        qb = jnp.transpose(qb, (1, 0, 2)) / jnp.sqrt(jnp.float32(hd))
        sc = _mm(qb, kt, quant, "hqd,hds->hqs")
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = _mm(p, vh, quant, "hqs,hsd->hqd")
        return jnp.transpose(o, (1, 0, 2))                  # (Qb, H, hd)

    out = jax.lax.map(block, jnp.arange(s // qblk))
    return out.reshape(s, h * hd)


def hidden(w: dict, cfg: dict, tokens, quant: str = "none"):
    """Final-norm hidden states (S, d) of one sequence; S at most Q_BLOCK
    or a multiple of it (pad the tail: causality keeps it from the real
    rows)."""
    m = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w["layers"])

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = (_mm(h, p["wq"], quant) + p["bq"]).reshape(
            s, m["heads"], m["head_dim"])
        k = (_mm(h, p["wk"], quant) + p["bk"]).reshape(
            s, m["kv_heads"], m["head_dim"])
        v = (_mm(h, p["wv"], quant) + p["bv"]).reshape(
            s, m["kv_heads"], m["head_dim"])
        o = _attend(_rope(q, pos, theta), _rope(k, pos, theta), v, quant)
        x = x + _mm(o, p["wo"], quant)
        h = _rms(x, p["ln2"], eps)
        a = jax.nn.silu(_mm(h, p["wg"], quant)) * _mm(h, p["wu"], quant)
        return x + _mm(a, p["wd"], quant), None

    x, _ = jax.lax.scan(layer, x, f32)
    return _rms(x, w["final_norm"].astype(jnp.float32), eps)


def logits_at(w: dict, cfg: dict, tokens, rows, quant: str = "none"):
    """Logits (R, vocab) at positions ``rows`` of one sequence."""
    h = hidden(w, cfg, tokens, quant)[rows]
    return _mm(h, w["embed"].T, quant)
