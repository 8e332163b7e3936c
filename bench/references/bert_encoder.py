"""Plain float32 reference of the BERT-base encoder as the program builds
it, with GELU through the dual-mode unit, and its training step.

Published BERT-base (Devlin et al. 2019; hf:google-bert/bert-base-uncased)
is a post-LayerNorm encoder with biases on every projection, token-type
embeddings, an embedding LayerNorm and a masked-LM head tied to the
embedding.  The program's ``bert-base`` configuration, which this file
follows, departs from it in these ways, each kept here as the program
runs it:

* pre-LayerNorm blocks with a final LayerNorm (``x + f(LN(x))``);
* no biases on the attention or FFN projections (LayerNorms keep theirs);
* no token-type embeddings, no embedding LayerNorm, no pooler;
* an untied output head ``(d, vocab)`` with no bias or transform;
* rotary position embedding (rotate-half, theta ``rope_theta``) on q
  and k in every layer, on top of the learned absolute positions;
* LayerNorm eps 1e-6 (published 1e-12), no dropout;
* GELU is the paper's Eq. 8 through the int unit (below), where the
  published model uses erf GELU.

The loss is the mean token cross-entropy; the optimizer is AdamW with
global-norm clipping, warmup, and decay on weights only, never on
LayerNorm gains or biases, as the program's optimizer states its rule
(its stacked per-layer gains and biases are 1-D per layer, so they do
not decay here).

**The unit's GELU** is written out here from the paper's description
(Eq. 8 and its fixed-point datapath), not taken from the program: input
z quantized to S5.10 (16 bits, 10 fraction bits, saturating);
k = sqrt(2/pi)(z + 0.044715 z^3) in int32 with the cubic input saturated
at |z| <= 8; sigma(2k) = softmax over the pair [k, -k] in the log domain
(max |k|, t = d log2(e), 2**t as a shift of an eight-piece linear 2**v,
the pair sum's log2 by a leading-one detector and an eight-piece linear
log2(1+f), one more 2**w); GELU = z * sigma, back to S5.10.  The
eight-piece fits are least-squares lines on eight equal segments of
[0, 1), coefficients rounded to Q2.14, as the paper's method states.  It
is int32 ``jax.numpy`` computed from those definitions alone, so it does
not depend on ``core/softmax_unit.py``; a CPU test holds the two to the
same words.  Its gradient is that of the tanh-form GELU (straight-through),
as the program trains it.

Everything is float32, and matmuls run at the precision the
configuration states (``run.matmul_precision``): ``default``, a TPU's
one pass over bfloat16 operands with float32 accumulation, as the
program gets it when it sets none.  ``dt='bf16'`` is the control, the
step below: weights and every activation and matmul operand in bfloat16
(LayerNorm, softmax, the unit's GELU input), with the loss, the float32
master weights and the Adam state as before.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
CONTROL = "bf16"

# ---------------- the unit's GELU, from the paper's definitions --------

IN_FRAC, EXP_FRAC, T_FRAC, COEF_FRAC, LOG2E_FRAC = 10, 14, 16, 14, 12
LOG2E_Q = round(math.log2(math.e) * 2 ** LOG2E_FRAC)
CUBIC_Q = round(0.044715 * 2 ** 16)
SQRT2PI_Q = round(math.sqrt(2 / math.pi) * 2 ** 14)


def _fit8(fn):
    """Least-squares line on each of 8 equal segments of [0, 1), Q2.14."""
    slopes, icpts = [], []
    edges = np.linspace(0.0, 1.0, 9)
    for i in range(8):
        x = np.linspace(edges[i], edges[i + 1], 4096, endpoint=False)
        a, b = np.polyfit(x, fn(x), 1)
        slopes.append(a)
        icpts.append(b)
    q = lambda c: np.round(np.asarray(c) * 2 ** COEF_FRAC).astype(np.int32)
    return q(slopes), q(icpts)


EXP2_FIT = _fit8(np.exp2)                       # 2**v, v in [0, 1)
LOG2_FIT = _fit8(lambda f: np.log2(1.0 + f))    # log2(1 + f)


def _pwl(frac, fit, out_frac):
    """Segment = top 3 of the 16 fraction bits; a*frac + b at 2**-out.
    The coefficients are picked by a chain of selects, as a hardware mux
    does (a table gather is far slower on a TPU)."""
    seg = frac >> (T_FRAC - 3)
    a = b = jnp.zeros_like(frac)
    for i in range(8):
        a = jnp.where(seg == i, I32(int(fit[0][i])), a)
        b = jnp.where(seg == i, I32(int(fit[1][i])), b)
    b = (b >> (COEF_FRAC - out_frac) if out_frac <= COEF_FRAC
         else b << (out_frac - COEF_FRAC))
    return ((a * frac) >> (COEF_FRAC + T_FRAC - out_frac)) + b


def _exp2(t):
    """2**t for t <= 0 at 2**-16, result at 2**-14: a shift of 2**v."""
    u = t >> T_FRAC
    v = t - (u << T_FRAC)
    return _pwl(v, EXP2_FIT, EXP_FRAC) >> jnp.clip(-u, 0, 31)


def _log2(s, s_frac):
    """log2 of int s > 0 at 2**-s_frac, result at 2**-16."""
    e = 31 - jax.lax.clz(s)
    rem = s - (I32(1) << e)
    frac = (rem << jnp.maximum(T_FRAC - e, 0)) >> jnp.maximum(e - T_FRAC, 0)
    return ((e - s_frac) << T_FRAC) + _pwl(frac, LOG2_FIT, T_FRAC)


def _to_log2(d):
    """d (<= 0, at 2**-10, saturated at -32) times log2(e), at 2**-16."""
    d = jnp.maximum(d, I32(-32 << IN_FRAC))
    return (d * I32(LOG2E_Q)) >> (IN_FRAC + LOG2E_FRAC - T_FRAC)


def gelu_unit(x):
    """GELU through the unit: float in, the unit's S5.10 word out."""
    z = jnp.clip(jnp.round(x.astype(jnp.float32) * 2 ** IN_FRAC),
                 -2 ** 15, 2 ** 15 - 1).astype(I32)
    zc = jnp.clip(z, -8 << IN_FRAC, 8 << IN_FRAC)
    z3 = (((zc * zc) >> IN_FRAC) * zc) >> IN_FRAC
    k = ((zc + ((z3 * I32(CUBIC_Q)) >> 16)) * I32(SQRT2PI_Q)) >> 14
    t1, t2 = _to_log2(k - jnp.abs(k)), _to_log2(-k - jnp.abs(k))
    s = jnp.maximum(_exp2(t1) + _exp2(t2), 1)
    sig = _exp2(jnp.minimum(t1 - _log2(s, EXP_FRAC), 0))
    return ((z * sig) >> EXP_FRAC).astype(jnp.float32) / 2 ** IN_FRAC


def gelu_tanh(x):
    k = math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)
    return 0.5 * x * (1.0 + jnp.tanh(k))


def gelu_ste(x):
    """Forward: the unit's words; backward: the tanh-form GELU's slope."""
    g = gelu_tanh(x)
    return g + jax.lax.stop_gradient(gelu_unit(x).astype(x.dtype) - g)


# ---------------- the encoder ----------------

def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"]}


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    m = dims(cfg)
    d, L, f, v = m["d"], m["layers"], m["ff"], m["vocab"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

    return {
        "embed": normal((v, d), 0.02),
        "pos": normal((m["positions"], d), 0.02),
        "final_g": gain((d,)), "final_b": normal((d,), 0.02),
        "head": normal((d, v), d ** -0.5),
        "layers": {
            "ln1_g": gain((L, d)), "ln1_b": normal((L, d), 0.02),
            "ln2_g": gain((L, d)), "ln2_b": normal((L, d), 0.02),
            "wq": normal((L, d, d), d ** -0.5),
            "wk": normal((L, d, d), d ** -0.5),
            "wv": normal((L, d, d), d ** -0.5),
            "wo": normal((L, d, d), d ** -0.5),
            "up": normal((L, d, f), d ** -0.5),
            "down": normal((L, f, d), f ** -0.5),
        },
    }


def n_active(cfg: dict) -> dict:
    """Parameters a token passes through in matmuls: the blocks'
    projections (``body``) and the output ``head``; the embedding and
    position tables are gathers."""
    m = dims(cfg)
    return {"body": float(m["layers"] * (4 * m["d"] ** 2
                                         + 2 * m["d"] * m["ff"])),
            "head": float(m["d"] * m["vocab"])}


def _mm(spec, a, b, prec):
    return jnp.einsum(spec, a, b, precision=prec)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def loss_sum(w: dict, cfg: dict, tokens, labels, dt: str = "f32"):
    """Summed token cross-entropy of a block of rows (``dt='bf16'``: the
    control)."""
    m = dims(cfg)
    eps = cfg["layer_norm_eps"]
    prec = jax.lax.Precision[cfg["run"]["matmul_precision"].upper()]
    if dt == CONTROL:
        w = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    b, s = tokens.shape
    h, hd = m["heads"], m["d"] // m["heads"]
    x = w["embed"][tokens] + w["pos"][jnp.arange(s)][None]

    @jax.checkpoint
    def layer(x, p):
        y = _ln(x, p["ln1_g"], p["ln1_b"], eps)
        q = _mm("bsd,de->bse", y, p["wq"], prec).reshape(b, s, h, hd)
        k = _mm("bsd,de->bse", y, p["wk"], prec).reshape(b, s, h, hd)
        v = _mm("bsd,de->bse", y, p["wv"], prec).reshape(b, s, h, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        sc = _mm("bqhd,bkhd->bhqk", q / math.sqrt(hd), k, prec)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _mm("bhqk,bkhd->bqhd", pr, v, prec).reshape(b, s, m["d"])
        x = x + _mm("bsd,de->bse", o, p["wo"], prec)
        y = _ln(x, p["ln2_g"], p["ln2_b"], eps)
        u = _mm("bsd,df->bsf", y, p["up"], prec)
        return x + _mm("bsf,fd->bsd", gelu_ste(u), p["down"], prec), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    y = _ln(x, w["final_g"], w["final_b"], eps)
    logits = _mm("bsd,dv->bsv", y, w["head"], prec).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)


def loss_and_grad(w, cfg, tokens, labels, dt="f32", rows=16):
    """Mean cross-entropy over the batch and its gradient, in blocks of
    ``rows`` rows so that the activations fit."""
    fn = _grad_fn(cfg, dt)
    n = tokens.shape[0] * tokens.shape[1]
    tot, grads = 0.0, None
    for i in range(0, tokens.shape[0], rows):
        v, g = fn(w, tokens[i:i + rows], labels[i:i + rows])
        tot = tot + v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return tot / n, jax.tree.map(lambda a: a / n, grads)


_GRAD = {}


def _grad_fn(cfg, dt):
    key = (json.dumps(cfg, sort_keys=True), dt)
    if key not in _GRAD:
        _GRAD[key] = jax.jit(jax.value_and_grad(
            lambda w, t, l: loss_sum(w, cfg, t, l, dt)))
    return _GRAD[key]


def decays(path, leaf) -> bool:
    """Weights decay; LayerNorm gains and biases never.  A leaf under
    ``layers`` is stacked over the layers, so its own rank is one less."""
    stacked = any(getattr(k, "key", None) == "layers" for k in path)
    return leaf.ndim - stacked >= 2


def train(w0: dict, cfg: dict, batches, opt: dict, dt: str = "f32"):
    """AdamW steps from ``w0`` over ``batches``.  Returns the losses, the
    first step's clipped gradient and the weights after the last step."""
    p = w0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first_grad = [], None
    b1, b2 = opt["b1"], opt["b2"]
    for step, (tokens, labels) in enumerate(batches):
        loss, g = loss_and_grad(p, cfg, tokens, labels, dt)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["grad_clip"] / (gn + 1e-6)), g)
        if step == 0:
            first_grad = g
        t = step + 1
        lr = opt["lr"] * t / opt["warmup_steps"] if step < \
            opt["warmup_steps"] else None
        if lr is None:
            raise ValueError("the reference follows warm-up steps only")
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

        def upd(path, pp, mm, vv):
            d = (mm / (1 - b1 ** t)) / (jnp.sqrt(vv / (1 - b2 ** t)) + 1e-8)
            if decays(path, pp):
                d = d + opt["weight_decay"] * pp
            return pp - lr * d
        p = jax.tree_util.tree_map_with_path(upd, p, m, v)
        losses.append(float(loss))
    return losses, first_grad, p
