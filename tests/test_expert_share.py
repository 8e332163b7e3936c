"""The expert-share MoE layer, YaRN and the serving dispatch.

- The shares of an expert-parallel split add up to the uncut layer.
- YaRN's frequencies and temperature follow DeepSeek-V2's published
  construction at the published numbers.
- Serving drops no token at any chunk length: a token's output does not
  depend on what else shares its chunk.
- qwen's paged serving gives the tokens it gave before the MoE, MLA and
  rope changes (no MoE, no MLA, no YaRN: nothing of them may reach it),
  and the other MoE models train as they did.
- Mesh serving keeps its group-local dispatch; MLA's decode tick in the
  absorbed form computes what the expanded prefill computes.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import YarnCfg
from repro.models.attention import MLASpec, mla_softmax_scale
from repro.models.layers import rope_freqs, yarn_freqs, yarn_mscale
from repro.models.moe import MoESpec, moe_apply, moe_init
from repro.models.transformer import init_lm
from repro.serve import Request, ServeEngine

V2_LITE_YARN = YarnCfg(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                       beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _spec(**kw):
    base = dict(d_model=32, d_ff=48, n_experts=8, top_k=3, n_shared=2,
                activation="silu", norm_topk_prob=False)
    base.update(kw)
    return MoESpec(**base)


def _share(p, first, n):
    """The params a chip holding experts [first, first + n) is given."""
    return {**p, **{k: p[k][first:first + n] for k in ("gate", "up",
                                                      "down")}}


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips holding two experts each: their outputs, with the
    shared experts (which every chip computes) counted once, sum to the
    whole layer's; their held rows sum to every routed row."""
    full = _spec(dispatch="dense")
    p = moe_init(jax.random.PRNGKey(0), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    want, _ = moe_apply(p, full, x)
    shared, _ = moe_apply({**p, **{k: jnp.zeros_like(p[k])
                                   for k in ("gate", "up", "down")}},
                          full, x)
    total, rows = -3 * shared, 0.0
    for first in (0, 2, 4, 6):
        s = _spec(first_held=first, n_held=2)
        y, held = moe_apply(_share(p, first, 2), s, x, dropless=True)
        total, rows = total + y, rows + float(held)
    # float32 sums of the same products in another order
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert rows == 2 * 24 * 3


def test_a_share_adds_nothing_for_tokens_routed_elsewhere():
    """Every slot routed to an absent expert: the share's output is the
    shared experts' alone, and it counts no held row."""
    s = _spec(first_held=6, n_held=2, n_shared=1)
    p = moe_init(jax.random.PRNGKey(2), s, jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, 16, 32)))
    # a router that always picks experts 0-2 for positive inputs
    p["router"] = jnp.zeros((32, 8)).at[:, :3].set(jnp.asarray([3., 2., 1.]))
    y, held = moe_apply(p, s, x, dropless=True)
    only_shared = moe_apply({**p, **{k: jnp.zeros_like(p[k]) for k in
                                     ("gate", "up", "down")}}, s, x,
                            dropless=True)[0]
    assert float(held) == 0.0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(only_shared))


def test_gates_follow_norm_topk_prob_and_the_routed_scale():
    """With one expert held and top-1 routing the output is gate x the
    expert; the gate is the softmax score, renormalised to 1 only when
    asked, then times routed_scale."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 32))
    outs = {}
    for norm, scale in ((False, 1.0), (True, 1.0), (False, 2.5)):
        s = _spec(n_experts=4, top_k=1, n_shared=0, norm_topk_prob=norm,
                  routed_scale=scale, dispatch="dense")
        p = moe_init(jax.random.PRNGKey(5), s, jnp.float32)
        outs[norm, scale] = moe_apply(p, s, x, dropless=True)[0]
        probs = jax.nn.softmax(x @ p["router"], -1).max(-1)
    np.testing.assert_allclose(outs[False, 1.0],
                               outs[True, 1.0] * probs[..., None],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[False, 2.5], 2.5 * outs[False, 1.0],
                               rtol=1e-5, atol=1e-6)


def test_serving_is_dropless_at_any_chunk_length():
    """A 1536-token chunk whose every token routes to the same two
    experts: the serving path gives each token the output it gets alone,
    whatever else shares the chunk (capacity-bounded dispatch drops most
    of these rows)."""
    s = _spec(n_experts=8, top_k=2, n_shared=1)
    p = moe_init(jax.random.PRNGKey(6), s, jnp.float32)
    p["router"] = jnp.zeros((32, 8)).at[:, 3].set(1.0).at[:, 5].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (1, 1536, 32)))
    y, held = moe_apply(p, s, x, dropless=True)
    assert float(held) == 1536 * 2
    other = x.at[:, 1:].set(jnp.abs(jax.random.normal(
        jax.random.PRNGKey(8), (1, 1535, 32))))
    y2, _ = moe_apply(p, s, other, dropless=True)
    alone, _ = moe_apply(p, s, x[:, :1], dropless=True)
    np.testing.assert_allclose(y[0, 0], y2[0, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[0, 0], alone[0, 0], rtol=1e-6, atol=1e-6)
    dropped, _ = moe_apply(p, s, x)          # training: capacity 1.25
    assert float(jnp.abs(dropped - y).max()) > 1e-3


def test_yarn_follows_the_published_construction():
    """DeepseekV2YarnRotaryEmbedding at V2-Lite's numbers (rope dim 64,
    theta 1e4, factor 40 over 4096, beta 32/1), written out in float64:
    correction range [10, 23], and MLA's softmax scale times
    (0.1 * 0.707 * ln 40 + 1)^2 = 1.5896."""
    dim, base = 64, 10000.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    assert (low, high) == (10, 23)
    i = np.arange(0, dim, 2) / dim
    extra, inter = 1.0 / base ** i, 1.0 / (40.0 * base ** i)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = inter * (1 - mask) + extra * mask
    got = np.asarray(yarn_freqs(dim, base, V2_LITE_YARN))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == np.float32(1.0) and abs(got[-1] * 40 - extra[-1]) < 1e-9
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(yarn_mscale(40.0, 0.707) - m) < 1e-12
    assert abs(m * m - 1.5896) < 1e-4
    spec = MLASpec(2048, 16, 0, 512, 128, 64, 128, yarn=V2_LITE_YARN)
    assert abs(mla_softmax_scale(spec) - 192 ** -0.5 * m * m) < 1e-12
    assert mla_softmax_scale(spec._replace(yarn=None)) == 192 ** -0.5
    # the fast-turning dimensions keep the plain frequencies
    np.testing.assert_array_equal(got[:low],
                                  np.asarray(rope_freqs(dim, base))[:low])


def test_qwen_paged_tokens_unchanged():
    """Greedy tokens of the reduced qwen through the paged engine, as
    served before the expert-share layer, YaRN and the engine's MoE/MLA
    counters existed; the engine counts nothing new for it."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(7), cfg)
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=128,
                      cache_mode="paged", prefill_chunk=32)
    out = eng.run([
        Request(rid=0, prompt=list(range(5, 45)), max_new=12),
        Request(rid=1, prompt=[9, 3, 7, 1], max_new=10),
        Request(rid=2, prompt=[(7 * i) % 500 for i in range(70)],
                max_new=8)])
    assert out == {
        0: [279, 230, 443, 449, 443, 173, 1, 202, 323, 57, 94, 447],
        1: [263, 254, 342, 263, 251, 263, 243, 342, 87, 87],
        2: [290, 168, 85, 37, 462, 133, 117, 243]}
    assert not {"moe_routed_rows", "moe_held_rows", "mla_latent_read",
                "decode_kv_live"} & set(eng.stats)


def test_deepseek_engine_counts_routed_held_and_latent_work():
    """The reduced DeepSeek through the paged engine: routed rows are the
    dispatched rows x top-k x MoE layers, held rows (every expert held)
    equal them, and each decode tick reads every slot's whole table."""
    cfg = registry.reduced_config("deepseek-v2-lite-16b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                      cache_mode="paged", prefill_chunk=16)
    eng.run([Request(rid=0, prompt=list(range(3, 12)), max_new=4),
             Request(rid=1, prompt=[5, 1, 4], max_new=5)])
    st = eng.stats
    moe_layers, k = cfg.n_layers - 1, cfg.moe.top_k
    rows = (st["prefill_chunks"] * 16 + st["decode_steps"] * 2)
    assert st["moe_routed_rows"] == rows * k * moe_layers
    assert st["moe_held_rows"] == st["moe_routed_rows"]
    assert st["mla_latent_read"] == st["decode_steps"] * 2 * 64
    assert 0 < st["decode_kv_live"] < st["mla_latent_read"]


# the reduced models' bf16 training forward as computed before the
# expert-share layer: logits[0, :3, :4], the sum of |logits| and the aux
# loss.  A float32 router product moves the sum by about 1e-5 of itself
# (granite) and 1e-4 (jamba), so rtol 1e-6 tells the two apart.
MOE_TRAIN_PINS = {
    "granite-moe-3b-a800m": (
        [[-0.1669921875, 0.310546875, -0.2001953125, 0.1630859375],
         [0.11865234375, 0.037353515625, -0.35546875, 0.27734375],
         [0.203125, 0.0303955078125, -0.29296875, 0.291015625]],
        2099.412109375, 2.0409035682678223),
    "jamba-v0.1-52b": (
        [[-1.4140625, -0.70703125, 0.34765625, -0.408203125],
         [-0.73828125, -0.83203125, 0.29296875, -1.09375],
         [-0.5546875, 1.0, 0.23828125, -1.34375]],
        13074.3310546875, 4.0263566970825195),
}


@pytest.mark.parametrize("arch", sorted(MOE_TRAIN_PINS))
def test_moe_training_forward_is_unchanged(arch):
    """Granite's and Jamba's defaults (router product in the activation
    dtype, gates renormalised, every expert held) train as before."""
    from repro.models.transformer import lm_apply
    cfg = registry.reduced_config(arch)
    assert not cfg.moe.router_f32 and cfg.moe.norm_topk_prob
    params = init_lm(jax.random.PRNGKey(3), cfg, jnp.bfloat16)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    lg, _, aux = lm_apply(params, cfg, toks)
    lg = np.asarray(lg.astype(jnp.float32))
    first, abs_sum, aux_want = MOE_TRAIN_PINS[arch]
    np.testing.assert_array_equal(lg[0, :3, :4], np.asarray(first))
    np.testing.assert_allclose(np.abs(lg).sum(), abs_sum, rtol=1e-6)
    np.testing.assert_allclose(float(aux), aux_want, rtol=1e-6)


def test_router_f32_routes_on_the_float32_product():
    """bf16 activations: with ``router_f32`` the gates are the float32
    softmax of the exact products; without it, of the products rounded
    to bf16 (the default every other MoE config keeps)."""
    from repro.models.moe import _route
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 32)).astype(
        jnp.bfloat16)
    s = _spec()
    p = moe_init(jax.random.PRNGKey(10), s, jnp.bfloat16)
    exact = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    rounded = (x @ p["router"]).astype(jnp.float32)
    for f32, logits in ((True, exact), (False, rounded)):
        gates, idx, _ = _route(p, s._replace(router_f32=f32), x)
        want_g, want_i = jax.lax.top_k(jax.nn.softmax(logits, -1), s.top_k)
        np.testing.assert_array_equal(idx, want_i)
        assert gates.dtype == (jnp.float32 if f32 else jnp.bfloat16)
        np.testing.assert_allclose(gates.astype(jnp.float32),
                                   want_g.astype(gates.dtype)
                                   .astype(jnp.float32), rtol=1e-6)


def test_mesh_serving_keeps_the_group_local_dispatch():
    """With mesh axes (the sharded serve cells) serving runs the pinned
    group-local capacity dispatch: exact while a sequence fits
    ``dropless_max_seq``, bounded at ``inference_cf`` past it, and an
    expert share is refused there."""
    from jax.sharding import Mesh
    s = _spec(n_experts=8, top_k=2, n_shared=1, dropless_max_seq=64)
    p = moe_init(jax.random.PRNGKey(11), s, jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def on_mesh(p, s, x):
        with jax.set_mesh(mesh):
            return jax.jit(lambda p, x: moe_apply(
                p, s, x, dropless=True, axes=("data", None)))(p, x)
    short = jax.random.normal(jax.random.PRNGKey(12), (2, 48, 32))
    y, rows = on_mesh(p, s, short)
    want, _ = moe_apply(p, s, short, dropless=True)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert float(rows) == 2 * 48 * 2
    # every token to experts 3 and 5: past dropless_max_seq the capacity
    # (2.0 x the balanced load) drops most of them
    p["router"] = jnp.zeros((32, 8)).at[:, 3].set(1.0).at[:, 5].set(0.5)
    long = jnp.abs(jax.random.normal(jax.random.PRNGKey(13), (1, 96, 32)))
    y, _ = on_mesh(p, s, long)
    assert float(jnp.abs(y - moe_apply(p, s, long, dropless=True)[0])
                 .max()) > 1e-3
    with pytest.raises(ValueError, match="one chip only"):
        on_mesh(p, s._replace(first_held=0, n_held=2), short)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "minicpm3-4b"])
def test_mla_decode_in_absorbed_form_matches_prefill(arch):
    """MLA's decode tick attends against the latent (query through the
    key half of ``wkv_b``, value half after the combine); its logits
    match a prefill of the same tokens, which expands the latent.
    float32 on the CPU: the two orders of the same products agree to
    1e-5 of the logits' scale."""
    from repro.models.transformer import init_caches, lm_apply
    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    caches = init_caches(cfg, 2, 32, jnp.float32)
    _, caches, _ = lm_apply(params, cfg, toks[:, :8], caches=caches, pos=0)
    for i in range(8, 12):
        got, caches, _ = lm_apply(params, cfg, toks[:, i:i + 1],
                                  caches=caches, pos=i)
        want, _, _ = lm_apply(params, cfg, toks[:, :i + 1],
                              caches=init_caches(cfg, 2, 32, jnp.float32),
                              pos=0)
        scale = float(jnp.abs(want[:, -1]).max())
        np.testing.assert_allclose(got[:, -1], want[:, -1],
                                   atol=1e-5 * scale, rtol=0)
