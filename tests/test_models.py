"""Per-architecture smoke tests (assignment: reduced config, one forward +
one train step on CPU, shape/NaN assertions) + cache-consistency checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import SHAPES, TrainConfig
from repro.models.transformer import (encoder_apply, init_caches, init_lm,
                                      lm_apply)
from repro.train.step import TrainState, make_train_step
from repro.optim import adamw_init

ARCHS = registry.ARCH_IDS


def _fwd_kwargs(cfg, b):
    kw = {}
    if cfg.family == "encdec":
        frames = jnp.zeros((b, 16, cfg.d_model))
        return {"frames": frames}
    if cfg.family == "vlm":
        return {"image_embeds": jnp.zeros((b, cfg.n_img_tokens, cfg.d_model))}
    return kw


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward_shapes_finite(arch):
    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    kw = _fwd_kwargs(cfg, 2)
    cross = None
    if "frames" in kw:
        cross = encoder_apply(params, cfg, kw["frames"])
    elif "image_embeds" in kw:
        cross = kw["image_embeds"]
    logits, caches, aux = lm_apply(params, cfg, toks, cross_src=cross)
    assert logits.shape == (2, 16, cfg.vocab)
    assert caches is None
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    cfg = registry.reduced_config(arch)
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=10, remat=True)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = TrainState(params, adamw_init(params), {})
    step = jax.jit(make_train_step(cfg, tcfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((2, 16, cfg.d_model))
    elif cfg.family == "vlm":
        batch["image_embeds"] = jnp.zeros((2, cfg.n_img_tokens, cfg.d_model))
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"])) and float(m["loss"]) > 0
    assert np.isfinite(float(m["grad_norm"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda p, q: float(jnp.abs(p - q).sum()),
                     state.params, state2.params))
    assert delta > 0


@pytest.mark.parametrize("arch", ["qwen3-14b", "minicpm3-4b", "jamba-v0.1-52b",
                                  "rwkv6-1.6b", "whisper-base"])
def test_prefill_then_decode_matches_full(arch):
    """prefill(0..n) + decode(n) logits == prefill(0..n+1) last logits."""
    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0, cfg.vocab)
    caches = init_caches(cfg, 2, 32)
    lg1, caches, _ = lm_apply(params, cfg, toks[:, :8], pos=0, caches=caches)
    lg2, _, _ = lm_apply(params, cfg, toks[:, 8:9], pos=8, caches=caches)
    full_caches = init_caches(cfg, 2, 32)
    lgf, _, _ = lm_apply(params, cfg, toks, pos=0, caches=full_caches)
    np.testing.assert_allclose(np.asarray(lg2[:, -1]), np.asarray(lgf[:, -1]),
                               atol=2e-4)


def test_per_row_positions_decode():
    """Vector pos: two rows at different depths decode independently."""
    cfg = registry.reduced_config("yi-6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    t = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 0, cfg.vocab)
    # row A: prefix of 5, row B: prefix of 9
    cA = init_caches(cfg, 1, 32)
    _, cA, _ = lm_apply(params, cfg, t[:, :5], pos=0, caches=cA)
    cB = init_caches(cfg, 1, 32)
    _, cB, _ = lm_apply(params, cfg, t[:, :9], pos=0, caches=cB)
    caches = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1)
                          if a.ndim > 1 and a.shape[1] == 1 else
                          jnp.concatenate([a, b], axis=0), cA, cB)
    # stacked-period caches have batch at axis 1
    caches = jax.tree_util.tree_map_with_path(
        lambda p, a: a, caches)  # structure sanity
    tok = jnp.concatenate([t[:, 5:6], t[:, 9:10]], axis=0)
    pos = jnp.asarray([5, 9], jnp.int32)
    lg, _, _ = lm_apply(params, cfg, tok, pos=pos, caches=caches)
    # oracle rows
    oA = init_caches(cfg, 1, 32)
    lgA, _, _ = lm_apply(params, cfg, t[:, :6], pos=0, caches=oA)
    oB = init_caches(cfg, 1, 32)
    lgB, _, _ = lm_apply(params, cfg, t[:, :10], pos=0, caches=oB)
    np.testing.assert_allclose(np.asarray(lg[0, -1]), np.asarray(lgA[0, -1]),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(lg[1, -1]), np.asarray(lgB[0, -1]),
                               atol=2e-4)


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (spot checks)."""
    c = registry.get_config("qwen3-14b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab) == (40, 5120, 40, 8, 17408, 151936)
    assert c.qk_norm
    c = registry.get_config("jamba-v0.1-52b")
    assert c.moe.n_experts == 16 and c.moe.top_k == 2
    assert sum(1 for s in c.pattern if s.mixer == "attn") == 1  # 1:7
    c = registry.get_config("deepseek-v2-lite-16b")
    assert c.mla.kv_lora_rank == 512 and c.moe.top_k == 6
    assert c.moe.n_shared == 2
    # hf:deepseek-ai/DeepSeek-V2-Lite config.json
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab) == (
        27, 2048, 16, 10944, 102400)
    assert (c.mla.q_lora_rank, c.mla.nope_dim, c.mla.rope_dim,
            c.mla.v_dim) == (0, 128, 64, 128)
    assert (c.moe.n_experts, c.moe.d_ff, c.moe.norm_topk_prob,
            c.moe.routed_scale) == (64, 1408, False, 1.0)
    assert (c.moe.first_held, c.moe.n_held) == (0, 0)     # all held
    assert len(c.prefix) == 1 and c.prefix[0].ffn == "mlp"
    assert c.pattern[0].ffn == "moe" and c.rope_theta == 10000.0
    y = c.rope_yarn
    assert (y.factor, y.original_max_pos, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (40.0, 4096, 32.0, 1.0, 0.707,
                                            0.707)
    assert not c.tie_embeddings and c.norm_eps == 1e-6
    c = registry.get_config("minicpm3-4b")
    assert c.n_layers == 62 and c.mla is not None
    c = registry.get_config("rwkv6-1.6b")
    assert c.sub_quadratic
    c = registry.get_config("whisper-base")
    assert c.enc_layers == 6 and c.vocab == 51865
    c = registry.get_config("granite-moe-3b-a800m")
    assert c.moe.n_experts == 40 and c.moe.top_k == 8


def test_cell_applicability_rules():
    jam = registry.get_config("jamba-v0.1-52b")
    yi = registry.get_config("yi-6b")
    assert registry.cell_applicable(jam, SHAPES["long_500k"])[0]
    assert not registry.cell_applicable(yi, SHAPES["long_500k"])[0]
