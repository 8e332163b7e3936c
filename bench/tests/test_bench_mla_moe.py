"""The DeepSeek-V2 family's plain reference against the program, and a
whole tiny run of its cell, on the CPU (float32 both sides, so the
tolerances are float32 round-off).

``tiny_deepseek`` cuts the real cell's files down to a size that runs in
seconds; it keeps the published YaRN numbers, the unrenormalised gates
and a held share (2 of 8 experts, not the first two)."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import faults, harness
from bench.tests.tiny import run_tiny

CELL = "deepseek-v2-lite.doc_chat"


def tiny_deepseek(seed: int = 12345678901, seconds: float = 1.5,
                  max_seq: int = 256):
    cell = harness.load_cell(CELL, seed=seed, seconds=seconds)
    c = cell.config
    c.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=2,
             num_experts_per_tok=3, vocab_size=512,
             max_position_embeddings=max_seq)
    c["assumed"].update(router_experts=8, first_held_expert=2)
    c["run"]["engine"].update(n_slots=4, num_blocks=33, prefill_chunk=64)
    c["run"]["check"] = {"requests": 4, "tokens": 40}
    c["run"]["dtype"] = "float32"
    cell.traffic.update(rate_per_s=20.0, warmup_s=0.5, tail_s=10.0)
    cell.traffic["prompt"].update(median=20, min=4, max=100)
    cell.traffic["output"].update(median=6, min=2, max=20)
    return cell


def _program(conf):
    ad = harness.load_by_name("adapters", "mla_moe_decoder")
    return ad.model_config(conf).replace(ffn_impl="dense", norm_impl="dense")


def test_mla_moe_reference_matches_prefill_and_paged_decode():
    """The engine's own chunk-prefill and paged-decode programs against
    the reference's full forward: YaRN at the published numbers, gates
    not renormalised, experts 2-3 of 8 held, and one prefill chunk of
    1280 tokens, longer than any capacity-free length the old serving
    dispatch kept."""
    from repro.models.transformer import init_paged_caches
    from repro.serve.engine import (make_chunk_prefill_step,
                                    make_paged_decode_step)
    conf = tiny_deepseek(max_seq=2048).config
    ref = harness.load_by_name("references", "mla_moe_decoder")
    ad = harness.load_by_name("adapters", "mla_moe_decoder")
    mcfg = _program(conf)
    assert not mcfg.moe.norm_topk_prob and mcfg.rope_yarn.factor == 40.0
    w = ref.init_weights(conf, jax.random.PRNGKey(4))
    params = ad.to_program(w)
    bs, nblk, chunk, n = 16, 128, 1280, 1200
    table = jnp.arange(1, nblk + 1, dtype=jnp.int32)[None]
    caches = init_paged_caches(mcfg, nblk + 1, bs, jnp.float32)
    prompt = np.random.default_rng(0).integers(
        0, conf["vocab_size"], n).tolist()
    toks = jnp.asarray([prompt + [0] * (chunk - n)], jnp.int32)
    lp, caches, held = jax.jit(make_chunk_prefill_step(mcfg, counts=True))(
        params, caches, toks, jnp.int32(0), table,
        jnp.asarray([n - 1], jnp.int32))
    nxt = int(jnp.argmax(lp[0]))
    ld, _, _ = jax.jit(make_paged_decode_step(mcfg, counts=True))(
        params, caches, jnp.asarray([[nxt]], jnp.int32),
        jnp.asarray([n], jnp.int32), table)
    seq = jnp.asarray(prompt + [nxt] + [0] * (2048 - n - 1), jnp.int32)
    want = ref.logits_at(w, conf, seq, jnp.asarray([n - 1, n]))
    got = jnp.concatenate([lp, ld])
    # float32 on both sides; the 1200-key softmax sums and the expert
    # combine run in another order, so gaps scale with the logits
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # 2 MoE layers x 1280 rows x top-3 routed over 8 experts, 2 held
    assert 0 < float(held) < 2 * chunk * 3


def test_adapter_rope_permutation_is_the_interleaved_rotation():
    """The adapter's column permutation turns the program's rotate-half
    into the published interleaved rotation: q.k over the rope dims is
    the same at every pair of positions."""
    from repro.configs.base import YarnCfg
    from repro.models.layers import apply_rope
    conf = tiny_deepseek().config
    ref = harness.load_by_name("references", "mla_moe_decoder")
    ad = harness.load_by_name("adapters", "mla_moe_decoder")
    rs = conf["rope_scaling"]
    yarn = YarnCfg(factor=40.0, original_max_pos=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=rs["mscale"],
                   mscale_all_dim=rs["mscale_all_dim"])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    pos = jnp.asarray([0, 7, 300, 4095, 8191])
    want = jax.vmap(lambda a: ref._rope(a, pos, conf))(x)
    got = apply_rope(ad._deinterleave(x, 0, 8), pos[None], 10000.0, yarn)
    dots_want = jnp.einsum("bshd,bthd->bhst", want, want)
    dots_got = jnp.einsum("bshd,bthd->bhst", got, got)
    np.testing.assert_allclose(dots_got, dots_want, rtol=1e-5, atol=1e-5)


# the tiny cell serves in float32: which requests finish depends on the
# CPU's pace, and in bfloat16 a near tie among the tiny router's top-3 of
# 8 flips with it (gaps 0-0.11 read over seeds and loads); in float32 the
# program's gaps are round-off, while an altered token lies a whole
# logit spread below the best
TINY_LIMITS = {"logit_gap": 0.05}


def test_tiny_doc_chat_runs_correct():
    """The cell's whole open loop on the CPU, built by the unchanged
    ``bench/loops/open.py``: correct against the reference, with the
    cell's end-to-end metrics."""
    cell = tiny_deepseek()
    cell.limits = dict(TINY_LIMITS)
    out = run_tiny(cell)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert out["attempted"] > 0 and out["failed"] == 0


def test_tiny_doc_chat_altered_token_is_not_correct():
    cell = tiny_deepseek()
    cell.limits = dict(TINY_LIMITS)
    with faults.token_altered():
        out = run_tiny(cell)
    assert not out["correct"], out["compared"]


def test_new_readers_read_the_counters_and_nothing_else():
    """Both readers give None for a program without the counters (the
    parent's, or a dense model's) and read the counters when present."""
    mfu = harness.load_by_name("metrics", "mfu.doc_chat")
    ratio = harness.load_by_name("metrics", "mla_latent_read_ratio")
    spans = harness.Spans()
    spans.items = [("engine.step", 0.0, 0.5), ("engine.step", 1.0, 1.5)]
    n = {"body": 1e9, "head": 2e8, "expert": 8.65e6, "moe_layers": 26.0,
         "top_k": 6.0}
    counters = {"prefill_tokens": 4096, "decode_tokens": 100}

    def run(c):
        return harness.Run(spans=spans, window=(0.0, 2.0), counters=c,
                           calls={}, dims={}, n_active=n,
                           device_kind="TPU v5 lite")
    assert mfu.read(run(counters)) is None
    assert ratio.read(run(counters)) is None
    got = {**counters, "moe_routed_rows": 1000, "moe_held_rows": 125.0,
           "mla_latent_read": 16 * 8192, "decode_kv_live": 16 * 2048}
    rows = 4196 * 26 * 6 * 0.125
    flops = 2e9 * 4196 + 2 * 2e8 * 100 + 2 * 8.65e6 * rows
    assert abs(mfu.read(run(got)) - 100 * flops / 1.0 / 197e12) < 1e-9
    assert ratio.read(run(got)) == 4.0
