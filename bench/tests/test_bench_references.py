"""The plain references against the program, at reduced sizes on the CPU
(float32 both sides, so the tolerances are float32 round-off)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generate, harness
from bench.tests.tiny import tiny_cell


def test_unit_gelu_words_match_the_program():
    from repro.core.activations import gelu_dualmode as program_ste
    from repro.core.softmax_unit import gelu_dualmode as program_words
    ref = harness.load_by_name("references", "bert_encoder")
    x = jnp.linspace(-40.0, 40.0, 160001, dtype=jnp.float32)
    assert (np.asarray(ref.gelu_unit(x)) ==
            np.asarray(program_words(x))).all()
    assert (np.asarray(ref.gelu_ste(x)) == np.asarray(program_ste(x))).all()
    g_ref = jax.grad(lambda v: ref.gelu_ste(v).sum())(x)
    g_prog = jax.grad(lambda v: program_ste(v).sum())(x)
    np.testing.assert_allclose(g_ref, g_prog, rtol=1e-5, atol=1e-5)


def test_bert_reference_loss_and_gradients_match_the_program():
    from repro.configs.base import TrainConfig
    from repro.train.step import make_loss_fn
    cell = tiny_cell("bert-base.train_s512")
    conf = cell.config
    ref = harness.load_by_name("references", "bert_encoder")
    ad = harness.load_by_name("adapters", "bert_encoder")
    w = ref.init_weights(conf, jax.random.PRNGKey(3))
    tokens, labels = generate.TrainData(cell.traffic, 5,
                                        conf["vocab_size"]).batch(0)
    loss_fn = make_loss_fn(ad.model_config(conf), TrainConfig())
    (lp, _), gp = jax.value_and_grad(loss_fn, has_aux=True)(
        ad.to_program(w), {"tokens": tokens, "labels": labels})
    lr, gr = ref.loss_and_grad(w, conf, tokens, labels, rows=2)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(
            ad.from_program(gp)), jax.tree.leaves(gr)):
        # float32 sums in another order: elementwise gaps scale with the
        # leaf, not the element
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-4 * float(np.abs(b).max()),
                                   err_msg=jax.tree_util.keystr(path))


def test_dense_decoder_reference_matches_prefill_and_paged_decode():
    """The engine's own chunk-prefill and paged-decode programs against
    the reference's full forward at the same positions."""
    from repro.models.transformer import init_paged_caches
    from repro.serve.engine import (make_chunk_prefill_step,
                                    make_paged_decode_step)
    cell = tiny_cell("qwen1.5-0.5b.chat")
    conf = cell.config
    ref = harness.load_by_name("references", "dense_decoder")
    ad = harness.load_by_name("adapters", "dense_decoder")
    mcfg = ad.model_config(conf).replace(ffn_impl="dense",
                                         norm_impl="dense")
    w = ref.init_weights(conf, jax.random.PRNGKey(4))
    params = ad.to_program(w)
    bs, nblk, chunk = 16, 16, 64
    table = jnp.arange(1, nblk + 1, dtype=jnp.int32)[None]
    caches = init_paged_caches(mcfg, nblk + 1, bs, jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, conf["vocab_size"], 40).tolist()
    toks = jnp.asarray([prompt + [0] * (chunk - 40)], jnp.int32)
    lp, caches = jax.jit(make_chunk_prefill_step(mcfg))(
        params, caches, toks, jnp.int32(0), table,
        jnp.asarray([39], jnp.int32))
    nxt = int(jnp.argmax(lp[0]))
    ld, _ = jax.jit(make_paged_decode_step(mcfg))(
        params, caches, jnp.asarray([[nxt]], jnp.int32),
        jnp.asarray([40], jnp.int32), table)
    seq = jnp.asarray(prompt + [nxt] + [0] * (256 - 41), jnp.int32)
    want = ref.logits_at(w, conf, seq, jnp.asarray([39, 40]))
    got = jnp.concatenate([lp, ld])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bert_reference_decays_weights_and_never_norms():
    """AdamW's stated rule: weights decay, LayerNorm gains and biases
    never, also where they are stacked over the layers."""
    conf = tiny_cell("bert-base.train_s512").config
    ref = harness.load_by_name("references", "bert_encoder")
    w = jax.eval_shape(lambda: ref.init_weights(conf, jax.random.PRNGKey(0)))
    got = {jax.tree_util.keystr(p): ref.decays(p, a) for p, a in
           jax.tree_util.tree_leaves_with_path(w)}
    assert {k for k, v in got.items() if v} == {
        "['embed']", "['pos']", "['head']", "['layers']['wq']",
        "['layers']['wk']", "['layers']['wv']", "['layers']['wo']",
        "['layers']['up']", "['layers']['down']"}
