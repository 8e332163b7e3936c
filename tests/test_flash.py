"""Blocked online-softmax attention vs the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.models.attention import _sdpa
from repro.models.flash import flash_attention, use_flash

RNG = np.random.default_rng(2)


def _mk(b, s, t, k, g, h, hv=None):
    hv = hv or h
    q = jnp.asarray(RNG.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, t, k, h)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, t, k, hv)), jnp.float32)
    return q, kk, v


def _naive(q, k, v, q_pos, kv_valid, causal):
    return _sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                 softmax_impl="float", causal=causal)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 64, 128])
def test_flash_matches_naive(causal, block):
    q, k, v = _mk(2, 64, 128, 2, 3, 16)
    q_pos = jnp.broadcast_to(jnp.arange(64, 128)[None], (2, 64))
    kv_valid = jnp.ones((2, 128), bool)
    out = flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                          causal=causal, block=block)
    want = _naive(q, k, v, q_pos, kv_valid, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


def test_flash_mla_style_hv_differs():
    q, k, v = _mk(2, 32, 32, 4, 1, 24, hv=12)   # qk head 24, v head 12
    q_pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    valid = jnp.ones((2, 32), bool)
    out = flash_attention(q, k, v, q_pos=q_pos, kv_valid=valid, block=8)
    want = _naive(q, k, v, q_pos, valid, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


@given(st.integers(1, 3), st.sampled_from([32, 48, 64]),
       st.integers(0, 40), st.booleans())
@settings(max_examples=30, deadline=None)
def test_flash_partial_validity_property(b, t, n_valid, causal):
    n_valid = min(n_valid, t)
    q, k, v = _mk(b, 16, t, 1, 2, 8)
    q_pos = jnp.broadcast_to(jnp.arange(t - 16, t)[None], (b, 16))
    kv_valid = jnp.broadcast_to(jnp.arange(t)[None] < max(n_valid, 1), (b, t))
    out = flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                          causal=causal, block=16)
    want = _naive(q, k, v, q_pos, kv_valid, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-6)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_flash_bf16_io():
    q, k, v = _mk(1, 32, 64, 2, 2, 16)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    q_pos = jnp.broadcast_to(jnp.arange(32, 64)[None], (1, 32))
    valid = jnp.ones((1, 64), bool)
    out = flash_attention(q, k, v, q_pos=q_pos, kv_valid=valid, block=16)
    want = _naive(q, k, v, q_pos, valid, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


def test_use_flash_threshold():
    assert not use_flash(1, 32768)          # decode: split-KV/naive path
    assert use_flash(4096, 4096)            # train_4k: blocked
    assert use_flash(32768, 32768)          # prefill_32k: blocked
    assert not use_flash(64, 64)
    # no divisibility condition: pad-and-slice handles ragged T, so long
    # non-512-multiple contexts must NOT fall back to materialized scores
    assert use_flash(4096, 4097)
    assert use_flash(32768, 33000)


def test_auto_blocked_pick_is_backend_aware(monkeypatch):
    """'auto' streams through the compiled Pallas kernel on TPU and the
    pure-JAX blocked path on interpret backends, where Pallas has no
    compiler and would run its interpreter.  Explicit impl strings are
    never rewritten."""
    from repro.kernels import dispatch
    from repro.models.flash import blocked_impl
    assert blocked_impl("tpu") == "flash_pallas"
    assert blocked_impl("cpu") == "flash"
    assert blocked_impl("gpu") == "flash"
    # this host (CPU/interpret): resolution unchanged from the seed rule
    assert dispatch.resolve_attention("auto", 4096, 4096) == "flash"
    assert dispatch.resolve_attention("auto", 64, 64) == "naive"
    # simulated TPU: blocked picks go to the compiled kernel; everything
    # else about resolution — naive short rows, dualmode routing, the
    # explicit-impl passthrough — is unchanged
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.resolve_attention("auto", 4096, 4096) == "flash_pallas"
    assert dispatch.resolve_attention("auto", 64, 64) == "naive"
    assert dispatch.resolve_attention(
        "auto", 4096, 4096, softmax_impl="dualmode") == "flash_pallas_int"
    assert dispatch.resolve_attention("flash", 4096, 4096) == "flash"


def test_flash_grad_finite():
    q, k, v = _mk(1, 32, 32, 1, 2, 8)
    q_pos = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    valid = jnp.ones((1, 32), bool)

    def loss(q_):
        return flash_attention(q_, k, v, q_pos=q_pos, kv_valid=valid,
                               block=8).sum()

    g = jax.grad(loss)(q)
    assert bool(jnp.all(jnp.isfinite(g)))
    # matches naive-path gradient
    g2 = jax.grad(lambda q_: _naive(q_, k, v, q_pos, valid, True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g2), atol=1e-5)
