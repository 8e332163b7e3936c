"""Sequence-parallel ring flash attention (ISSUE 4 tentpole).

In-process tests build the ring mesh over however many devices exist —
one in the plain tier-1 run, eight under the CI multi-device lane
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — so the same
suite exercises the real rotation when devices are available.  The gold
acceptance test (output and dq/dk/dv parity vs the single-device Pallas
kernel <= 1e-5 on an emulated 8-device mesh) always runs multi-device
via the subprocess fixture.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ring_attention import ring_flash_attention
from repro.launch.mesh import auto_mesh
from repro.models.attention import _naive_sdpa
from repro.models.flash import flash_attention_merged

RNG = np.random.default_rng(13)


def _mk(b, s, t, k, g, h, hv=None):
    hv = hv or h
    q = jnp.asarray(RNG.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, t, k, h)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, t, k, hv)), jnp.float32)
    return q, kk, v


def _ring_mesh(s: int, t: int):
    """Largest power-of-two device ring that divides both sequence dims."""
    n = len(jax.devices())
    while n > 1 and (s % n or t % n):
        n //= 2
    return auto_mesh((n,), ("model",)), n


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_naive_and_single_device(causal):
    q, k, v = _mk(2, 64, 64, 2, 2, 16)
    q_pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    kv_valid = jnp.asarray(RNG.random((2, 64)) > 0.2).at[:, 0].set(True)
    mesh, _ = _ring_mesh(64, 64)
    got = ring_flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                               mesh=mesh, causal=causal, interpret=True)
    want = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                       causal=causal)
    sd = flash_attention_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sd), atol=1e-5)


def test_ring_merged_stats_match_single_device_residual_contract():
    """The MERGED (m, l) must equal the single-device kernel's saved
    whole-row statistics — the residual contract IS the ring interface."""
    q, k, v = _mk(1, 32, 32, 2, 2, 8)
    q_pos = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    kv_valid = jnp.ones((1, 32), bool)
    mesh, _ = _ring_mesh(32, 32)
    _, m_r, l_r = ring_flash_attention(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, mesh=mesh,
        interpret=True, return_stats=True)
    _, m_s, l_s = flash_attention_pallas(
        q, k, v, q_pos=q_pos, kv_valid=kv_valid, interpret=True,
        return_stats=True)
    np.testing.assert_allclose(np.asarray(m_r), np.asarray(m_s), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_r), np.asarray(l_s),
                               rtol=1e-5, atol=1e-6)


def test_ring_grads_match_naive():
    q, k, v = _mk(1, 32, 32, 1, 2, 8)
    q_pos = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    kv_valid = jnp.ones((1, 32), bool)
    mesh, _ = _ring_mesh(32, 32)

    def g_of(fn):
        return jax.grad(lambda q_, k_, v_: fn(q_, k_, v_).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    gr = g_of(lambda q_, k_, v_: ring_flash_attention(
        q_, k_, v_, q_pos=q_pos, kv_valid=kv_valid, mesh=mesh,
        interpret=True))
    gn = g_of(lambda q_, k_, v_: _naive_sdpa(
        q_, k_, v_, q_pos=q_pos, kv_valid=kv_valid))
    for name, a, b in zip(("dq", "dk", "dv"), gr, gn):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, err_msg=name)


def test_ring_skip_masked_hops_is_parity_neutral():
    """Skipped causal hops drop only the exp(MASK_VALUE) mass of fully
    masked keys — forcing every hop must agree within float tolerance."""
    q, k, v = _mk(1, 32, 32, 1, 1, 8)
    q_pos = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    kv_valid = jnp.ones((1, 32), bool)
    mesh, _ = _ring_mesh(32, 32)
    kw = dict(q_pos=q_pos, kv_valid=kv_valid, mesh=mesh, causal=True,
              interpret=True)
    fast = ring_flash_attention(q, k, v, skip_masked_hops=True, **kw)
    full = ring_flash_attention(q, k, v, skip_masked_hops=False, **kw)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(full),
                               atol=1e-6)


def test_ring_matches_pure_jax_merged_reference():
    """kernels/ring_attention.py across devices == the one-host fold in
    models/flash.flash_attention_merged (the pure-JAX home of the
    partial-merge contract) — for any split count."""
    q, k, v = _mk(1, 16, 48, 2, 1, 8, hv=12)
    q_pos = jnp.broadcast_to(jnp.arange(32, 48)[None], (1, 16))
    kv_valid = jnp.asarray(RNG.random((1, 48)) > 0.25).at[:, 0].set(True)
    want = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=True)
    for n_splits in (1, 2, 4):
        got = flash_attention_merged(q, k, v, q_pos=q_pos,
                                     kv_valid=kv_valid, n_splits=n_splits,
                                     causal=True, block=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=f"n={n_splits}")


def test_ring_requires_mesh_and_divisible_shapes():
    q, k, v = _mk(1, 16, 16, 1, 1, 8)
    q_pos = jnp.broadcast_to(jnp.arange(16)[None], (1, 16))
    kv_valid = jnp.ones((1, 16), bool)
    with pytest.raises(ValueError, match="mesh"):
        ring_flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid)
    if len(jax.devices()) > 1:
        mesh = auto_mesh((len(jax.devices()),), ("model",))
        q2, k2, v2 = _mk(1, 17, 17, 1, 1, 8)
        with pytest.raises(ValueError, match="divide"):
            ring_flash_attention(
                q2, k2, v2, q_pos=jnp.broadcast_to(
                    jnp.arange(17)[None], (1, 17)),
                kv_valid=jnp.ones((1, 17), bool), mesh=mesh)


# ---------------- dispatch resolution ----------------

def test_resolve_ring_upgrade_is_mesh_and_knob_gated():
    n = len(jax.devices())
    mesh = auto_mesh((n,), ("model",))
    # no ambient mesh -> never ring, knob or not
    assert dispatch.resolve_attention(
        "auto", 4096, 4096, ring_axis="model") == "flash"
    with jax.set_mesh(mesh):
        got = dispatch.resolve_attention("auto", 4096, 4096,
                                         ring_axis="model")
        assert got == ("flash_ring" if n > 1 else "flash")
        # knob off -> today's resolution, mesh or not
        assert dispatch.resolve_attention("auto", 4096, 4096) == "flash"
        # non-divisible sequence dims stay on the single-device pick
        assert dispatch.resolve_attention(
            "auto", 4097, 4099, ring_axis="model") == "flash"
        # dualmode is a numerics contract the ring now honors (ISSUE 7):
        # blocked dualmode streams the snapped int kernel, and the ring
        # upgrade applies on top of it exactly like the float path
        assert dispatch.resolve_attention(
            "auto", 4096, 4096, softmax_impl="dualmode",
            ring_axis="model") == (
                "flash_ring" if n > 1 else "flash_pallas_int")
        # short rows never stream, ring or not
        assert dispatch.resolve_attention(
            "auto", 1, 4096, ring_axis="model") == "naive"


def test_explicit_ring_plus_dualmode_resolves_and_matches():
    """ISSUE 7: dualmode + ring is a supported pairing — each hop runs
    the one-sweep snapped kernel and partials fold with the int monoid,
    so the ring output matches the single-device snapped kernel."""
    assert dispatch.resolve_attention(
        "flash_ring", 4096, 4096,
        softmax_impl="dualmode") == "flash_ring"
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >1 device for a ring")
    from repro.kernels.flash_attention_int import flash_attention_pallas_int
    mesh = auto_mesh((n,), ("model",))
    b, s, t = 2, 4 * n, 8 * n
    q, k, v = _mk(b, s, t, 2, 2, 16)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None] + (t - s), (b, s))
    kv_valid = jnp.ones((b, t), bool)
    with jax.set_mesh(mesh):
        got = ring_flash_attention(q, k, v, q_pos=q_pos,
                                   kv_valid=kv_valid,
                                   softmax_impl="dualmode")
    want = flash_attention_pallas_int(q, k, v, q_pos=q_pos,
                                      kv_valid=kv_valid, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


def test_serve_engine_resolves_ring_prefill_per_phase():
    """An engine given a mesh + a ring_axis config resolves long-context
    prefill to the ring path while decode (s_q=1) stays naive."""
    from repro.configs import registry
    from repro.models.transformer import init_lm
    from repro.serve import ServeEngine
    n = len(jax.devices())
    cfg = registry.reduced_config("qwen1.5-0.5b").replace(
        vocab=64, ring_axis="model")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    mesh = auto_mesh((n,), ("model",))
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=4096,
                      prefill_buckets=(2048,), mesh=mesh)
    want_prefill = "flash_ring" if n > 1 else "flash"
    assert eng.prefill_attn_impl == want_prefill
    assert eng.decode_attn_impl == "naive"
    # the compiled prefill runs at EVERY bucket: one non-dividing bucket
    # (36 % ring != 0) must veto the ring for the whole phase, not crash
    # the first short prompt at runtime
    eng2 = ServeEngine(cfg, params, n_slots=2, max_seq=4096,
                       prefill_buckets=(36, 2048), mesh=mesh)
    assert eng2.prefill_attn_impl == "flash"


def test_gqa_layer_forward_through_ring_matches_naive():
    """The full model-layer path (AttnSpec.ring_axis -> _sdpa -> registry
    entry -> shard_map) with an EXPLICIT flash_ring impl under a mesh."""
    from repro.models.attention import AttnSpec, gqa_apply, gqa_init
    mesh, _ = _ring_mesh(32, 32)
    base = dict(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
    spec_ring = AttnSpec(**base, attn_impl="flash_ring",
                         ring_axis="model")
    spec_naive = AttnSpec(**base, attn_impl="naive")
    p = gqa_init(jax.random.PRNGKey(0), spec_ring, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 32, 32)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    with jax.set_mesh(mesh):
        got, _ = gqa_apply(p, spec_ring, x, positions=positions)
    want, _ = gqa_apply(p, spec_naive, x, positions=positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


# ---------------- the 8-device gold test (acceptance criterion) ----------

def test_ring_8dev_parity_vs_single_device_pallas(subproc):
    """flash_ring output and dq/dk/dv vs single-device flash_pallas
    <= 1e-5 on an emulated 8-device mesh — ISSUE 4 acceptance."""
    code = '''
import jax, jax.numpy as jnp
import numpy as np
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ring_attention import ring_flash_attention
from repro.launch.mesh import auto_mesh

rng = np.random.default_rng(3)
b, s, t, kh, g, h, hv = 2, 64, 128, 2, 3, 16, 16
q = jnp.asarray(rng.normal(size=(b, s, kh, g, h)), jnp.float32)
k = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
v = jnp.asarray(rng.normal(size=(b, t, kh, hv)), jnp.float32)
q_pos = jnp.broadcast_to(jnp.arange(t - s, t)[None], (b, s))
valid = jnp.asarray(rng.random((b, t)) > 0.2).at[:, 0].set(True)
mesh = auto_mesh((8,), ("model",))
assert mesh.shape["model"] == 8

out_r = ring_flash_attention(q, k, v, q_pos=q_pos, kv_valid=valid,
                             mesh=mesh, interpret=True)
out_s = flash_attention_pallas(q, k, v, q_pos=q_pos, kv_valid=valid,
                               interpret=True)
d_out = float(jnp.abs(out_r - out_s).max())
assert d_out <= 1e-5, d_out

def g_of(fn):
    return jax.grad(lambda q_, k_, v_: fn(q_, k_, v_).sum(),
                    argnums=(0, 1, 2))(q, k, v)
g_r = g_of(lambda *a: ring_flash_attention(
    *a, q_pos=q_pos, kv_valid=valid, mesh=mesh, interpret=True))
g_s = g_of(lambda *a: flash_attention_pallas(
    *a, q_pos=q_pos, kv_valid=valid, interpret=True))
for name, a, b_ in zip(("dq", "dk", "dv"), g_r, g_s):
    d = float(jnp.abs(a - b_).max())
    assert d <= 1e-5, (name, d)
print("RING_8DEV_OK", d_out)
'''
    assert "RING_8DEV_OK" in subproc(code, n_devices=8)
