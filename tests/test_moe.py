"""MoE dispatch: sort-path and serving path vs dense oracle, capacity
semantics, aux loss.  ``dropless=True`` is the serving path (held experts
through ragged_dot, aux = held rows); the training path's 'sort' dispatch
drops nothing when its capacity factor is n_experts / top_k."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import MoESpec, moe_apply, moe_init


def _spec(**kw):
    base = dict(d_model=32, d_ff=64, n_experts=4, top_k=2, n_shared=0,
                capacity_factor=1.25, activation="silu", dispatch="sort")
    base.update(kw)
    return MoESpec(**base)


NO_DROP = 4 / 2            # capacity_factor giving cap = S: n_experts/top_k


def _x(b=2, s=8, d=32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, d)) * 0.5


def test_sort_dropless_matches_dense():
    s_sort = _spec(capacity_factor=NO_DROP)
    s_dense = _spec(dispatch="dense")
    p = moe_init(jax.random.PRNGKey(1), s_sort, jnp.float32)
    x = _x()
    y_sort, aux1 = moe_apply(p, s_sort, x)
    y_dense, aux2 = moe_apply(p, s_dense, x)
    y_serve, held = moe_apply(p, s_sort, x, dropless=True)
    np.testing.assert_allclose(np.asarray(y_sort), np.asarray(y_dense),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_serve), np.asarray(y_dense),
                               atol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux2), atol=1e-6)
    assert float(held) == 2 * 8 * 2          # every routed row is held


def test_capacity_drops_tokens_when_tight():
    s_tight = _spec(capacity_factor=0.25)
    p = moe_init(jax.random.PRNGKey(1), s_tight, jnp.float32)
    x = _x()
    y_tight, _ = moe_apply(p, s_tight, x)
    y_free, _ = moe_apply(p, s_tight, x, dropless=True)
    # with tight capacity SOME token outputs must differ (drops)
    assert float(jnp.abs(y_tight - y_free).max()) > 1e-6


def test_shared_experts_added():
    s = _spec(n_shared=1)
    p = moe_init(jax.random.PRNGKey(2), s, jnp.float32)
    x = _x()
    y, _ = moe_apply(p, s, x, dropless=True)
    # zeroing shared expert changes output
    p2 = dict(p)
    p2["shared"] = jax.tree.map(jnp.zeros_like, p["shared"])
    y2, _ = moe_apply(p2, s, x, dropless=True)
    assert float(jnp.abs(y - y2).max()) > 1e-6


def test_aux_loss_uniform_router_is_one():
    """Balanced routing gives aux ~ 1 (Switch normalization)."""
    s = _spec(n_experts=8, top_k=2)
    p = moe_init(jax.random.PRNGKey(3), s, jnp.float32)
    p["router"] = jnp.zeros_like(p["router"])      # uniform probs
    x = _x(b=8, s=32)
    _, aux = moe_apply(p, s, x)
    assert abs(float(aux) - 1.0) < 0.2


def test_custom_vjp_matches_dense_oracle_grads():
    """The dispatch/combine custom VJPs (built to keep GSPMD-friendly
    scatter forms in backward) must match autodiff of the dense path."""
    s_sort = _spec(capacity_factor=NO_DROP)
    s_dense = _spec(dispatch="dense")
    p = moe_init(jax.random.PRNGKey(7), s_sort, jnp.float32)
    x = _x(seed=9)
    tgt = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def loss(p_, spec):
        y, aux = moe_apply(p_, spec, x)
        return jnp.sum((y - tgt) ** 2) + 0.1 * aux

    g_sort = jax.grad(loss)(p, s_sort)
    g_dense = jax.grad(loss)(p, s_dense)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4),
        g_sort, g_dense)

    gx_sort = jax.grad(lambda x_: jnp.sum(
        moe_apply(p, s_sort, x_)[0] ** 2))(x)
    gx_dense = jax.grad(lambda x_: jnp.sum(
        moe_apply(p, s_dense, x_)[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(gx_sort), np.asarray(gx_dense),
                               atol=2e-4, rtol=2e-4)


def test_moe_grads_flow_to_experts():
    s = _spec()
    p = moe_init(jax.random.PRNGKey(4), s, jnp.float32)
    x = _x()

    def loss(p_):
        y, aux = moe_apply(p_, s, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    gn = float(jnp.abs(g["gate"]).sum() + jnp.abs(g["router"]).sum())
    assert np.isfinite(gn) and gn > 0
