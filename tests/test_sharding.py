"""Sharding rules: spec validity for every arch, FSDP wrap, cache SP
fallback, and an 8-device execution equivalence test (sharded == single)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.models.transformer import init_caches, init_lm


def _check_tree(mesh_shape, axis_names, specs, shapes):
    sizes = dict(zip(axis_names, mesh_shape))

    def check(path, leaf, spec):
        assert len(spec) <= len(leaf.shape), (path, leaf.shape, spec)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= sizes[a]
            assert leaf.shape[i] % n == 0, (path, leaf.shape, spec)
    jax.tree_util.tree_map_with_path(check, shapes, specs)


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_specs_divisible_all_archs(arch, fsdp, subproc=None):
    # use FULL configs: this is exactly what the production mesh sees
    code_mesh = (16, 16)
    import repro.distributed.sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    cfg = registry.get_config(arch)
    shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    specs = sh.param_pspecs(shapes, FakeMesh(), fsdp=fsdp)
    _check_tree(code_mesh, ("data", "model"), specs, shapes)


@pytest.mark.parametrize("arch", ["qwen3-14b", "jamba-v0.1-52b",
                                  "deepseek-v2-lite-16b", "rwkv6-1.6b"])
@pytest.mark.parametrize("batch", [1, 32, 128])
def test_cache_specs_divisible(arch, batch):
    import repro.distributed.sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    cfg = registry.get_config(arch)
    shapes = jax.eval_shape(lambda: init_caches(cfg, batch, 2048))
    specs = sh.cache_pspecs(shapes, FakeMesh(), batch)
    _check_tree((16, 16), ("data", "model"), specs, shapes)
    if batch == 1 and arch != "rwkv6-1.6b":
        # SP fallback: some KV-cache seq dim must be sharded over 'data'
        # (rwkv has no seq-dim caches — O(1) recurrent state only)
        found = []
        jax.tree_util.tree_map_with_path(
            lambda p, s: found.append("data" in tuple(s)), specs,
            is_leaf=lambda x: isinstance(x, P))
        assert any(found)


@pytest.mark.parametrize("max_seq", [2048, 2050])
def test_cache_specs_ring_axis_shards_kv_sequence(max_seq):
    """ISSUE 4 bugfix regression: with a ring_axis, KV-cache sequence
    dims shard over that axis (so ring shards place where the rotation
    expects them) — guarded, so a non-divisible sequence (2050 % 16 != 0)
    replicates instead of silently padding — and the same axis is never
    booked twice in one spec (head dims yield to the ring)."""
    import repro.distributed.sharding as sh

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 16}
    cfg = registry.get_config("qwen3-14b")
    batch = 4                                  # divisible: batch-DP active
    shapes = jax.eval_shape(lambda: init_caches(cfg, batch, max_seq))
    specs = sh.cache_pspecs(shapes, FakeMesh(), batch, ring_axis="model")
    _check_tree((2, 16), ("data", "model"), specs, shapes)
    divisible = max_seq % 16 == 0

    def seq_axes(spec_tree):
        """(kv-seq-dim axis, spec) per k/v leaf + a double-booking scan."""
        seqs, booked = [], []

        def visit(path, spec):
            parts = [p for p in tuple(spec) if p is not None]
            booked.append(len(parts) != len(set(parts)))
            names = [str(getattr(e, "key", getattr(e, "idx", "")))
                     for e in path]
            if names and names[-1] in ("k", "v"):
                seq_idx = 2 if "periods" in names else 1
                seqs.append(spec[seq_idx] if len(spec) > seq_idx else None)
        jax.tree_util.tree_map_with_path(
            visit, spec_tree, is_leaf=lambda x: isinstance(x, P))
        return seqs, booked

    seqs, booked = seq_axes(specs)
    assert not any(booked)
    assert seqs and all(
        (s == "model") == divisible for s in seqs), seqs
    # without the knob the old behavior is untouched: batch-DP shards,
    # sequence dims stay unsharded
    base_seqs, base_booked = seq_axes(sh.cache_pspecs(shapes, FakeMesh(),
                                                      batch))
    assert not any(base_booked)
    assert base_seqs and all(s is None for s in base_seqs)


def test_tp_sharded_training_matches_single_device(subproc):
    """Gold test: loss on a (2,4) DP x TP mesh == unsharded loss."""
    code = '''
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data import SyntheticLM
from repro.models.transformer import init_lm
from repro.optim import adamw_init
from repro.train.step import TrainState, make_train_step, state_pspecs
from repro.launch.mesh import auto_mesh

cfg = registry.reduced_config("qwen3-14b").replace(vocab=128)
tcfg = TrainConfig(lr=1e-3, remat=True)
ds = SyntheticLM(vocab=128, seq_len=32, global_batch=8)
t, l = ds.batch(0)
batch = {"tokens": t, "labels": l}
params = init_lm(jax.random.PRNGKey(0), cfg)
state = TrainState(params, adamw_init(params), {})

# single-device reference
s1, m1 = jax.jit(make_train_step(cfg, tcfg))(state, batch)

# sharded
mesh = auto_mesh((2, 4), ("data", "model"))
_, spec = state_pspecs(cfg, tcfg, mesh)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                  is_leaf=lambda x: isinstance(x, P))
state_sh = jax.device_put(state, sh)
bsh = NamedSharding(mesh, P("data", None))
batch_sh = jax.tree.map(lambda x: jax.device_put(x, bsh), batch)
with jax.set_mesh(mesh):
    step = jax.jit(make_train_step(cfg, tcfg, mesh),
                   in_shardings=(sh, bsh), out_shardings=(sh, None))
    s2, m2 = step(state_sh, batch_sh)
np.testing.assert_allclose(float(m1["ce"]), float(m2["ce"]), rtol=2e-5)
np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                           rtol=1e-3)
d = jax.tree.reduce(jnp.maximum, jax.tree.map(
    lambda a, b: jnp.abs(a - b).max(), s1.params,
    jax.device_get(s2.params)))
assert float(d) < 3e-5, float(d)
print("TP_EQUIV_OK", float(m2["ce"]))
'''
    out = subproc(code, n_devices=8)
    assert "TP_EQUIV_OK" in out


def test_moe_ep_sharded_matches_single(subproc):
    """Expert-parallel MoE arch on a mesh == single device."""
    code = '''
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data import SyntheticLM
from repro.models.transformer import init_lm
from repro.optim import adamw_init
from repro.train.step import TrainState, make_train_step, state_pspecs
from repro.launch.mesh import auto_mesh

cfg = registry.reduced_config("granite-moe-3b-a800m").replace(vocab=128)
tcfg = TrainConfig(lr=1e-3, remat=False)
ds = SyntheticLM(vocab=128, seq_len=16, global_batch=4)
t, l = ds.batch(0)
batch = {"tokens": t, "labels": l}
params = init_lm(jax.random.PRNGKey(0), cfg)
state = TrainState(params, adamw_init(params), {})
_, m1 = jax.jit(make_train_step(cfg, tcfg))(state, batch)
mesh = auto_mesh((2, 4), ("data", "model"))
_, spec = state_pspecs(cfg, tcfg, mesh)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                  is_leaf=lambda x: isinstance(x, P))
state_sh = jax.device_put(state, sh)
bsh = NamedSharding(mesh, P("data", None))
batch_sh = jax.tree.map(lambda x: jax.device_put(x, bsh), batch)
with jax.set_mesh(mesh):
    _, m2 = jax.jit(make_train_step(cfg, tcfg, mesh),
                    in_shardings=(sh, bsh), out_shardings=(sh, None)
                    )(state_sh, batch_sh)
np.testing.assert_allclose(float(m1["ce"]), float(m2["ce"]), rtol=5e-5)
print("EP_EQUIV_OK")
'''
    out = subproc(code, n_devices=8)
    assert "EP_EQUIV_OK" in out
