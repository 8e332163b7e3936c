"""Paged KV cache: BlockPool allocator invariants (property-tested),
pool write/gather round-trips, the block-table flash-decode kernel's
parity against the pure-JAX paged fold oracle and the dense paths on the
stacked lane-dense pool (L, N, bs, K*h) read at several layers, and the
engine's donation of the pool to its step programs.

The allocator property test is hypothesis-compatible: when the
`hypothesis` package is present the operation sequences are drawn by it;
otherwise a seeded PRNG drives the SAME property function (no dependency
is installed for this — the image decides)."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import dispatch, tiling
from repro.kernels.flash_decode import (flash_decode_paged,
                                        flash_decode_pallas)
from repro.models.attention import paged_gather, paged_write
from repro.models.flash import flash_attention_paged_ref
from repro.models.transformer import init_lm
from repro.serve import Request, ServeEngine
from repro.serve.paged_cache import BlockPool, chain_hashes

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------- allocator properties ----------------

def _pool_invariants(pool: BlockPool):
    live = set(pool._ref)
    free = set(pool._free)
    cached = set(pool._cached)
    # block 0 is the write sentinel: never allocatable, never live
    assert 0 not in live and 0 not in free and 0 not in cached
    # no block is simultaneously live/free/cached (no double-alloc)
    assert not (live & free) and not (live & cached) and not (free & cached)
    # no leak: every non-sentinel block is in exactly one of the sets
    assert live | free | cached == set(range(1, pool.num_blocks))
    assert all(r >= 1 for r in pool._ref.values())


def _run_ops(ops):
    """Interpret a sequence of (op, arg) against a small pool, checking
    invariants after every step.  Ops: alloc n / free i-th held ref /
    share (re-take refs on a registered prefix) / register held blocks."""
    pool = BlockPool(num_blocks=9, block_size=4)
    held = []                 # (block, token_prefix_hash) refs we own
    registered = []           # hash chains we registered
    next_tok = [0]
    for op, arg in ops:
        if op == "alloc":
            got = pool.alloc(arg)
            if got is not None:
                assert len(got) == arg
                assert len(set(got)) == arg          # no dup in one grant
                for b in got:
                    held.append(b)
            else:
                assert pool.available() < arg        # refusal was honest
        elif op == "free" and held:
            pool.decref(held.pop(arg % len(held)))
        elif op == "register" and held:
            toks = list(range(next_tok[0], next_tok[0] + 4))
            next_tok[0] += 4
            hs = chain_hashes(toks, 4)
            b = held[arg % len(held)]
            pool.register(hs, [b])
            registered.append((hs, b))
        elif op == "share" and registered:
            hs, b = registered[arg % len(registered)]
            got = pool.match_prefix(hs)
            for g in got:
                held.append(g)
        _pool_invariants(pool)
    # refcount round-trip: dropping every held ref empties the live set
    for b in held:
        pool.decref(b)
    _pool_invariants(pool)
    assert pool.in_use() == 0
    assert pool.available() == pool.num_blocks - 1


_OP_NAMES = ("alloc", "free", "register", "share")


def _random_ops(seed, n=60):
    rng = random.Random(seed)
    return [(rng.choice(_OP_NAMES), rng.randrange(6)) for _ in range(n)]


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(_OP_NAMES),
                              st.integers(0, 5)), max_size=80))
    def test_block_pool_invariants(ops):
        _run_ops(ops)
else:
    @pytest.mark.parametrize("seed", range(25))
    def test_block_pool_invariants(seed):
        _run_ops(_random_ops(seed))


def test_block_pool_alloc_all_or_nothing():
    pool = BlockPool(num_blocks=5, block_size=4)
    got = pool.alloc(4)
    assert got is not None and len(got) == 4
    assert pool.alloc(1) is None                 # empty: refuse
    assert pool.in_use() == 4                    # and nothing half-taken
    pool.decref(got[0])
    assert pool.alloc(2) is None                 # still short: refuse whole
    assert pool.alloc(1) == [got[0]]


def test_block_pool_prefix_revival_and_eviction():
    """Refcount-0 registered blocks stay matchable (LRU cache) until
    capacity pressure evicts them — then the hash is gone too."""
    pool = BlockPool(num_blocks=4, block_size=2)
    hs = chain_hashes([1, 2, 3, 4], 2)
    blocks = pool.alloc(2)
    pool.register(hs, blocks)
    for b in blocks:
        pool.decref(b)
    assert pool.in_use() == 0
    assert pool.match_prefix(hs) == blocks       # revived from the LRU
    for b in blocks:
        pool.decref(b)
    assert pool.alloc(3) is not None             # evicts both cached blocks
    assert pool.match_prefix(hs) == []           # index dropped on eviction


def test_chain_hashes_left_context_sensitivity():
    # same block tokens, different left context -> different hash
    a = chain_hashes([1, 2, 3, 4, 5, 6], 2)
    b = chain_hashes([9, 9, 3, 4, 5, 6], 2)
    assert a[0] != b[0] and a[1] != b[1] and a[2] != b[2]
    assert chain_hashes([1, 2, 3], 2) == a[:1]   # partial block: no hash


# ---------------- pool write / gather ----------------

@pytest.mark.parametrize("layer", [0, 2])
def test_paged_write_gather_round_trip(layer):
    key = jax.random.PRNGKey(0)
    bs, nblk, b, n_layers = 8, 4, 3, 3
    pool = jnp.zeros((n_layers, 1 + b * nblk, bs, 2 * 4), jnp.float32)
    # shuffled physical layout: logical order != physical order
    tables = jnp.asarray(np.random.RandomState(0).permutation(
        np.arange(1, 1 + b * nblk)).reshape(b, nblk).astype(np.int32))
    new = jax.random.normal(key, (b, 13, 2, 4))
    pool = paged_write(pool, new, jnp.asarray([0, 3, 19]), tables, layer)
    dense = paged_gather(pool, tables, layer)
    for i, off in enumerate([0, 3, 19]):
        np.testing.assert_array_equal(
            np.asarray(dense[i, off:off + 13]),
            np.asarray(new[i]).reshape(13, 2 * 4))
    # only layer `layer` of the stack was written
    others = np.delete(np.asarray(pool), layer, axis=0)
    assert not others.any()
    # out-of-range rows (pos 19 + 13 == 32 == capacity) never touched
    # the sentinel guard: writing past the table clamps to block 0
    over = paged_write(pool, new, jnp.asarray([25, 25, 25]), tables, layer)
    np.testing.assert_array_equal(np.asarray(paged_gather(over, tables,
                                                          layer)[:, :25]),
                                  np.asarray(dense[:, :25]))


# ---------------- kernel parity ----------------

N_LAYERS = 3


def _mk_paged_case(seed, b, kh, g, hd, hv, nblk, bs, shuffle=True):
    """q, stacked lane-dense K/V pools (N_LAYERS, N, bs, K*h|K*hv) with
    different random data in every layer, shuffled tables, ragged
    positions."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n_pool = 1 + b * nblk
    q = jax.random.normal(ks[0], (b, 1, kh, g, hd))
    k_pool = jax.random.normal(ks[1], (N_LAYERS, n_pool, bs, kh * hd))
    v_pool = jax.random.normal(ks[2], (N_LAYERS, n_pool, bs, kh * hv))
    ids = np.arange(1, n_pool)
    if shuffle:
        ids = np.random.RandomState(seed).permutation(ids)
    tables = jnp.asarray(ids.reshape(b, nblk).astype(np.int32))
    t = nblk * bs
    q_pos = jax.random.randint(ks[3], (b, 1), 0, t)
    kv_valid = jnp.arange(t)[None, :] <= q_pos
    return q, k_pool, v_pool, tables, q_pos, kv_valid


def _layer(pool, layer, kh):
    """Layer ``layer`` of a stacked lane-dense pool as the (N, bs, K, h)
    pool the fold oracle reads."""
    return pool[layer].reshape(pool.shape[1:3] + (kh, -1))


def _dense(pool, tables, layer, kh):
    """The (B, T, K, h) cache the contiguous kernel reads."""
    d = paged_gather(pool, tables, layer)
    return d.reshape(d.shape[:2] + (kh, -1))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("num_splits", [1, 2, 4])
@pytest.mark.parametrize("gqa", [(4, 1), (2, 3)])
def test_flash_decode_paged_matches_oracle_and_dense(num_splits, gqa, layer):
    """The block-table kernel == the pure-JAX paged fold oracle == the
    dense split-KV kernel fed a gathered cache — with PHYSICALLY
    SHUFFLED tables, so any confusion of physical block id with logical
    position shows up as a mismatch, and a layer index into a stack
    whose every layer holds other keys."""
    kh, g = gqa
    q, k_pool, v_pool, tables, q_pos, kv_valid = _mk_paged_case(
        1, b=3, kh=kh, g=g, hd=16, hv=16, nblk=8, bs=16)
    got = flash_decode_paged(q, k_pool, v_pool, block_tables=tables,
                             layer=layer, q_pos=q_pos, kv_valid=kv_valid,
                             num_splits=num_splits, interpret=True)
    ref = flash_attention_paged_ref(
        q, _layer(k_pool, layer, kh), _layer(v_pool, layer, kh),
        block_tables=tables, q_pos=q_pos, kv_valid=kv_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    dense = flash_decode_pallas(q, _dense(k_pool, tables, layer, kh),
                                _dense(v_pool, tables, layer, kh),
                                q_pos=q_pos, kv_valid=kv_valid,
                                num_splits=num_splits, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=1e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_flash_decode_paged_mla_head_dims(layer):
    # MLA decode shape: shared latent head, hv != hd
    q, k_pool, v_pool, tables, q_pos, kv_valid = _mk_paged_case(
        2, b=2, kh=1, g=4, hd=24, hv=16, nblk=4, bs=16)
    got = flash_decode_paged(q, k_pool, v_pool, block_tables=tables,
                             layer=layer, q_pos=q_pos, kv_valid=kv_valid,
                             interpret=True)
    ref = flash_attention_paged_ref(
        q, _layer(k_pool, layer, 1), _layer(v_pool, layer, 1),
        block_tables=tables, q_pos=q_pos, kv_valid=kv_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_flash_decode_paged_table_permutation_invariance():
    """Permuting PHYSICAL block placement (and the tables with it) must
    not change a single output word — masking is logical-position-only."""
    q, k_pool, v_pool, tables, q_pos, kv_valid = _mk_paged_case(
        3, b=2, kh=2, g=2, hd=16, hv=16, nblk=4, bs=16, shuffle=False)
    base = flash_decode_paged(q, k_pool, v_pool, block_tables=tables,
                              layer=1, q_pos=q_pos, kv_valid=kv_valid,
                              interpret=True)
    n = k_pool.shape[1]
    perm = np.random.RandomState(7).permutation(n - 1) + 1
    inv = np.zeros(n, np.int32)
    inv[perm] = np.arange(1, n)
    k2 = jnp.concatenate([k_pool[:, :1], k_pool[:, perm]], 1)
    v2 = jnp.concatenate([v_pool[:, :1], v_pool[:, perm]], 1)
    t2 = jnp.asarray(inv)[tables]
    moved = flash_decode_paged(q, k2, v2, block_tables=t2, layer=1,
                               q_pos=q_pos, kv_valid=kv_valid,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))


@pytest.mark.parametrize("mode", ["dualmode", "float"])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_registry_entry(layer, mode):
    fn = dispatch.get_paged_attention("flash_decode")
    assert fn is not None
    assert dispatch.get_paged_attention("naive") is None
    # dualmode on the paged entry runs the snapped int split path (ISSUE 7)
    # and matches the dense decode on the gathered cache tiled at the
    # block size BIT FOR BIT: same words, same split fold, same per-head
    # arithmetic — the lane-dense pool and the block tables only change
    # the addressing
    q, k_pool, v_pool, tables, q_pos, kv_valid = _mk_paged_case(
        4, b=2, kh=2, g=2, hd=16, hv=16, nblk=4, bs=16)
    got = fn(q, k_pool, v_pool, block_tables=tables, layer=layer,
             q_pos=q_pos, kv_valid=kv_valid, causal=True, scale=None,
             softmax_impl=mode)
    dense = flash_decode_pallas(q, _dense(k_pool, tables, layer, 2),
                                _dense(v_pool, tables, layer, 2),
                                q_pos=q_pos, kv_valid=kv_valid, block_kv=16,
                                interpret=True, softmax_impl=mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))


# ---------------- engine fast path (paged) ----------------

def test_paged_engine_decode_routes_through_kernel():
    """A long-cache PAGED engine resolves flash_decode and its compiled
    decode step contains the pallas_call — the block-table gather is the
    kernel's scalar-prefetch index map, not a dense materialization."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2,
                      max_seq=tiling.DECODE_FLASH_MIN_KV,
                      cache_mode="paged")
    assert eng.decode_attn_impl == "flash_decode"
    toks = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray([5, 9], jnp.int32)
    tables = jnp.zeros((2, eng.max_blocks), jnp.int32)
    from repro.serve.engine import make_paged_decode_step
    jaxpr = str(jax.make_jaxpr(make_paged_decode_step(
        cfg.replace(attn_impl="flash_decode")))(
        params, eng.caches, toks, pos, tables))
    assert "pallas_call" in jaxpr
    # ...and a gather of the full pool into a dense (B,T,...) cache is
    # exactly what the kernel avoids: no reshape to the dense kv shape
    out = eng.run([Request(rid=0, prompt=[1, 2, 3], max_new=3),
                   Request(rid=1, prompt=[4, 5], max_new=3)])
    ref = ServeEngine(cfg, params, n_slots=2,
                      max_seq=tiling.DECODE_FLASH_MIN_KV,
                      cache_mode="contiguous", prefill_buckets=(8,)).run(
        [Request(rid=0, prompt=[1, 2, 3], max_new=3),
         Request(rid=1, prompt=[4, 5], max_new=3)])
    assert out == ref


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b"])
def test_paged_steps_donate_the_pool(arch):
    """The chunk-prefill and decode programs consume the caches they are
    handed — every buffer of the caches passed in is deleted after the
    step, the engine holds the updated pools — and the donated engine
    still serves the contiguous engine's greedy tokens (deepseek's first
    layer is a prefix pool, a stack of one)."""
    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                      cache_mode="paged", prefill_chunk=16)
    reqs = [Request(rid=0, prompt=list(range(3, 12)), max_new=4),
            Request(rid=1, prompt=[5, 1, 4], max_new=5)]
    eng.submit(reqs[0])
    before = eng.caches
    eng.step()                   # one prefill chunk, then a decode tick
    assert eng.stats["prefill_chunks"] == 1
    assert eng.stats["decode_steps"] == 1
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    before = eng.caches
    eng.step()                   # decode only
    assert eng.stats["prefill_chunks"] == 1
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.caches))
    out = eng.run(reqs[1:])
    ref = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                      cache_mode="contiguous", prefill_buckets=(16,)).run(
        [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
         for r in reqs])
    assert out == ref
