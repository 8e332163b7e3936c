"""Split-KV flash-decode kernel + serve-engine decode fast path (ISSUE 5,
dual-mode decode ISSUE 7).

Covers what the parity matrix doesn't: the split-count heuristic, the
dispatch guards (s_q=1 only, 'auto' resolution at decode shapes), the
ragged per-slot tile skip, the dual-mode int split path, and the
engine-level contract — a long-cache ServeEngine resolves its decode
program through ``flash_decode`` (jaxpr-proved) for BOTH float and
dualmode configs, while short caches stay on whole-row naive.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import dispatch, tiling
from repro.kernels.flash_decode import flash_decode_pallas
from repro.models.attention import _naive_sdpa
from repro.models.transformer import init_lm
from repro.serve import Request, ServeEngine
from repro.serve.engine import make_decode_step

RNG = np.random.default_rng(29)


def _mk(b, t, kh, g, h, hv=None, dtype=jnp.float32):
    hv = hv or h
    q = jnp.asarray(RNG.normal(size=(b, 1, kh, g, h)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, kh, h)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, kh, hv)), dtype)
    return q, k, v


# ---------------- kernel ----------------

def test_ragged_slot_depths_match_naive():
    """Every batch row at its own cache depth — the continuous-batching
    shape: the per-row causal tile skip must reproduce the naive mask."""
    b, t = 4, 1024
    q, k, v = _mk(b, t, 2, 2, 16)
    # slot depths spread from nearly-empty to nearly-full bucket
    q_pos = jnp.asarray([[3], [129], [700], [1023]], jnp.int32)
    kv_valid = jnp.arange(t)[None, :] <= q_pos          # (B, T) ragged
    want = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid)
    for ns in (1, 2, 4, 8):
        got = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                  num_splits=ns)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=f"n_splits={ns}")


def test_hv_off_lane_grid():
    """hv=72 exercises the lane-rounded acc scratch (MLA-style v dim)."""
    q, k, v = _mk(1, 200, 1, 2, 16, hv=72)
    q_pos = jnp.full((1, 1), 199, jnp.int32)
    kv_valid = jnp.ones((1, 200), bool)
    want = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid)
    got = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                              num_splits=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_more_splits_than_tiles_emits_sentinels():
    """num_splits beyond the tile count: the surplus splits hold only
    phantom keys, emit the (MASK_VALUE, 0, 0) sentinel, and the merge is
    unchanged — the degenerate end of the split-invariance law."""
    q, k, v = _mk(1, 100, 2, 1, 8)
    q_pos = jnp.full((1, 1), 99, jnp.int32)
    kv_valid = jnp.ones((1, 100), bool)
    ref = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                              num_splits=1)
    got = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                              num_splits=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_rejects_wide_query_tiles():
    q = jnp.zeros((1, 2, 1, 1, 8), jnp.float32)
    k = jnp.zeros((1, 16, 1, 8), jnp.float32)
    v = jnp.zeros((1, 16, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="s_q=1"):
        flash_decode_pallas(q, k, v, q_pos=jnp.zeros((1, 2), jnp.int32),
                            kv_valid=jnp.ones((1, 16), bool))


# ---------------- tiling heuristic ----------------

def test_decode_splits_heuristic():
    """Sized from cache length, capped, and degenerating to 1 split (=
    plain blocked streaming) at short caches."""
    assert tiling.decode_splits(256, max_splits=8) == 1
    assert tiling.decode_splits(2048, max_splits=8) == 1
    assert tiling.decode_splits(4096, max_splits=8) == 2
    assert tiling.decode_splits(16384, max_splits=8) == 8
    assert tiling.decode_splits(65536, max_splits=8) == 8
    assert tiling.decode_splits(65536, max_splits=4) == 4
    # default cap: min(core count, DECODE_MAX_SPLITS), always >= 1
    assert 1 <= tiling.decode_splits(1 << 20) <= tiling.DECODE_MAX_SPLITS


def test_decode_kv_block_lane_aligned():
    for t in (100, 1024, 4096, 65536):
        for ns in (1, 2, 4, 8):
            b = tiling.decode_kv_block(t, ns)
            assert b % tiling.LANE == 0 and b <= 512


# ---------------- dispatch resolution ----------------

def test_auto_resolution_decode_shapes():
    assert dispatch.resolve_attention(
        "auto", 1, tiling.DECODE_FLASH_MIN_KV) == "flash_decode"
    assert dispatch.resolve_attention("auto", 1, 65536) == "flash_decode"
    # short cache: whole-row naive stays
    assert dispatch.resolve_attention("auto", 1, 256) == "naive"
    # dualmode decode: flash_decode routes to the int split path inside
    # the entry — the unit streams split-KV instead of whole-row naive
    assert dispatch.resolve_attention(
        "auto", 1, 65536, softmax_impl="dualmode") == "flash_decode"
    # wide-q shapes never pick the decode kernel
    assert dispatch.resolve_attention("auto", 2, 65536) != "flash_decode"


def test_auto_decode_pick_is_mesh_gated():
    """flash_decode is a single-device kernel: under an ambient mesh
    (sharded serving, the 512-device dry-run cells) an unshardable
    pallas_call would gather every slot's full cache per chip, so the
    'auto' decode pick stays on the shardable whole-row naive graph."""
    from repro.launch.mesh import auto_mesh
    assert dispatch.resolve_attention("auto", 1, 65536) == "flash_decode"
    mesh = auto_mesh((len(jax.devices()),), ("model",))
    with jax.set_mesh(mesh):
        assert dispatch.resolve_attention("auto", 1, 65536) == "naive"
    assert dispatch.resolve_attention("auto", 1, 65536) == "flash_decode"


def test_explicit_flash_decode_dualmode_resolves_and_runs():
    """ISSUE 7: dualmode + flash_decode is a supported pairing — it
    resolves, and the entry runs the snapped int split path whose output
    matches the naive whole-row SNAPPED unit (word-identical recurrence,
    f32 numerator@v order the only slack)."""
    assert dispatch.resolve_attention(
        "flash_decode", 1, 4096, softmax_impl="dualmode") == "flash_decode"
    b, t = 2, 512
    q, k, v = _mk(b, t, 2, 2, 16)
    q_pos = jnp.asarray([[100], [511]], jnp.int32)
    kv_valid = jnp.arange(t)[None, :] <= q_pos
    entry = dispatch.get_attention("flash_decode")
    got = entry(q, k, v, q_pos=q_pos, kv_valid=kv_valid, causal=True,
                scale=None, softmax_impl="dualmode")
    want = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                       softmax_impl="dualmode_snap")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and vs the CLASSIC whole-row unit: the max-quantization bound
    want_c = _naive_sdpa(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                         softmax_impl="dualmode")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_c),
                               atol=2e-3)


def test_dualmode_decode_split_invariance():
    """The int monoid fold: WHERE the cache splits cannot change words."""
    b, t = 2, 1024
    q, k, v = _mk(b, t, 2, 2, 16)
    q_pos = jnp.asarray([[40], [1000]], jnp.int32)
    kv_valid = jnp.arange(t)[None, :] <= q_pos
    ref = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                              num_splits=1, softmax_impl="dualmode")
    for ns in (2, 4, 8):
        got = flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                  num_splits=ns, softmax_impl="dualmode")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, err_msg=f"n_splits={ns}")


# ---------------- serve engine fast path ----------------

def test_engine_decode_resolves_flash_decode_at_long_kv():
    """Long-cache engine: decode resolves the split-KV kernel and the
    jitted decode step really routes through it (a pallas_call in the
    jaxpr); short-cache and dualmode engines stay on naive."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=2048,
                      prefill_buckets=(8,), cache_mode="contiguous")
    assert eng.decode_attn_impl == "flash_decode"
    step = make_decode_step(cfg.replace(attn_impl=eng.decode_attn_impl))
    toks = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray([5, 9], jnp.int32)
    jaxpr = jax.make_jaxpr(step)(params, eng.caches, toks, pos)
    assert "pallas_call" in str(jaxpr), \
        "decode step does not route through the flash_decode kernel"
    # short cache: naive decode, and NO pallas_call in its decode step
    short = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                        prefill_buckets=(8,), cache_mode="contiguous")
    assert short.decode_attn_impl == "naive"
    jaxpr_s = jax.make_jaxpr(make_decode_step(
        cfg.replace(attn_impl=short.decode_attn_impl)))(
        params, short.caches, toks, pos)
    assert "pallas_call" not in str(jaxpr_s)
    # dualmode engine decode takes the split-KV fast path too (ISSUE 7:
    # the int monoid made flash_decode softmax-aware)
    dual = ServeEngine(cfg.replace(softmax_impl="dualmode"), params,
                      n_slots=2, max_seq=2048, prefill_buckets=(8,),
                      cache_mode="contiguous")
    assert dual.decode_attn_impl == "flash_decode"


def test_engine_decode_step_logits_match_naive():
    """The fast path is numerics-neutral: one batched decode step through
    flash_decode matches the naive decode step's logits at mixed slot
    depths (the ragged continuous-batching state)."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=1024,
                      prefill_buckets=(8,), cache_mode="contiguous")
    assert eng.decode_attn_impl == "flash_decode"
    # mixed-depth slots over a prefilled cache
    outs = eng.run([Request(rid=0, prompt=[1, 2, 3], max_new=2),
                    Request(rid=1, prompt=[5] * 7, max_new=2),
                    Request(rid=2, prompt=[4, 9], max_new=2)])
    assert sorted(outs) == [0, 1, 2]
    toks = jnp.asarray([[3], [7], [11]], jnp.int32)
    pos = jnp.asarray([4, 8, 3], jnp.int32)
    fast = make_decode_step(cfg.replace(attn_impl="flash_decode"))
    slow = make_decode_step(cfg.replace(attn_impl="naive"))
    lf, _ = fast(params, eng.caches, toks, pos)
    ls, _ = slow(params, eng.caches, toks, pos)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ls), atol=2e-4)
