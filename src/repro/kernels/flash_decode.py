"""Split-KV Pallas flash-decode kernel — the s_q=1 serving fast path.

Decode is the shape the blocked flash kernels are worst at: one query row
per head means the q-tile grid axis degenerates and the whole KV cache is
streamed by a single sequential sweep.  "Flash decoding" recovers
parallelism from the only dimension left — the KEYS: the cache is carved
into ``num_splits`` independent grid cells, each runs the standard
blocked online-softmax sweep (the same
:func:`repro.kernels.datapath.online_softmax_update` step every other
flash flavor runs) into a self-contained partial state ``(m, l, o·l)``,
and the partials fold with
:func:`repro.kernels.datapath.online_softmax_merge_n` — the vectorized
n-way form of the partial-merge monoid the ring uses, so the merged words
are pinned against ``models/flash.flash_attention_merged`` in tests.

Two decode-specific specializations on top of the generic kernel:

  * The G query groups of a KV head become the score-tile ROWS (the
    single query row broadcast over groups), so GQA decode still feeds
    the MXU a (G, block_kv) tile instead of a 1-row sliver.
  * Ragged continuous batching: each batch row carries its own cache
    depth via ``q_pos`` (the serving engine's per-slot ``pos`` vector).
    Causal KV tiles that start beyond a row's position are skipped with
    ``pl.when`` — a slot at depth 500 in a 64k bucket does ~1 tile of
    work per split, not the longest slot's full bucket.  Skipped tiles
    drop only the exp(MASK_VALUE) ~ 1e-13 relative mass of fully-masked
    keys (the same approximation ring attention's hop skip makes).

Shapes match every other flash flavor, with S pinned to 1:

    q (B, 1, K, G, h)   k (B, T, K, h)   v (B, T, K, hv) -> (B, 1, K, G, hv)

Masking reuses :func:`flash_attention.masked_score_block` — user-invalid
keys take ``datapath.MASK_VALUE``, tiling phantoms take ``-inf`` — so
decode can never disagree with the other implementations on which keys
are "off".  Forward-only: decode never differentiates.  Runs on CPU with
``interpret=True`` (the default on the CPU backend).

Layout: the contiguous cache stays token-major (B, T, K, h); each grid
step (batch row, split, kv tile) reads one (block_kv, K, h) block holding
EVERY kv head — the only block of that layout whose last two dims the
TPU accepts — and loops over the heads inside the kernel.  The paged pool
is lane-dense and stacked over layers, (L, N, bs, K*h): its TPU default
layout is row-major, so the kernel reads it as it is stored, one
(bs, K*h) block per grid step at (layer, table[b, tile]), and splits the
heads by lane slices.  Positions, the block table and the layer index
ride as scalar prefetch, per-tile validity as one whole-row block per
batch row.

DUAL-MODE decode (``softmax_impl='dualmode'``): the same split-KV grid
runs the snapped-max INT recurrence instead — score words via
``flash_attention_int.int_score_words``, per-tile state update via
``flash_attention_int.snap_tile_update``, and the per-split partial is
the int monoid state ``(m snapped, S buckets, acc)`` folded host-side by
:func:`repro.core.softmax_unit.online_merge_n_int` (the int twin of
``online_softmax_merge_n``).  The causal tile skip carries over: for the
int unit a skipped tile's keys sit >= 16 octaves below any live max, so
they contribute zero words to the normalizer l; only their ~2**-40 f32
numerator mass is dropped (the same order of approximation as the float
path's exp(MASK_VALUE) drop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import softmax_unit as unit

from . import datapath as dp
from . import dispatch, tiling
from .flash_attention import masked_score_block
from .flash_attention_int import int_score_words, snap_tile_update


def _kv_head(ref, h: int, width: int):
    """(bkv, width) keys or values of kv head ``h`` from a token-major
    (1, bkv, K, width) cache block or a lane-dense (1, bkv, K*width) pool
    block (the head is then a static lane slice)."""
    if len(ref.shape) == 4:
        return ref[0, :, h, :]
    return ref[0, :, h * width:(h + 1) * width]


def _decode_body(qpos_ref, valid_ref, q_ref, k_ref, v_ref, om_ref, ol_ref,
                 oacc_ref, m_ref, l_ref, acc_ref, *, block_kv: int,
                 inner: int, causal: bool, t_kv: int):
    """One (batch, split, kv-tile) grid cell over EVERY kv head.

    K/V blocks carry all heads (the full (K, h) extents of the token-major
    cache, or the full K*h lanes of the paged pool, both of which the TPU
    block rule accepts); the head loop runs here, one (G, bkv) score tile
    per head.  The kv-tile axis is innermost, so the (m, l, acc) VMEM scratch
    streams one split's tiles sequentially; at the split's last tile the
    UNNORMALIZED partial (m, l, acc = o·l) is written out for the n-way
    fold.
    """
    b = pl.program_id(0)
    sp = pl.program_id(1)
    kj = pl.program_id(2)
    n_heads, g, hd = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    hv = oacc_ref.shape[-1]
    kv_tile = sp * inner + kj
    q_pos = qpos_ref[b]

    @pl.when(kj == 0)
    def _():
        # empty-split sentinel (MASK_VALUE, 0, 0): splits whose every tile
        # is skipped/phantom emit the merge identity, not garbage
        m_ref[...] = jnp.full_like(m_ref, dp.MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update():
        valid = valid_ref[0, pl.ds(kv_tile, 1), :]         # (1, bkv)
        for h in range(n_heads):
            q = q_ref[0, 0, h].astype(jnp.float32)         # (G, h) pre-scaled
            kb = _kv_head(k_ref, h, hd).astype(jnp.float32)  # (bkv, h)
            vb = _kv_head(v_ref, h, hv).astype(jnp.float32)  # (bkv, hv)
            s, _ = masked_score_block(q, kb, q_pos, valid, kv_tile,
                                      block_kv=block_kv, causal=causal,
                                      t_kv=t_kv)
            m, l = m_ref[h, :g, :1], l_ref[h, :g, :1]      # (G, 1)
            m_new, l_new, p, corr = dp.online_softmax_update(m, l, s)
            acc_ref[h, :g, :hv] = acc_ref[h, :g, :hv] * corr + jnp.dot(
                p, vb, preferred_element_type=jnp.float32)
            m_ref[h, :g, :1] = m_new
            l_ref[h, :g, :1] = l_new

    if causal:
        # ragged fast path: this row attends to nothing at or beyond its
        # own position, so tiles starting past q_pos are pure MASK_VALUE /
        # phantom mass — skip them entirely (per BATCH row: b is a grid dim)
        pl.when(kv_tile * block_kv <= q_pos)(update)
    else:
        update()

    @pl.when(kj == inner - 1)
    def _():
        om_ref[0, 0] = m_ref[:, :g, :1]
        ol_ref[0, 0] = l_ref[:, :g, :1]
        oacc_ref[0, 0] = acc_ref[:, :g, :hv]


def _decode_body_int(qpos_ref, valid_ref, q_ref, k_ref, v_ref, om_ref,
                     os_ref, oacc_ref, m_ref, s_ref, acc_ref, *,
                     block_kv: int, inner: int, causal: bool, t_kv: int,
                     guard_shift: int):
    """Dual-mode twin of ``_decode_body``: same grid, same tile skip, but
    the per-split partial is the snapped int monoid state (m, S, acc)."""
    b = pl.program_id(0)
    sp = pl.program_id(1)
    kj = pl.program_id(2)
    n_heads, g, hd = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    hv = oacc_ref.shape[-1]
    nb = unit.N_SNAP_BUCKETS
    kv_tile = sp * inner + kj
    q_pos = qpos_ref[b]

    @pl.when(kj == 0)
    def _():
        # empty-split sentinel (SNAP_MIN, 0, 0) — the int merge identity
        m_ref[...] = jnp.full_like(m_ref, unit.SNAP_MIN)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update():
        valid = valid_ref[0, pl.ds(kv_tile, 1), :]         # (1, bkv)
        for h in range(n_heads):
            q = q_ref[0, 0, h].astype(jnp.float32)         # (G, h) pre-scaled
            kb = _kv_head(k_ref, h, hd).astype(jnp.float32)  # (bkv, h)
            vb = _kv_head(v_ref, h, hv).astype(jnp.float32)  # (bkv, hv)
            sq = int_score_words(q, kb, q_pos, valid, kv_tile,
                                 block_kv=block_kv, causal=causal,
                                 t_kv=t_kv)
            m_new, S_new, acc_new = snap_tile_update(
                m_ref[h, :g, :1], s_ref[h, :g, :nb], acc_ref[h, :g, :hv],
                sq, vb, guard_shift)
            m_ref[h, :g, :1] = m_new
            s_ref[h, :g, :nb] = S_new
            acc_ref[h, :g, :hv] = acc_new

    if causal:
        pl.when(kv_tile * block_kv <= q_pos)(update)
    else:
        update()

    @pl.when(kj == inner - 1)
    def _():
        om_ref[0, 0] = m_ref[:, :g, :1]
        os_ref[0, 0] = s_ref[:, :g, :nb]
        oacc_ref[0, 0] = acc_ref[:, :g, :hv]


def _paged_body(body, n_index: int):
    """Block-table wrapper of a decode body: the ``n_index``
    scalar-prefetched refs that only address the pool (the layer index and
    the block table) arrive first and are consumed entirely by the
    BlockSpec index maps — the body proper is the SAME sweep as contiguous
    decode (the physical gather happens in the pipeline, not the
    arithmetic)."""
    def run(*refs, **kw):
        body(*refs[n_index:], **kw)
    return run


def _decode_call(q, k, v, q_pos, valid, *, kv_index, scalars, bkv: int,
                 num_splits: int, inner: int, causal: bool, t_kv: int,
                 interpret: bool, guard_shift: int | None):
    """The split-KV pallas_call shared by all four decode flavors.

    ``q`` (B, 1, K, G, h) pre-scaled f32; ``k``/``v`` a token-major cache
    (B, T, K, h) when ``scalars`` is empty, else the stacked lane-dense
    pool (L, N, bs, K*h); the block of ``bkv`` keys over all heads sits at
    ``kv_index(b, kv_tile, *scalars)``; ``valid`` the
    (B, num_splits * inner, bkv) per-tile validity rows; ``scalars`` the
    scalar-prefetch operands ahead of the per-row positions ``q_pos``
    (B,) int32.  ``guard_shift=None`` runs the float body, an int the
    dual-mode one.  Returns the per-split partials, split axis 1.
    """
    b, _, kh, g, hd = q.shape
    paged = bool(scalars)
    hv = v.shape[-1] // kh if paged else v.shape[-1]
    n_tiles = num_splits * inner
    int_mode = guard_shift is not None
    nb = unit.N_SNAP_BUCKETS
    rows = tiling.round_up(g, tiling.SUBLANE)
    n_pre = len(scalars) + 1

    def kv_map(b_, sp, kj, *pre):
        return (*kv_index(b_, sp * inner + kj, *pre[:-1]), 0, 0)

    def row_map(b_, sp, kj, *pre):
        return (b_, 0, 0)

    def part_map(b_, sp, kj, *pre):
        return (b_, sp, 0, 0, 0)

    def kv_block(width):
        # the pool's layer dim is squeezed: the body sees (1, bs, K*width)
        return ((None, 1, bkv, kh * width) if paged
                else (1, bkv, kh, width))

    in_specs = [
        pl.BlockSpec((1, n_tiles, bkv), row_map),
        pl.BlockSpec((1, 1, kh, g, hd), lambda b_, *r: (b_, 0, 0, 0, 0)),
        pl.BlockSpec(kv_block(hd), kv_map),
        pl.BlockSpec(kv_block(hv), kv_map),
    ]
    stat_lanes = nb if int_mode else 1
    stat_dtype = jnp.int32 if int_mode else jnp.float32
    m_dtype = jnp.int32 if int_mode else jnp.float32
    out_specs = [pl.BlockSpec((1, 1, kh, g, 1), part_map),
                 pl.BlockSpec((1, 1, kh, g, stat_lanes), part_map),
                 pl.BlockSpec((1, 1, kh, g, hv), part_map)]
    out_shape = [
        jax.ShapeDtypeStruct((b, num_splits, kh, g, 1), m_dtype),
        jax.ShapeDtypeStruct((b, num_splits, kh, g, stat_lanes), stat_dtype),
        jax.ShapeDtypeStruct((b, num_splits, kh, g, hv), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((kh, rows, tiling.scratch_lanes(1)), m_dtype),      # m
        pltpu.VMEM((kh, rows, tiling.scratch_lanes(stat_lanes)),
                   stat_dtype),                                        # l | S
        pltpu.VMEM((kh, rows, tiling.scratch_lanes(hv)), jnp.float32),  # acc
    ]
    kw = dict(block_kv=bkv, inner=inner, causal=causal, t_kv=t_kv)
    if int_mode:
        body = functools.partial(_decode_body_int, guard_shift=guard_shift,
                                 **kw)
    else:
        body = functools.partial(_decode_body, **kw)
    if paged:
        body = _paged_body(body, len(scalars))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre, grid=(b, num_splits, inner),
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch)
    return pl.pallas_call(body, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        *scalars, q_pos.reshape(b).astype(jnp.int32), valid, q, k, v)


def _finish_decode(parts, out_dtype, int_mode: bool):
    """Split fold + normalize: one vectorized n-way merge over the split
    axis — the same monoid the ring folds pairwise, so the merged words
    satisfy the partial-merge contract (pinned vs flash_attention_merged
    in tests); dual-mode folds the int monoid and divides once by the
    bucket-telescoped l word."""
    part_m, part_l, part_acc = parts
    if int_mode:
        _, S, acc = unit.online_merge_n_int(part_m, part_l, part_acc, axis=1)
        l = unit.online_finish_int(S)                      # (B, 1, K, G)
        return (acc / l[..., None].astype(jnp.float32)).astype(out_dtype)
    _, l, acc = dp.online_softmax_merge_n(part_m, part_l, part_acc, axis=1)
    return dp.online_softmax_finish(l, acc).astype(out_dtype)  # (B,1,K,G,hv)


@functools.partial(jax.jit, static_argnames=(
    "causal", "num_splits", "block_kv", "interpret", "guard_shift"))
def _flash_decode_jit(q, k, v, q_pos, kv_valid, scale, *, causal: bool,
                      num_splits: int, block_kv: int, interpret: bool,
                      guard_shift: int | None):
    t = k.shape[1]
    # fold the traced scale into q (one compile across scales, the same
    # contract as flash_attention_pallas)
    qf = q.astype(jnp.float32) * scale
    bkv = block_kv
    inner = tiling.cdiv(tiling.cdiv(t, bkv), num_splits)
    t_pad = num_splits * inner * bkv
    kf, _ = tiling.pad_dim(k, 1, t_pad)
    vf, _ = tiling.pad_dim(v, 1, t_pad)
    valid, _ = tiling.pad_dim(kv_valid.astype(jnp.int32), 1, t_pad, value=0)
    parts = _decode_call(
        qf, kf, vf, q_pos, valid.reshape(q.shape[0], -1, bkv),
        kv_index=lambda b_, tile: (b_, tile), scalars=(), bkv=bkv,
        num_splits=num_splits, inner=inner, causal=causal, t_kv=t,
        interpret=interpret, guard_shift=guard_shift)
    return _finish_decode(parts, v.dtype, guard_shift is not None)


@functools.partial(jax.jit, static_argnames=(
    "causal", "num_splits", "interpret", "guard_shift"))
def _flash_decode_paged_jit(q, k_pool, v_pool, tables, layer, q_pos,
                            kv_valid, scale, *, causal: bool,
                            num_splits: int, interpret: bool,
                            guard_shift: int | None):
    bs = k_pool.shape[2]                 # block size == KV tile width
    nblk = tables.shape[1]
    qf = q.astype(jnp.float32) * scale
    inner = tiling.cdiv(nblk, num_splits)
    # pad the table out to the grid (surplus tiles alias sentinel block 0
    # and are masked off as phantoms by the t_kv check / kv_valid pad)
    tab, _ = tiling.pad_dim(tables.astype(jnp.int32), 1,
                            num_splits * inner, value=0)
    valid, _ = tiling.pad_dim(kv_valid.astype(jnp.int32), 1,
                              num_splits * inner * bs, value=0)
    # THE paged difference: the KV tile index routes through the
    # scalar-prefetched layer index and block table instead of a
    # contiguous stride
    parts = _decode_call(
        qf, k_pool, v_pool, q_pos, valid.reshape(q.shape[0], -1, bs),
        kv_index=lambda b_, tile, lay, tab_: (lay[0], tab_[b_, tile]),
        scalars=(jnp.reshape(layer, (1,)).astype(jnp.int32), tab),
        bkv=bs, num_splits=num_splits, inner=inner,
        causal=causal, t_kv=nblk * bs, interpret=interpret,
        guard_shift=guard_shift)
    return _finish_decode(parts, v_pool.dtype, guard_shift is not None)


def flash_decode_paged(q, k_pool, v_pool, *, block_tables, layer, q_pos,
                       kv_valid, causal: bool = True,
                       scale: float | None = None,
                       num_splits: int | None = None,
                       interpret: bool | None = None,
                       softmax_impl: str = "float"):
    """Block-table flash decode: KV gathered through a paged pool.

    ``k_pool``/``v_pool`` are the stacked lane-dense pools
    (L, N_blocks, block_size, K*h|K*hv), read at layer ``layer`` (an int
    or a traced int32 scalar, e.g. the layer scan's index), and
    ``block_tables`` is (B, max_blocks) int32 mapping each row's logical
    block index to its pool block (sentinel 0 past the row's length; the
    sentinel's mass is masked to exp(MASK_VALUE) by ``kv_valid`` exactly
    like any dense invalid key).  The KV tile width IS the block size, one
    table entry per grid step via scalar prefetch, and everything after
    the gather — masking, the per-row causal tile skip, the
    ``online_softmax_merge_n`` fold — is byte-for-byte the contiguous
    kernel's code path, so the split/parity contracts carry over.

    ``softmax_impl='dualmode'`` runs the snapped-max INT recurrence on the
    same paged grid (see module docstring).
    """
    if q.shape[1] != 1:
        raise ValueError(
            f"flash_decode is the s_q=1 decode kernel; got s_q={q.shape[1]}"
            " — use 'flash'/'flash_pallas' for wide query tiles")
    kh = q.shape[2]
    if (k_pool.ndim != 4 or k_pool.shape[-1] % kh
            or v_pool.shape[-1] % kh):
        raise ValueError(
            f"pools must be (layers, blocks, block_size, {kh} kv heads x "
            f"head dim); got {k_pool.shape} and {v_pool.shape}")
    nblk, bs = block_tables.shape[1], k_pool.shape[2]
    if kv_valid.shape[1] != nblk * bs:
        raise ValueError(
            f"kv_valid covers {kv_valid.shape[1]} keys but the table maps "
            f"{nblk} blocks x {bs} = {nblk * bs}")
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / q.shape[-1] ** 0.5) if scale is None else scale
    if num_splits is None:
        num_splits = min(tiling.decode_splits(nblk * bs), nblk)
    num_splits = max(1, min(num_splits, nblk))
    # dual-mode guard from the LOGICAL cache extent, as the whole-row
    # unit would apply it
    guard_shift = _guard_shift(softmax_impl, nblk * bs, "flash_decode_paged")
    return _flash_decode_paged_jit(q, k_pool, v_pool, block_tables,
                                   jnp.asarray(layer, jnp.int32), q_pos,
                                   kv_valid, jnp.float32(scale),
                                   causal=causal, num_splits=num_splits,
                                   interpret=interpret,
                                   guard_shift=guard_shift)


def flash_decode_pallas(q, k, v, *, q_pos, kv_valid, causal: bool = True,
                        scale: float | None = None,
                        num_splits: int | None = None,
                        block_kv: int | None = None,
                        interpret: bool | None = None,
                        softmax_impl: str = "float"):
    """Split-KV flash decode; see module docstring for shapes/masking.

    ``num_splits=None`` picks the :func:`repro.kernels.tiling.
    decode_splits` heuristic (cache length / core count, 1 at short
    caches).  The output is invariant to the split count — WHERE the
    cache is split only changes which partial each key lands in, and the
    merge is the associative monoid fold.  ``softmax_impl='dualmode'``
    swaps in the snapped-max INT recurrence (same grid, int partials,
    :func:`repro.core.softmax_unit.online_merge_n_int` fold).
    """
    if q.shape[1] != 1:
        raise ValueError(
            f"flash_decode is the s_q=1 decode kernel; got s_q={q.shape[1]}"
            " — use 'flash'/'flash_pallas' for wide query tiles")
    t = k.shape[1]
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / q.shape[-1] ** 0.5) if scale is None else scale
    if num_splits is None:
        num_splits = tiling.decode_splits(t)
    if block_kv is None:
        block_kv = tiling.decode_kv_block(
            t, num_splits, _key_bytes(k.shape[2], k.shape[3], v.shape[3],
                                      k.dtype))
    guard_shift = _guard_shift(softmax_impl, t, "flash_decode_pallas")
    return _flash_decode_jit(q, k, v, q_pos, kv_valid, jnp.float32(scale),
                             causal=causal, num_splits=num_splits,
                             block_kv=block_kv, interpret=interpret,
                             guard_shift=guard_shift)


def _guard_shift(softmax_impl: str, t_kv: int, name: str) -> int | None:
    """None for the float body; the whole-row unit's guard shift for an
    n=t_kv row for the dual-mode body."""
    if softmax_impl == "dualmode":
        return max(0, t_kv.bit_length() - 16)
    if softmax_impl != "float":
        raise ValueError(f"{name} softmax_impl={softmax_impl!r}: expected "
                         "'float' or 'dualmode'")
    return None


def _key_bytes(kh: int, hd: int, hv: int, dtype) -> int:
    """VMEM bytes one cached key takes in a decode block: its (K, h) K
    slab plus its (K, hv) V slab, on the (sublane, lane) grid."""
    size = jnp.dtype(dtype).itemsize
    return max(tiling.vmem_tile_bytes(kh, hd, size),
               tiling.vmem_tile_bytes(kh, hv, size))


def vmem_plan(t_kv: int, hd: int, hv: int, g: int = 1, kh: int = 1):
    """Static VMEM residency of the four decode kernels (see
    ``flash_attention.vmem_plan`` for the contract).  The paged variants
    tile by the engine's block size instead of the split-KV block and read
    lane-dense (bs, K*h) pool blocks; the scalar-prefetched positions,
    layer index and block table live in SMEM, not VMEM, so they do not
    appear here."""
    num_splits = tiling.decode_splits(t_kv)
    bkv = tiling.decode_kv_block(t_kv, num_splits,
                                 _key_bytes(kh, hd, hv, jnp.float32))
    bs = tiling.paged_block_size(t_kv)
    rows = tiling.round_up(g, tiling.SUBLANE)
    nb = unit.N_SNAP_BUCKETS

    def plan(block, n_tiles, int_mode, paged=False):
        stat = jnp.int32 if int_mode else jnp.float32
        lanes = nb if int_mode else 1
        kv = ((lambda w: (1, block, kh * w)) if paged
              else (lambda w: (1, block, kh, w)))
        return {
            "in:kv_valid": ((1, n_tiles, block), jnp.int32),
            "in:q": ((1, 1, kh, g, hd), jnp.float32),
            "in:k": (kv(hd), jnp.float32),
            "in:v": (kv(hv), jnp.float32),
            "out:part_m": ((1, 1, kh, g, 1), stat),
            "out:part_l": ((1, 1, kh, g, lanes), stat),
            "out:part_acc": ((1, 1, kh, g, hv), jnp.float32),
            "scratch:m": ((kh, rows, tiling.scratch_lanes(1)), stat),
            "scratch:l": ((kh, rows, tiling.scratch_lanes(lanes)), stat),
            "scratch:acc": ((kh, rows, tiling.scratch_lanes(hv)),
                            jnp.float32),
        }

    n_dense = num_splits * tiling.cdiv(tiling.cdiv(t_kv, bkv), num_splits)
    n_paged = tiling.cdiv(t_kv, bs)
    return {
        "decode_float": plan(bkv, n_dense, False),
        "decode_int": plan(bkv, n_dense, True),
        "decode_paged_float": plan(bs, n_paged, False, paged=True),
        "decode_paged_int": plan(bs, n_paged, True, paged=True),
    }


def _attention_entry(q, k, v, *, q_pos, kv_valid, causal, scale,
                     softmax_impl="float", ring_axis=""):
    # both int contracts route to the snapped int recurrence — a snap
    # request must never silently fall back to the float path
    impl = ("dualmode" if softmax_impl in ("dualmode", "dualmode_snap")
            else "float")
    return flash_decode_pallas(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                               causal=causal, scale=scale,
                               softmax_impl=impl)


def _paged_attention_entry(q, k_pool, v_pool, *, block_tables, layer,
                           q_pos, kv_valid, causal, scale,
                           softmax_impl="float", ring_axis=""):
    impl = ("dualmode" if softmax_impl in ("dualmode", "dualmode_snap")
            else "float")
    return flash_decode_paged(q, k_pool, v_pool, block_tables=block_tables,
                              layer=layer, q_pos=q_pos, kv_valid=kv_valid,
                              causal=causal, scale=scale, softmax_impl=impl)


dispatch.register_attention(
    "flash_decode", _attention_entry,
    modes=("float", "dualmode", "dualmode_snap"), grad=False,
    decode_only=True, mesh_safe=False,
    note="split-KV s_q=1 kernel; single-device (gathers sharded KV)")
dispatch.register_paged_attention("flash_decode", _paged_attention_entry)
