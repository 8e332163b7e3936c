"""One tiling policy for every Pallas kernel in this package.

Replaces the three divergent per-kernel heuristics the kernels used to
carry (``_row_block`` in dualmode_softmax, ``_tile2d`` there too, ``_pick``
in fused_ffn), each of which searched for an exact divisor of the problem
shape and so degraded to 1-wide blocks on primes / odd sizes.  The policy
here never shrinks a block to fit a remainder: callers PAD the operand up
to a block multiple with :func:`pad_dim` and slice the result back with
:func:`unpad` — blocks stay VPU/MXU aligned for any input shape.

Constants follow the TPU layout rules (pallas guide §Tiling):
lane width 128, f32 sublane 8, ~16 MiB VMEM per core of which we budget
~2 MiB per operand tile.
"""
from __future__ import annotations

import jax.numpy as jnp

LANE = 128            # VPU lane width / MXU edge: last-dim block multiple
SUBLANE = 8           # f32 sublane: second-to-last-dim block multiple
VMEM_TILE_BUDGET = 2 * 1024 * 1024   # bytes per operand tile
VMEM_CORE_BUDGET = 16 * 1024 * 1024  # whole-kernel VMEM per TensorCore:
#   every pallas_call's resident set — double-buffered in/out tiles plus
#   scratch — must fit this; repro.analysis.vmem audits each kernel's
#   declared ``vmem_plan()`` against it over the canonical shape grid
UNIT_LIVE_TILES = 16   # tile-sized values the unit's own kernels
#   (dualmode_softmax) hold at once: in and out, double-buffered, plus
#   the int body's temporaries — the v5e compiler asked 25.8 MiB for the
#   GELU-mode int body on 2 MiB tiles, i.e. ~13 live tiles


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(n: int, multiple: int) -> int:
    return cdiv(n, multiple) * multiple


def fit_block(n: int, multiple: int, cap: int) -> int:
    """Largest aligned block that divides the minimally padded extent.

    Pads ``n`` only up to the next ``multiple`` (the hardware alignment),
    then picks the largest block <= cap that is a multiple of ``multiple``
    AND divides that padded extent — so block choice never inflates the
    padding beyond alignment (513 cols -> 640 with 128-wide blocks, not
    1024 with a blind 512 block)."""
    padded = round_up(n, multiple)
    cap = max(min(cap - cap % multiple, padded), multiple)
    for b in range(cap, 0, -multiple):
        if padded % b == 0:
            return b
    return multiple


def scratch_lanes(n: int) -> int:
    """Lane extent for a VMEM scratch whose logical minor dim is ``n``.

    Head dims off the 128 lane grid (MLA hv=72 style) must not shrink the
    scratch tile below the hardware lane width — round up and let the
    kernel body address the live ``[:, :n]`` slice.
    """
    return round_up(n, LANE)


def pad_dim(x, axis: int, multiple: int, value=0.0):
    """Pad ``x`` along ``axis`` up to a multiple; returns (padded, pad)."""
    pad = (-x.shape[axis]) % multiple
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths, constant_values=value)
    return x, pad


def unpad(y, axis: int, n: int):
    """Slice ``y`` back to length ``n`` along ``axis``."""
    if y.shape[axis] == n:
        return y
    idx = [slice(None)] * y.ndim
    idx[axis] = slice(0, n)
    return y[tuple(idx)]


def row_block(n_rows: int, n_cols: int, itemsize: int = 4) -> int:
    """Rows per block for whole-row kernels (row reductions need full rows).

    The row length is fixed at ``n_cols`` (pad it to a LANE multiple first);
    rows per block fill a 1/UNIT_LIVE_TILES share of VMEM, SUBLANE-aligned.
    Callers pad the row count to a multiple of the returned block.
    """
    budget = VMEM_CORE_BUDGET // UNIT_LIVE_TILES
    rows = max(budget // (max(n_cols, 1) * itemsize), SUBLANE)
    return fit_block(n_rows, SUBLANE, rows)


def tile2d(m: int, n: int, itemsize: int = 4) -> tuple[int, int]:
    """(bm, bn) for elementwise 2D kernels: LANE-wide, each tile a
    1/UNIT_LIVE_TILES share of VMEM."""
    budget = VMEM_CORE_BUDGET // UNIT_LIVE_TILES
    bn = fit_block(n, LANE, 512)
    bm = fit_block(m, SUBLANE, max(budget // (bn * itemsize), SUBLANE))
    return bm, bn


def norm_rows(n_rows: int, n_cols: int, n_streams: int = 4,
              itemsize: int = 4) -> int:
    """Rows per block for the fused norm-seam kernels (fused_norm.py).

    Like :func:`row_block` these are whole-row kernels (the moments need
    the full feature dim), but the residual-norm epilogue keeps FOUR
    (bm, d) streams resident at once — x, the residual, and both outputs
    — so the per-stream budget is halved to keep the double-buffered
    resident set inside VMEM_CORE_BUDGET.
    """
    per_row = max(n_cols, 1) * itemsize * max(n_streams, 1)
    rows = max(2 * VMEM_TILE_BUDGET // per_row, SUBLANE)
    return fit_block(n_rows, SUBLANE, rows)


def matmul_blocks(m: int, f: int, want_m: int = 128,
                  want_f: int = 512) -> tuple[int, int]:
    """(bm, bf) output-tile shape for matmul-epilogue kernels.

    MXU-aligned (multiples of SUBLANE/LANE); blocks divide the minimally
    padded extent instead of forcing a pad up to the wanted block size.
    """
    return fit_block(m, SUBLANE, want_m), fit_block(f, LANE, want_f)


#   split-KV decode ("flash decoding") policy: decode runs s_q=1, so the
#   only parallelism left is over the KEYS — the cache is carved into
#   `num_splits` independent sweeps whose (m, l, o·l) partials merge via
#   datapath.online_softmax_merge_n.  Splitting only pays once each split
#   still streams a meaningful stretch of cache, and more splits than
#   cores just queue.
DECODE_FLASH_MIN_KV = 1024   # below this the s_q=1 'auto' pick stays naive
DECODE_SPLIT_KEYS = 2048     # min keys per split before another split pays
DECODE_MAX_SPLITS = 8        # partial-merge fan-in cap


# TensorCores per chip, keyed by ``device_kind`` as the TPU runtime
# reports it ("TPU v5 lite" is the v5e).  A kind missing here raises —
# the split-KV grid is sized from this, and a guess would size it wrong.
TPU_CORES_PER_CHIP = {"TPU v5 lite": 1}


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on the default backend.

    The one home of that decision: the TPU compiles every kernel, the CPU
    backend interprets them (the test path), and any other backend
    raises — a program that meant to run on the chip must never fall
    back to interpreting kernels in silence."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and run interpreted on CPU; "
        f"the {backend!r} backend is neither")


def device_core_count() -> int:
    """Cores on the primary device — the parallelism the split-KV decode
    grid is trying to fill.

    On TPU the per-chip TensorCore count of the device kind
    (:data:`TPU_CORES_PER_CHIP`); on the CPU backend, where the grid is
    interpreted, a fixed DECODE_MAX_SPLITS so split counts stay
    deterministic across hosts.  Any other device raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return DECODE_MAX_SPLITS
    if dev.platform == "tpu" and dev.device_kind in TPU_CORES_PER_CHIP:
        return TPU_CORES_PER_CHIP[dev.device_kind]
    raise RuntimeError(
        f"unknown device {dev.platform}/{dev.device_kind!r}: add its core "
        "count to tiling.TPU_CORES_PER_CHIP")


def decode_splits(t_kv: int, max_splits: int | None = None) -> int:
    """Split count for the s_q=1 split-KV decode kernel.

    Sized from the cache length (one split per DECODE_SPLIT_KEYS keys)
    and capped by the core count / DECODE_MAX_SPLITS; degenerates to 1
    split — plain blocked streaming — at short caches.
    """
    if max_splits is None:
        max_splits = min(DECODE_MAX_SPLITS, device_core_count())
    return int(max(1, min(max_splits, t_kv // DECODE_SPLIT_KEYS)))


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile once laid out on the (sublane,
    lane) grid: rows pad to the dtype's packed sublane count (8 for
    32-bit, 16 for 16-bit), cols to the lane width."""
    return (round_up(rows, SUBLANE * max(4 // itemsize, 1))
            * round_up(cols, LANE) * itemsize)


def decode_kv_block(t_kv: int, num_splits: int, key_bytes: int = 0) -> int:
    """KV tile width for one decode split: LANE-aligned, <= 512 keys,
    dividing the minimally padded per-split extent, and — given
    ``key_bytes``, the VMEM one key of the block takes across all its
    heads — small enough that one K or V tile stays in the tile budget."""
    cap = 512
    if key_bytes:
        cap = min(cap, max(LANE, VMEM_TILE_BUDGET // key_bytes))
    return fit_block(cdiv(t_kv, max(num_splits, 1)), LANE, cap)


#   paged-KV policy: the serve engine's block pool carves the cache into
#   fixed-size blocks addressed through per-request block tables.  The
#   block size is the paged decode kernel's KV tile width — one grid step
#   gathers exactly one block via the scalar-prefetched table — so it
#   must be SUBLANE-aligned (it lands on the second-to-last cache axis)
#   and small enough that short prompts don't strand most of a block.
PAGED_MIN_BLOCK = SUBLANE     # floor: sublane alignment of the seq axis
PAGED_MAX_BLOCK = LANE        # cap: one lane-width tile per grid step


def paged_block_size(max_seq: int) -> int:
    """Tokens per paged-KV block for an engine bounded by ``max_seq``.

    Targets ~16 blocks per maximal sequence (enough table entries for
    prefix sharing to find full-block boundaries, few enough that the
    scalar-prefetch table stays tiny), clamped to the hardware alignment
    window [SUBLANE, LANE]."""
    want = round_up(cdiv(max_seq, 16), SUBLANE)
    return int(max(PAGED_MIN_BLOCK, min(PAGED_MAX_BLOCK, want)))


def attention_blocks(s_q: int, t_kv: int) -> tuple[int, int]:
    """(bq, bkv) for blocked attention: q rows x kv keys per grid step.

    Scores tile is (bq, bkv) f32; 128x512 = 256 KiB, well inside budget,
    with kv LANE-aligned (it is the score tile's minor dim).
    """
    return fit_block(s_q, SUBLANE, 128), fit_block(t_kv, LANE, 512)
