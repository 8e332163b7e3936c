"""Blocked online-softmax attention (flash attention, pure JAX).

Why it exists: the naive path materializes (B,H,S,T) scores — at the
assigned train_4k/prefill_32k shapes that is 10s of GB per chip and can
never fit VMEM/HBM.  The blocked form streams KV in chunks and keeps only
(B,H,S,block) live.

Faithfulness note (DESIGN.md §2): the paper's softmax normalizes in the
LOG domain (Eq. 10), y = 2^(t_i - m - log2 Σ 2^(t_j - m)).  That form
telescopes exactly into the online-softmax recurrence (Milakov &
Gimelshein [22], the same family the paper's adder-tree architecture
cites): carrying (m, l) per row IS the streaming evaluation of Eq. 10.
The inner step is therefore ``repro.kernels.datapath.
online_softmax_update`` — the unit's own arithmetic, streamed, and the
SAME function the Pallas kernel body executes (kernels/flash_attention.py
is this loop with a Pallas grid around it).  (This module is the FLOAT
form; the bit-accurate int unit streams through the snapped one-sweep
kernel in kernels/flash_attention_int.py — dispatch never pairs
'dualmode' with this float path.)

Shapes: q (B,S,K,G,h), k (B,T,K,h), v (B,T,K,hv) -> out (B,S,K,G,hv).
hv may differ from h (MLA).  Masking: kv position t attends iff
kv_valid[b,t] and (not causal or t <= q_pos[b,s]); masked scores take
``datapath.MASK_VALUE`` so every attention implementation masks
identically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import datapath as dp
from repro.kernels import dispatch, tiling


def flash_attention(q, k, v, *, q_pos, kv_valid, causal: bool = True,
                    block: int = 1024, scale: float | None = None,
                    return_stats: bool = False):
    """Blocked online-softmax attention (see module docstring).

    ``return_stats=True`` additionally returns the per-row online-softmax
    statistics ``(m, l)`` laid out (B, K, G, S): the running max and
    normalizer of the PRE-SCALED masked scores.  This is the residual
    contract the Pallas forward kernel saves for its backward kernels
    (``kernels/flash_attention_bwd.py``) — exposed here so parity tests
    can pin the kernel's saved statistics against the pure-JAX blocked
    reference.
    """
    b, s_q, kh, g, hd = q.shape
    t = k.shape[1]
    hv = v.shape[-1]
    block = min(block, t)
    # non-divisible T: pad KV up to a block multiple (tiling policy) with
    # invalid keys, instead of shrinking the block toward a 1-wide scan
    k, _ = tiling.pad_dim(k, 1, block)
    v, _ = tiling.pad_dim(v, 1, block)
    kv_valid, _ = tiling.pad_dim(kv_valid, 1, block, value=False)
    nb = k.shape[1] // block
    scale = (1.0 / hd ** 0.5) if scale is None else scale

    qf = q.astype(jnp.float32) * scale
    t_idx = jnp.arange(block)

    def body(carry, i):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * block, block, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * block, block, 1)
        validb = jax.lax.dynamic_slice_in_dim(kv_valid, i * block, block, 1)
        # scores for this block: (B,K,G,S,block)
        sc = jnp.einsum("bskgh,btkh->bkgst", qf, kb.astype(jnp.float32))
        pos_b = i * block + t_idx                              # (block,)
        mask = validb[:, None, :]                              # (B,1,block)
        if causal:
            mask = mask & (pos_b[None, None, :] <= q_pos[:, :, None])
        sc = jnp.where(mask[:, None, None, :, :], sc, dp.MASK_VALUE)
        if k.shape[1] != t:
            # pad-introduced phantom keys must carry NO mass (-inf), unlike
            # user-invalid keys which keep the finite MASK_VALUE for bit
            # parity with the naive path's masking
            sc = jnp.where(pos_b[None, None, None, None, :] < t, sc,
                           -jnp.inf)
        # online log-domain update (Eq. 10 streamed, shared datapath step)
        m, l, p, corr = dp.online_softmax_update(m, l, sc)
        acc = acc * corr + jnp.einsum(
            "bkgst,btkh->bkgsh", p, vb.astype(jnp.float32))
        return (m, l, acc), None

    m0 = jnp.full((b, kh, g, s_q, 1), dp.MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, kh, g, s_q, 1), jnp.float32)
    acc0 = jnp.zeros((b, kh, g, s_q, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nb))
    out = dp.online_softmax_finish(l, acc)                     # (B,K,G,S,hv)
    out = jnp.moveaxis(out, 3, 1).astype(v.dtype)              # (B,S,K,G,hv)
    if return_stats:
        return out, m[..., 0], l[..., 0]                       # (B,K,G,S)
    return out


def flash_attention_merged(q, k, v, *, q_pos, kv_valid, n_splits: int,
                           causal: bool = True, scale: float | None = None,
                           block: int = 1024):
    """Ring-attention oracle on ONE host: split KV into ``n_splits``
    contiguous shards, run the blocked reference per shard (each shard
    sees shard-local key positions, so ``q_pos`` is shifted by the
    shard's offset — exactly what a ring hop does), convert each
    finished shard back to its unnormalized partial ``(m, l, o*l)`` and
    fold with :func:`repro.kernels.datapath.online_softmax_merge`.

    This is the pure-JAX home of the partial-merge contract: the Pallas
    ring kernel (``kernels/ring_attention.py``) is this fold run across
    devices, and the merge's split-point invariance — the output must
    not depend on ``n_splits`` — is what the property tests pin.
    """
    t = k.shape[1]
    assert t % n_splits == 0, (t, n_splits)
    t_loc = t // n_splits
    scale = (1.0 / q.shape[-1] ** 0.5) if scale is None else scale
    qf = q.astype(jnp.float32) * scale

    part = None
    for i in range(n_splits):
        sl = slice(i * t_loc, (i + 1) * t_loc)
        o_i, m_i, l_i = flash_attention(
            qf, k[:, sl], v[:, sl], q_pos=q_pos - i * t_loc,
            kv_valid=kv_valid[:, sl], causal=causal, scale=1.0,
            block=min(block, t_loc), return_stats=True)
        # (B,K,G,S) stats -> (B,S,K,G,1) merge layout; o*l recovers the
        # shard's unnormalized accumulator
        m_i = jnp.moveaxis(m_i, 3, 1)[..., None]
        l_i = jnp.moveaxis(l_i, 3, 1)[..., None]
        part_i = (m_i, l_i, o_i.astype(jnp.float32) * l_i)
        part = part_i if part is None else dp.online_softmax_merge(
            part, part_i)
    _, l, acc = part
    return dp.online_softmax_finish(l, acc).astype(v.dtype)


def flash_attention_paged_ref(q, k_pool, v_pool, *, block_tables, q_pos,
                              kv_valid, causal: bool = True,
                              scale: float | None = None):
    """Paged fold oracle: one python loop over LOGICAL blocks, each block
    gathered through the table from ONE layer's (N, bs, K, h|hv) pools
    (the serving pools' layer ``l`` reshaped), scored+masked exactly like
    the dense paths, reduced to its ``(m, l, o·l)`` partial with
    :func:`repro.kernels.datapath.online_softmax_partial` and folded with
    :func:`repro.kernels.datapath.online_softmax_merge`.

    This is the block-table twin of :func:`flash_attention_merged` — the
    pure-JAX home of the paged kernel's contract: the Pallas block-table
    gather must produce the same words as this fold, and the fold itself
    is split-invariant (one block per partial is the finest split).  The
    table's physical permutation must be invisible: only the LOGICAL
    block index enters the mask arithmetic.
    """
    b, s_q = q.shape[:2]
    nblk, bs = block_tables.shape[1], k_pool.shape[1]
    scale = (1.0 / q.shape[-1] ** 0.5) if scale is None else scale
    qf = q.astype(jnp.float32) * scale

    part = None
    for j in range(nblk):
        kb = k_pool[block_tables[:, j]].astype(jnp.float32)  # (B,bs,K,h)
        vb = v_pool[block_tables[:, j]].astype(jnp.float32)  # (B,bs,K,hv)
        s = jnp.einsum("bskgh,btkh->bskgt", qf, kb,
                       preferred_element_type=jnp.float32)
        kv_pos = j * bs + jnp.arange(bs)
        mask = kv_valid[:, j * bs:(j + 1) * bs][:, None, None, None, :]
        if causal:
            mask = mask & (kv_pos[None, None, None, None, :]
                           <= q_pos[:, :, None, None, None])
        s = jnp.where(mask, s, dp.MASK_VALUE)
        # (B,bs,K,hv) -> (B,1,K,1,bs,hv): broadcast over S and G
        part_j = dp.online_softmax_partial(
            s, jnp.moveaxis(vb, 1, 2)[:, None, :, None])
        part = part_j if part is None else dp.online_softmax_merge(
            part, part_j)
    _, l, acc = part
    return dp.online_softmax_finish(l, acc).astype(v_pool.dtype)


def use_flash(s_q: int, t: int, threshold: int = 1 << 22) -> bool:
    """Blocked path when the scores tensor would exceed ~16 MB f32/head.

    (No divisibility condition: non-divisible T pads to the block grid.)"""
    return s_q * t > threshold


def blocked_impl(backend: str | None = None) -> str:
    """The 'auto' rule's blocked pick, backend-aware.

    On TPU the compiled Pallas kernel is the fast path.  Other backends
    have no Pallas compiler: the kernel would run the Pallas interpreter,
    one grid step at a time in Python, while the pure-JAX blocked graph
    compiles through XLA, so 'auto' prefers 'flash' there.  Explicit
    impl strings are never rewritten — this only shapes the 'auto'
    resolution.
    """
    backend = backend or jax.default_backend()
    return "flash_pallas" if backend == "tpu" else "flash"


def _auto_rule(s_q: int, t: int) -> str:
    """impl='auto': naive for short rows, blocked when the score tensor
    would blow VMEM, and the split-KV decode kernel for the generative-
    inference shape — one query row against a long KV cache.

    The decode pick is MESH-GATED: flash_decode is a single-device
    kernel, and a pallas_call has no partitioning rule — lowered under
    an ambient mesh that shards the KV cache (launch/sharding
    cache_pspecs over a ring axis, the 512-device dry-run cells) it
    would gather every slot's full cache per chip, which is exactly the
    per-chip HBM blowup the dry-run fit check guards.  Sharded decode
    stays on the shardable whole-row naive graph until a shard_map'd
    decode kernel exists (ROADMAP: paged KV follow-up)."""
    if (s_q == 1 and t >= tiling.DECODE_FLASH_MIN_KV
            and dispatch.ambient_mesh() is None):
        return "flash_decode"
    return blocked_impl() if use_flash(s_q, t) else "naive"


def _attention_entry(q, k, v, *, q_pos, kv_valid, causal, scale,
                     softmax_impl="float", ring_axis=""):
    if softmax_impl != "float":
        raise ValueError(
            "attn_impl='flash' is the float blocked path and cannot honor "
            f"softmax_impl={softmax_impl!r} (a dualmode word contract) — "
            "use 'naive' or 'flash_pallas_int'")
    return flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                           causal=causal, scale=scale)


dispatch.register_attention(
    "flash", _attention_entry,
    modes=("float",), grad=True,
    note="pure-JAX blocked online softmax (reference VJP)")
dispatch.set_attention_auto_rule(_auto_rule)
