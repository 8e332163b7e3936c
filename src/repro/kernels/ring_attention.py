"""Sequence-parallel ring flash attention over a mesh axis.

The ROADMAP's last kernel item: compose the blocked Pallas flash
attention with the distributed layer.  Online softmax is an associative,
commutative monoid (Milakov & Gimelshein), so per-shard ``(m, l, acc)``
partials merge EXACTLY via :func:`repro.kernels.datapath.
online_softmax_merge` no matter how the key set was split — ring
attention is the datapath's fold run across devices, and the existing
``(m, l)`` residual contract is the interface:

  * Q (with its global positions) stays put, sharded along the sequence
    dim over ``axis``; the K/V/kv_valid shards rotate around the ring
    with ``jax.lax.ppermute``, each carrying its global key offset.
  * Each hop runs the EXISTING single-device Pallas kernel
    (``flash_attention_pallas(..., return_stats=True)``) on the local q
    shard against the visiting KV shard — the kernel sees shard-local
    key positions, so the hop shifts ``q_pos`` by the shard's offset —
    and merges the hop's partial into the running (m, l, acc).
  * Causal hops whose KV shard lies entirely in every local row's
    future are skipped (``lax.cond``): such a shard would contribute
    only the exp(MASK_VALUE) ~ 1e-13 relative mass of fully-masked keys,
    and not visiting it at all is where the ring's throughput win lives
    (the diagonal wavefront does ~half the hops of the full rotation).

Backward: the custom VJP composes the PR-3 dq and dk/dv kernels
(``kernels/flash_attention_bwd.py``) per hop with a REVERSE rotation in
which each KV shard travels the ring together with its dk/dv
accumulator — every q shard adds its contribution as the block visits,
and after the full circle the accumulator arrives back on the shard
that owns the KV block.  dS is formed from the MERGED (m, l) — the
whole-row statistics — so each hop's tile gradients are exactly the
single-device backward's for those columns, and dq sums over hops.

Shapes match every other flash flavor (GQA/MLA compatible):

    q (B, S, K, G, h)   k (B, T, K, h)   v (B, T, K, hv) -> (B, S, K, G, hv)

with S and T both divisible by the ring axis size.  Runs on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` with
``interpret=True`` (the default on the CPU backend) — the multi-device
CI lane.

DUAL-MODE ring (``softmax_impl='dualmode'``, forward-only): the snapped
int monoid of :mod:`repro.core.softmax_unit` is a partial contract
exactly like ``(m, l, o*l)``, so each hop runs the one-sweep int kernel
(``flash_attention_pallas_int(..., return_partial=True)``) and folds the
``(m snapped, S buckets, acc)`` hop partial with
:func:`repro.core.softmax_unit.online_merge_int`.  The guard shift is
fixed from the GLOBAL key count before sharding, so every shard's words
are the whole-row unit's words and the fold is word-exact regardless of
ring size or hop order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import softmax_unit as unit

from . import datapath as dp
from . import dispatch, tiling
from .flash_attention import flash_attention_pallas
from .flash_attention_bwd import flash_attention_bwd_pallas
from .flash_attention_int import flash_attention_pallas_int


def _stats_to_rows(x):
    """(B, K, G, S) kernel-stat layout -> (B, S, K, G, 1) merge layout."""
    return jnp.moveaxis(x, 3, 1)[..., None]


def _rows_to_stats(x):
    """(B, S, K, G, 1) merge layout -> (B, K, G, S) kernel-stat layout."""
    return jnp.moveaxis(x[..., 0], 1, 3)


def _ring_perm(n: int, reverse: bool = False):
    if reverse:
        return [(i, (i - 1) % n) for i in range(n)]
    return [(i, (i + 1) % n) for i in range(n)]


def _rotate(tree, axis: str, perm):
    return jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), tree)


# --------------------------------------------------------------------------
# per-shard loops (run INSIDE shard_map; q pre-scaled f32)
# --------------------------------------------------------------------------

def _ring_fwd_local(qf, k, v, q_pos, kv_valid, *, axis, n_shards, t_loc,
                    causal, block_q, block_kv, interpret, skip_hops):
    b, s_loc, kh, g, _ = qf.shape
    hv = v.shape[-1]
    off0 = (jax.lax.axis_index(axis) * t_loc).astype(jnp.int32)[None]
    qpos_max = jnp.max(q_pos)
    perm = _ring_perm(n_shards)

    m0 = jnp.full((b, s_loc, kh, g, 1), dp.MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, s_loc, kh, g, 1), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, kh, g, hv), jnp.float32)

    def hop(carry, _):
        k_c, v_c, valid_c, off_c, m, l, acc = carry

        def run(m_, l_, acc_):
            o_h, m_h, l_h = flash_attention_pallas(
                qf, k_c, v_c, q_pos=q_pos - off_c[0], kv_valid=valid_c,
                causal=causal, scale=1.0, block_q=block_q,
                block_kv=block_kv, interpret=interpret, return_stats=True)
            m_h, l_h = _stats_to_rows(m_h), _stats_to_rows(l_h)
            # o = acc/l (online_softmax_finish): o*l recovers the shard's
            # unnormalized accumulator, the mergeable partial
            acc_h = o_h.astype(jnp.float32) * l_h
            return dp.online_softmax_merge((m_, l_, acc_),
                                           (m_h, l_h, acc_h))

        if skip_hops and causal:
            m, l, acc = jax.lax.cond(
                off_c[0] <= qpos_max, run,
                lambda m_, l_, acc_: (m_, l_, acc_), m, l, acc)
        else:
            m, l, acc = run(m, l, acc)
        k_c, v_c, valid_c, off_c = _rotate((k_c, v_c, valid_c, off_c),
                                           axis, perm)
        return (k_c, v_c, valid_c, off_c, m, l, acc), None

    carry0 = (k, v, kv_valid, off0, m0, l0, acc0)
    (_, _, _, _, m, l, acc), _ = jax.lax.scan(hop, carry0, None,
                                              length=n_shards)
    out = dp.online_softmax_finish(l, acc).astype(v.dtype)
    return out, _rows_to_stats(m), _rows_to_stats(l)


def _ring_fwd_local_int(qf, k, v, q_pos, kv_valid, *, axis, n_shards,
                        t_loc, causal, block_q, block_kv, interpret,
                        skip_hops, guard_shift):
    """Dual-mode twin of ``_ring_fwd_local``: the hop partial is the
    snapped int monoid state, folded with ``online_merge_int``.  The
    caller fixes ``guard_shift`` from the GLOBAL key count so hop words
    match the whole-row unit's.  Forward-only."""
    b, s_loc, kh, g, _ = qf.shape
    hv = v.shape[-1]
    nb = unit.N_SNAP_BUCKETS
    off0 = (jax.lax.axis_index(axis) * t_loc).astype(jnp.int32)[None]
    qpos_max = jnp.max(q_pos)
    perm = _ring_perm(n_shards)

    m0 = jnp.full((b, s_loc, kh, g, 1), unit.SNAP_MIN, jnp.int32)
    S0 = jnp.zeros((b, s_loc, kh, g, nb), jnp.int32)
    acc0 = jnp.zeros((b, s_loc, kh, g, hv), jnp.float32)

    def hop(carry, _):
        k_c, v_c, valid_c, off_c, m, S, acc = carry

        def run(m_, S_, acc_):
            acc_h, m_h, S_h = flash_attention_pallas_int(
                qf, k_c, v_c, q_pos=q_pos - off_c[0], kv_valid=valid_c,
                causal=causal, scale=1.0, block_q=block_q,
                block_kv=block_kv, interpret=interpret,
                guard_shift=guard_shift, return_partial=True)
            # stats (B,K,G,S[,nb]) -> merge rows (B,S,K,G,[1|nb])
            m_h = _stats_to_rows(m_h)
            S_h = jnp.moveaxis(S_h, 3, 1)
            return unit.online_merge_int((m_, S_, acc_), (m_h, S_h, acc_h))

        if skip_hops and causal:
            m, S, acc = jax.lax.cond(
                off_c[0] <= qpos_max, run,
                lambda m_, S_, acc_: (m_, S_, acc_), m, S, acc)
        else:
            m, S, acc = run(m, S, acc)
        k_c, v_c, valid_c, off_c = _rotate((k_c, v_c, valid_c, off_c),
                                           axis, perm)
        return (k_c, v_c, valid_c, off_c, m, S, acc), None

    carry0 = (k, v, kv_valid, off0, m0, S0, acc0)
    (_, _, _, _, m, S, acc), _ = jax.lax.scan(hop, carry0, None,
                                              length=n_shards)
    l = unit.online_finish_int(S)                      # (B, S_loc, K, G)
    return (acc / l[..., None].astype(jnp.float32)).astype(v.dtype)


def _ring_bwd_local(qf, k, v, o, m, l, do, q_pos, kv_valid, *, axis,
                    n_shards, t_loc, causal, block_q, block_kv, interpret,
                    skip_hops):
    b, s_loc, kh, g, hd = qf.shape
    off0 = (jax.lax.axis_index(axis) * t_loc).astype(jnp.int32)[None]
    qpos_max = jnp.max(q_pos)
    # reverse rotation: each KV shard travels WITH its dk/dv accumulator
    # and is home again after the full circle
    perm = _ring_perm(n_shards, reverse=True)

    dq0 = jnp.zeros((b, s_loc, kh, g, hd), jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def hop(carry, _):
        k_c, v_c, valid_c, off_c, dk_c, dv_c, dq = carry

        def run(dq_, dk_, dv_):
            dq_h, dk_h, dv_h = flash_attention_bwd_pallas(
                qf, k_c, v_c, o, m, l, do, q_pos=q_pos - off_c[0],
                kv_valid=valid_c, causal=causal, block_q=block_q,
                block_kv=block_kv, interpret=interpret)
            return dq_ + dq_h, dk_ + dk_h, dv_ + dv_h

        if skip_hops and causal:
            dq, dk_c, dv_c = jax.lax.cond(
                off_c[0] <= qpos_max, run,
                lambda dq_, dk_, dv_: (dq_, dk_, dv_), dq, dk_c, dv_c)
        else:
            dq, dk_c, dv_c = run(dq, dk_c, dv_c)
        k_c, v_c, valid_c, off_c, dk_c, dv_c = _rotate(
            (k_c, v_c, valid_c, off_c, dk_c, dv_c), axis, perm)
        return (k_c, v_c, valid_c, off_c, dk_c, dv_c, dq), None

    carry0 = (k, v, kv_valid, off0, dk0, dv0, dq0)
    (_, _, _, _, dk, dv, dq), _ = jax.lax.scan(hop, carry0, None,
                                               length=n_shards)
    return dq, dk, dv


def _ring_local(qf, k, v, q_pos, kv_valid, *, return_stats, **kw):
    """shard_map body: custom VJP around the two ring loops."""
    if return_stats:
        return _ring_fwd_local(qf, k, v, q_pos, kv_valid, **kw)

    @jax.custom_vjp
    def run(qf_, k_, v_, q_pos_, kv_valid_):
        out, _, _ = _ring_fwd_local(qf_, k_, v_, q_pos_, kv_valid_, **kw)
        return out

    def fwd(qf_, k_, v_, q_pos_, kv_valid_):
        out, m, l = _ring_fwd_local(qf_, k_, v_, q_pos_, kv_valid_, **kw)
        return out, (qf_, k_, v_, out, m, l, q_pos_, kv_valid_)

    def bwd(res, gy):
        import numpy as np
        qf_, k_, v_, o, m, l, q_pos_, kv_valid_ = res
        dq, dk, dv = _ring_bwd_local(
            qf_, k_, v_, o, m, l, gy.astype(jnp.float32), q_pos_,
            kv_valid_, **kw)
        f0 = jax.dtypes.float0
        return (dq, dk.astype(k_.dtype), dv.astype(v_.dtype),
                np.zeros(q_pos_.shape, f0), np.zeros(kv_valid_.shape, f0))

    run.defvjp(fwd, bwd)
    return run(qf, k, v, q_pos, kv_valid)


# --------------------------------------------------------------------------
# global-array entry
# --------------------------------------------------------------------------

def ring_flash_attention(q, k, v, *, q_pos, kv_valid, mesh=None,
                         axis: str = "model", causal: bool = True,
                         scale: float | None = None,
                         block_q: int | None = None,
                         block_kv: int | None = None,
                         interpret: bool | None = None,
                         skip_masked_hops: bool = True,
                         return_stats: bool = False,
                         softmax_impl: str = "float"):
    """Sequence-parallel ring flash attention (see module docstring).

    Takes GLOBAL arrays and wraps the per-shard ring loop in a
    ``shard_map`` over ``axis``: q/q_pos/k/v/kv_valid shard along their
    sequence dims, everything else is replicated.  ``mesh=None`` picks
    up the ambient ``jax.set_mesh`` context.  Differentiable: the custom
    VJP composes the dedicated backward kernels per hop (reverse
    rotation, dk/dv accumulated on the shard that owns the KV block).

    ``return_stats=True`` returns ``(out, m, l)`` with the MERGED
    whole-row statistics laid out (B, K, G, S) — the same residual
    contract as the single-device kernel, which parity tests pin the
    merge against.  ``skip_masked_hops=False`` forces every hop to run
    (the skipped hops' only contribution is the exp(MASK_VALUE) mass of
    fully-masked keys, ~1e-13 relative).

    ``softmax_impl='dualmode'`` runs the snapped int monoid per hop (see
    module docstring) — forward-only, and ``return_stats`` is not
    supported there (the int partial is (m, S-buckets, acc), a different
    residual contract).
    """
    if mesh is None:
        mesh = dispatch.ambient_mesh()
    if mesh is None or axis not in mesh.axis_names:
        raise ValueError(
            f"ring_flash_attention needs a mesh with axis {axis!r} — pass "
            "mesh= or run under `with jax.set_mesh(mesh):` (launch/mesh.auto_mesh)")
    n = mesh.shape[axis]
    s_q, hd = q.shape[1], q.shape[-1]
    t = k.shape[1]
    if s_q % n or t % n:
        raise ValueError(
            f"flash_ring shards the sequence dims over {axis!r} (size "
            f"{n}): s_q={s_q} and t_kv={t} must both divide")
    if interpret is None:
        interpret = tiling.interpret_mode()
    scale = (1.0 / hd ** 0.5) if scale is None else scale
    bq, bkv = tiling.attention_blocks(s_q // n, t // n)
    bq = bq if block_q is None else block_q
    bkv = bkv if block_kv is None else block_kv

    # fold the scale into q HERE, outside the custom_vjp — d(scale) flows
    # through the multiply and the ring loops stay scale-free, exactly
    # like the single-device kernel
    qf = q.astype(jnp.float32) * jnp.float32(scale)
    if softmax_impl == "dualmode":
        if return_stats:
            raise ValueError(
                "ring_flash_attention: return_stats is a float (m, l) "
                "residual contract; the dualmode ring folds (m, S, acc) "
                "int partials and does not expose them")
        # the whole-row guard, from the key count BEFORE sharding
        local = functools.partial(
            _ring_fwd_local_int, axis=axis, n_shards=n, t_loc=t // n,
            causal=causal, block_q=bq, block_kv=bkv, interpret=interpret,
            skip_hops=skip_masked_hops,
            guard_shift=max(0, t.bit_length() - 16))
    elif softmax_impl == "float":
        local = functools.partial(
            _ring_local, axis=axis, n_shards=n, t_loc=t // n, causal=causal,
            block_q=bq, block_kv=bkv, interpret=interpret,
            skip_hops=skip_masked_hops, return_stats=return_stats)
    else:
        raise ValueError(
            f"ring_flash_attention softmax_impl={softmax_impl!r}: expected "
            "'float' or 'dualmode'")

    def seq(nd: int, d: int = 1) -> P:
        return P(*[axis if i == d else None for i in range(nd)])

    in_specs = (seq(5), seq(4), seq(4), seq(2), seq(2))
    out_specs = ((seq(5), seq(4, 3), seq(4, 3)) if return_stats
                 else seq(5))
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(qf, k, v, q_pos.astype(jnp.int32), kv_valid)


def vmem_plan(s_q: int, t_kv: int, hd: int, hv: int, g: int = 1,
              n_shards: int = 8):
    """Static VMEM residency of the ring's per-hop local kernels.

    The ring never launches a kernel of its own — each hop runs the
    single-device flash kernels on the SHARD-LOCAL extents, so the plan
    delegates to those modules at (s_q/n, t_kv/n) and namespaces the
    calls per hop."""
    from . import flash_attention, flash_attention_int
    s_loc = max(s_q // n_shards, 1)
    t_loc = max(t_kv // n_shards, 1)
    out = {}
    for mod in (flash_attention, flash_attention_int):
        for name, plan in mod.vmem_plan(s_loc, t_loc, hd, hv, g).items():
            out[f"ring_hop_{name}"] = plan
    return out


def _attention_entry(q, k, v, *, q_pos, kv_valid, causal, scale,
                     softmax_impl="float", ring_axis="model"):
    impl = ("dualmode" if softmax_impl in ("dualmode", "dualmode_snap")
            else "float")
    return ring_flash_attention(q, k, v, q_pos=q_pos, kv_valid=kv_valid,
                                causal=causal, scale=scale,
                                axis=ring_axis or "model",
                                softmax_impl=impl)


dispatch.register_attention(
    "flash_ring", _attention_entry,
    modes=("float", "dualmode", "dualmode_snap"), grad=True,
    needs_mesh=True, mesh_safe=True,
    note="shard_map ring over the KV axis; requires an ambient mesh")
