"""Mixture-of-Experts: token-choice top-k routing with two dispatch paths.

  'sort'  — production path, GROUP-LOCAL: every sequence routes its own S
            tokens (sort by expert id within the sequence, scatter into a
            per-sequence (E, C_g, d) capacity buffer, batched expert FFN,
            gather back).  Because the group axis is the batch axis, the
            sort/scatter never crosses a data shard — GSPMD keeps dispatch
            local and the only collective is the einsum-aligned exchange
            with the expert-parallel weights over 'model'.  (A global sort
            over the 1M-token train_4k batch measured 170s of all-gather
            per step at 256 chips — group-local dispatch removes it.)
            Capacity is per group: C_g = ceil(S*k/E * cf), the per-batch
            balance modern MoE trainers use.
  'dense' — reference path: compute every expert for every token, weight by
            gates.  Exact (no capacity drops); used by tests as the oracle
            and by tiny smoke configs.

Includes shared experts (DeepSeek-V2) and the standard load-balance aux
loss.  Expert FFNs use the configured activation, so the paper's dual-mode
unit serves MoE experts too.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.activations import get_activation
from .layers import Params, dense_init, mlp, mlp_init


def _ambient_axis_size(axis) -> int:
    """Size of a mesh axis from the ambient ``jax.set_mesh`` context (1 if
    no mesh / unknown axis — pins become no-risk no-ops)."""
    pm = jax.sharding.get_abstract_mesh()
    total = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        total *= dict(pm.shape).get(a, 1)
    return total


class MoESpec(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0         # DeepSeek-style always-on experts
    capacity_factor: float = 1.25
    activation: str = "silu"
    ffn_impl: str = "dense"   # shared-expert MLP execution (dispatch registry)
    dispatch: str = "sort"    # 'sort' | 'dense'
    ep_pad: int = 0           # padded stack size (0 = n_experts)
    # inference capacity: truly dropless (cap=S) is exact for short
    # sequences (decode, engine tests) but at 32k-token prefill the
    # worst-case buffer is S/E-fold oversized (hundreds of TB) — beyond
    # this length we bound capacity at inference_cf x the balanced load,
    # the standard serving trade-off.
    dropless_max_seq: int = 1024
    inference_cf: float = 2.0


def moe_init(key, s: MoESpec, dtype) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    kg, ku, kd = jax.random.split(ke, 3)
    e = max(s.ep_pad, s.n_experts)       # padded experts are dead weight
    p = {
        "router": dense_init(kr, s.d_model, s.n_experts, dtype,
                             scale=0.02),
        "gate": _stack_init(kg, e, s.d_model, s.d_ff, dtype),
        "up": _stack_init(ku, e, s.d_model, s.d_ff, dtype),
        "down": _stack_init(kd, e, s.d_ff, s.d_model, dtype),
    }
    if s.n_shared:
        p["shared"] = mlp_init(ks, s.d_model, s.d_ff * s.n_shared, dtype,
                               gated=True)
    return p


def _stack_init(key, e: int, d_in: int, d_out: int, dtype):
    return (jax.random.normal(key, (e, d_in, d_out))
            * (1.0 / math.sqrt(d_in))).astype(dtype)


def _route(p: Params, s: MoESpec, x):
    """(B,S,d) -> gates (B,S,k), expert idx (B,S,k), aux loss.

    Routing stays in batch-major layout end to end — a flattened (T,E)
    router forces GSPMD to all-gather the global token set for top_k
    (measured 10.7 GB/step at granite train_4k)."""
    logits = (x @ p["router"]).astype(jnp.float32)           # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, s.top_k)               # (B,S,k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # aux loss: E * sum_e f_e * p_e   (Switch Transformer eq. 4); counts
    # via one-hot sums (shard-local), not a global scatter
    me = jnp.mean(probs, axis=(0, 1))                        # (E,)
    ce = jnp.sum(jax.nn.one_hot(idx, s.n_experts, dtype=jnp.float32),
                 axis=(0, 1, 2))
    ce = ce / (x.shape[0] * x.shape[1] * s.top_k)
    aux = s.n_experts * jnp.sum(me * ce)
    return gates.astype(x.dtype), idx, aux


def _expert_ffn(p: Params, s: MoESpec, xb):
    """Batched expert FFN over buffers xb: (E, C, d) -> (E, C, d)."""
    act = get_activation(s.activation)
    g = jnp.einsum("ecd,edf->ecf", xb, p["gate"])
    u = jnp.einsum("ecd,edf->ecf", xb, p["up"])
    return jnp.einsum("ecf,efd->ecd", act(g) * u, p["down"])


# ---------------- custom-VJP dispatch/combine ----------------
# Autodiff transposes a gather into a GENERIC scatter-add; GSPMD lowers
# those with its replicate+mask+all-reduce fallback (measured 0.4-6.6 TB
# of backward collectives per MoE train step).  These custom VJPs keep
# BOTH directions in the forms GSPMD partitions cleanly, and every float
# gather/scatter is TOKEN-MAJOR 2D-indexed ((t,k) -> (e, rank) tables) —
# float permutation-gathers in expert-sorted order measured 6.6 TB of
# all-reduce at granite train_4k; only the int rank tables are sorted.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(x_flat, idx, rank_tok, cap: int, e_buf: int):
    """(t,d) tokens -> (e_buf, cap, d) expert buffer.

    idx/rank_tok: (t,k) expert id and within-expert rank per slot; the
    (e, rank) pairs are unique; rank >= cap drops (capacity)."""
    t, k = idx.shape
    d = x_flat.shape[-1]
    xk = jnp.broadcast_to(x_flat[:, None, :], (t, k, d))
    buf = jnp.zeros((e_buf, cap, d), x_flat.dtype)
    return buf.at[idx.reshape(-1), rank_tok.reshape(-1)].set(
        xk.reshape(t * k, d), mode="drop", unique_indices=True)


def _dispatch_fwd(x_flat, idx, rank_tok, cap, e_buf):
    return _dispatch(x_flat, idx, rank_tok, cap, e_buf), (idx, rank_tok)


def _dispatch_bwd(cap, e_buf, res, dbuf):
    idx, rank_tok = res
    # token-major gather of each slot's grad, summed over the k slots
    slots = dbuf.at[idx, rank_tok].get(mode="fill", fill_value=0)
    return slots.sum(axis=1).astype(dbuf.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _combine(h, gk_tok, idx, rank_tok):
    """y (t,d) = sum_k gk[t,k] * h[idx[t,k], rank_tok[t,k]]."""
    slots = h.at[idx, rank_tok].get(mode="fill", fill_value=0)  # (t,k,d)
    return jnp.sum(slots * gk_tok[..., None], axis=1)


def _combine_fwd(h, gk_tok, idx, rank_tok):
    return _combine(h, gk_tok, idx, rank_tok), (h, gk_tok, idx, rank_tok)


def _combine_bwd(res, dy):
    h, gk_tok, idx, rank_tok = res
    t, k = idx.shape
    dyk = jnp.broadcast_to(dy[:, None, :], (t, k, dy.shape[-1]))
    dh = jnp.zeros_like(h).at[idx.reshape(-1), rank_tok.reshape(-1)].set(
        (dyk * gk_tok[..., None]).reshape(t * k, -1).astype(h.dtype),
        mode="drop", unique_indices=True)
    slots = h.at[idx, rank_tok].get(mode="fill", fill_value=0)
    dgk = jnp.sum(dyk * slots, axis=-1).astype(gk_tok.dtype)
    return dh, dgk, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _moe_sort_local(p: Params, s: MoESpec, x_flat, gates, idx, cap: int,
                    e_buf: int | None = None):
    """One group's dispatch: x_flat (S,d), gates/idx (S,k) -> buffers.

    Only INT arrays are sorted (to compute each slot's within-expert
    rank); all float traffic moves through the token-major custom-VJP
    dispatch/combine above."""
    t, d = x_flat.shape
    n_slots = t * s.top_k

    flat_e = idx.reshape(-1)                                  # (S*k,)
    order = jnp.argsort(flat_e)                               # stable
    e_sorted = flat_e[order]
    unsort = jnp.argsort(order)

    # rank within expert = position - start offset of that expert
    counts = jnp.zeros((s.n_experts,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                      # exclusive
    rank = jnp.arange(n_slots) - starts[e_sorted]
    rank_tok = rank[unsort].reshape(t, s.top_k)               # token-major
    gk_tok = gates * (rank_tok < cap)

    buf = _dispatch(x_flat, idx, rank_tok, cap, e_buf or s.n_experts)
    return buf, (gk_tok, rank_tok)


def _moe_sort(p: Params, s: MoESpec, x, gates, idx, dropless=False,
              axes=None):
    """Group-local dispatch over the batch axis.  x (B,S,d) -> (B,S,d).

    `axes` = (dp_axis, ep_axis) mesh-axis names: explicit sharding pins on
    the dispatch buffers — GSPMD loses the batch sharding through the
    batched scatter otherwise (measured: full-B f32 buffers replicated on
    every chip, 60+ GiB at jamba train_4k)."""
    b, sl, d = x.shape
    k = s.top_k
    if dropless and sl <= s.dropless_max_seq:
        cap = sl       # an expert can receive at most S slots: zero drops
    else:
        cf = s.inference_cf if dropless else s.capacity_factor
        cap = int(math.ceil(sl * k / s.n_experts * cf))
        cap = min(cap, sl)

    e_buf = max(s.ep_pad, s.n_experts)
    # Two dispatch layouts (chosen at trace time from shapes + mesh):
    #  * batch-DP: expert stacks are SMALL (granite: 80 MB/layer) ->
    #    replicate the weights and shard the batch-group dim over the
    #    WHOLE mesh.  Every scatter/gather is shard-local; GSPMD's
    #    sharded-scatter fallback (measured 1.27 TB of all-reduce per
    #    granite train step — 94% of its collectives) never fires.
    #  * EP: big stacks shard over 'model'; the buffer resharding becomes
    #    the expert all-to-all.
    small_stacks = (p["gate"].size * p["gate"].dtype.itemsize) <= (1 << 28)
    if axes is not None:
        dp, ep = axes
        dp_t = tuple(dp) if isinstance(dp, tuple) else (dp,)
        full = dp_t + ((ep,) if ep and ep not in dp_t else ())
        if small_stacks and b % _ambient_axis_size(full) == 0:
            dp, ep = (full if len(full) > 1 else full[0]), None
        elif e_buf % _ambient_axis_size(ep) != 0:
            ep = None            # uneven EP would pad-communicate
        axes = (dp, ep)
    pin = (lambda t, spec: jax.lax.with_sharding_constraint(t, spec)) \
        if axes is not None else (lambda t, spec: t)
    if axes is not None:
        from jax.sharding import PartitionSpec as P
        x = pin(x, P(dp, None, None))
        gates = pin(gates, P(dp, None, None))
        idx = pin(idx, P(dp, None, None))

    bufs, meta = jax.vmap(
        lambda xg, gg, ig: _moe_sort_local(p, s, xg, gg, ig, cap, e_buf))(
            x, gates, idx)                     # bufs: (B, E, C, d)
    if axes is not None:
        # the (dp,None)->(dp,ep) pin pair reads as a redundant reshard
        # but measured BETTER than the single pin (deepseek 18.1 vs 21.9s
        # t_n): the batch-local stop keeps the scatter unsharded on E, so
        # its lowering never hits GSPMD's replicate+all-reduce fallback.
        bufs = pin(bufs, P(dp, None, None, None))
        bufs = pin(bufs, P(dp, ep, None, None))
    h = jnp.einsum("becd,edf->becf", bufs, p["gate"])
    u = jnp.einsum("becd,edf->becf", bufs, p["up"])
    act = get_activation(s.activation)
    h = jnp.einsum("becf,efd->becd", act(h) * u, p["down"])   # (B,E,C,d)
    if axes is not None:
        h = pin(h, P(dp, ep, None, None))
        h = pin(h, P(dp, None, None, None))    # back to batch-local

    def gather_back(hg, m, ig):
        gk_tok, rank_tok = m
        return _combine(hg, gk_tok, ig, rank_tok)

    return jax.vmap(gather_back)(h, meta, idx)


def _moe_dense(p: Params, s: MoESpec, x_flat, gates, idx):
    # (T,d) through every expert: (E,T,d); weight by scattered gates
    act = get_activation(s.activation)
    g = jnp.einsum("td,edf->etf", x_flat, p["gate"])
    u = jnp.einsum("td,edf->etf", x_flat, p["up"])
    h = jnp.einsum("etf,efd->etd", act(g) * u, p["down"])     # (E,T,d)
    w = jnp.zeros((x_flat.shape[0], p["gate"].shape[0]), x_flat.dtype)
    w = jax.vmap(lambda wi, ii, gi: wi.at[ii].add(gi))(w, idx, gates)
    return jnp.einsum("etd,te->td", h, w)


def moe_apply(p: Params, s: MoESpec, x, dropless: bool = False, axes=None):
    """x: (B,S,d) -> (y, aux_loss).

    dropless=True (inference): no token drops up to `dropless_max_seq`
    (capacity-bounded routing is a *training* throughput device and would
    make decode outputs depend on what else shares the batch); longer
    prefills fall back to inference_cf-bounded capacity."""
    b, sl, d = x.shape
    gates, idx, aux = _route(p, s, x)
    if s.dispatch == "dense":
        y = _moe_dense(p, s, x.reshape(-1, d), gates.reshape(-1, s.top_k),
                       idx.reshape(-1, s.top_k)).reshape(b, sl, d)
    else:
        y = _moe_sort(p, s, x, gates, idx, dropless=dropless, axes=axes)
    if s.n_shared:
        y = y + mlp(p["shared"], x, s.activation, impl=s.ffn_impl)
    return y, aux
