"""Mixture-of-Experts: token-choice top-k routing, one layer that may hold
only a share of the experts, and three dispatch paths.

Routing scores every one of the ``n_experts`` router outputs (softmax,
greedy top-k), renormalises the k gates only if ``norm_topk_prob`` and
scales them by ``routed_scale``; with ``router_f32`` the router's product
and the gates are float32, as DeepSeek-V2's gate computes them.  The layer
holds experts ``[first_held, first_held + n_held)`` of them (all by
default): one chip's share under expert parallelism.  An expert it does
not hold adds nothing, so the layer's output is that share's part of the
result, plus the shared experts, which every share computes.

  serving ('dropless', the engine's prefill and decode) — the routed
            rows that land on held experts, sorted by expert and run
            through ``jax.lax.ragged_dot``: no capacity, so no token is
            dropped at any chunk length and a token's output never
            depends on what else shares its chunk.  Only held experts'
            rows are computed.  Under a mesh (``axes`` given) serving
            keeps the group-local 'sort' dispatch below with its pins:
            dropless up to ``dropless_max_seq`` tokens a sequence, then
            bounded at ``inference_cf`` x the balanced load (a flattened
            sort there would gather the global token set); a held share
            is served on one chip only.
  'sort'  — training, GROUP-LOCAL: every sequence routes its own S
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
  'dense' — reference path: compute every held expert for every token,
            weight by gates.  Exact (no capacity drops); used by tests as
            the oracle and by tiny smoke configs.

Includes shared experts (DeepSeek-V2) and the standard load-balance aux
loss.  Expert FFNs use the configured activation, so the paper's dual-mode
unit serves MoE experts too.  The router, the held experts and the shared
experts run under the named scopes ``moe.route``, ``moe.experts`` and
``moe.shared``.
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
    # mesh serving's capacity: truly dropless (cap=S) is exact for short
    # sequences but at 32k-token prefill the worst-case buffer is S/E-fold
    # oversized (hundreds of TB) — beyond this length capacity is bounded
    # at inference_cf x the balanced load.  One chip serves dropless.
    dropless_max_seq: int = 1024
    inference_cf: float = 2.0
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    router_f32: bool = False  # float32 router product and gates
    first_held: int = 0       # held experts [first_held, +n_held)
    n_held: int = 0           # 0 = all n_experts

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


def moe_init(key, s: MoESpec, dtype) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    kg, ku, kd = jax.random.split(ke, 3)
    e = max(s.ep_pad, s.held)            # padded experts are dead weight
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

    With ``router_f32`` the router's product and the gates are float32
    (DeepSeek-V2's gate); otherwise the product is in the activation
    dtype and the gates come back in it.  Routing stays in batch-major
    layout end to end — a flattened (T,E) router forces GSPMD to
    all-gather the global token set for top_k (measured 10.7 GB/step at
    granite train_4k)."""
    if s.router_f32:
        logits = jnp.dot(x, p["router"],
                         preferred_element_type=jnp.float32)  # (B,S,E)
    else:
        logits = (x @ p["router"]).astype(jnp.float32)       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, s.top_k)               # (B,S,k)
    if s.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if s.routed_scale != 1.0:
        gates = gates * s.routed_scale
    # aux loss: E * sum_e f_e * p_e   (Switch Transformer eq. 4); counts
    # via one-hot sums (shard-local), not a global scatter
    me = jnp.mean(probs, axis=(0, 1))                        # (E,)
    ce = jnp.sum(jax.nn.one_hot(idx, s.n_experts, dtype=jnp.float32),
                 axis=(0, 1, 2))
    ce = ce / (x.shape[0] * x.shape[1] * s.top_k)
    aux = s.n_experts * jnp.sum(me * ce)
    return gates.astype(jnp.float32 if s.router_f32 else x.dtype), idx, aux


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

    ``dropless`` (mesh serving): capacity S up to ``dropless_max_seq``,
    so nothing is dropped; longer sequences are bounded at
    ``inference_cf``.

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
        cap = min(int(math.ceil(sl * k / s.n_experts * cf)), sl)

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


def _held_slot(s: MoESpec, idx):
    """Each routed slot's expert in the held stack, and whether it is held
    at all; an absent expert's slot points one past the stack."""
    local = idx - s.first_held
    held = (local >= 0) & (local < s.held)
    return jnp.where(held, local, max(s.ep_pad, s.held)), held


def _moe_dense(p: Params, s: MoESpec, x_flat, gates, idx):
    # (T,d) through every held expert: (E,T,d); weight by scattered gates
    act = get_activation(s.activation)
    g = jnp.einsum("td,edf->etf", x_flat, p["gate"])
    u = jnp.einsum("td,edf->etf", x_flat, p["up"])
    h = jnp.einsum("etf,efd->etd", act(g) * u, p["down"])     # (E,T,d)
    w = jnp.zeros((x_flat.shape[0], p["gate"].shape[0]), gates.dtype)
    slot, _ = _held_slot(s, idx)
    w = jax.vmap(lambda wi, ii, gi: wi.at[ii].add(gi, mode="drop"))(
        w, slot, gates)
    return jnp.einsum("etd,te->td", h, w)


def _moe_held(p: Params, s: MoESpec, x_flat, gates, idx):
    """The serving dispatch: x_flat (T,d), gates/idx (T,k) -> (y (T,d),
    routed rows that landed on held experts).

    The T*k routed slots are sorted by held expert (absent ones last) and
    the held groups run through ``ragged_dot``; no capacity, so nothing
    is dropped.  The buffer is T*k rows, the most a held share can be
    routed, and only the leading groups are computed."""
    t, d = x_flat.shape
    slot, held = _held_slot(s, idx.reshape(-1))
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.zeros((p["gate"].shape[0],), jnp.int32).at[slot].add(
        1, mode="drop")
    rows = x_flat[order // s.top_k]                         # (T*k, d)
    act = get_activation(s.activation)
    g = jax.lax.ragged_dot(rows, p["gate"], sizes)
    u = jax.lax.ragged_dot(rows, p["up"], sizes)
    h = jax.lax.ragged_dot(act(g) * u, p["down"], sizes)
    # back to token-major slots; rows past the held groups are not
    # computed and are selected away, never multiplied
    h = jnp.zeros_like(h).at[order].set(h, unique_indices=True)
    h = jnp.where(held[:, None], h, 0).reshape(t, s.top_k, d)
    return (jnp.sum(h * gates[..., None], axis=1),
            jnp.sum(held, dtype=jnp.float32))


def moe_apply(p: Params, s: MoESpec, x, dropless: bool = False, axes=None):
    """x: (B,S,d) -> (y, aux).

    Training (``dropless=False``): capacity-bounded 'sort' dispatch (or
    the 'dense' oracle); ``aux`` is the load-balance loss.  A held share
    is served only: training holds every expert.

    Serving (``dropless=True``): the held experts' routed rows through
    ``ragged_dot`` (or the 'dense' oracle), with no capacity — dropping
    would make a token's output depend on what else shares the batch;
    ``aux`` is the number of routed rows that landed on held experts, a
    float32 count reduced on the device.  Under a mesh (``axes``) serving
    runs the pinned group-local 'sort' dispatch (module docstring) and
    holds every expert."""
    b, sl, d = x.shape
    with jax.named_scope("moe.route"):
        gates, idx, aux = _route(p, s, x)
    x_flat = x.reshape(-1, d)
    g_flat, i_flat = gates.reshape(-1, s.top_k), idx.reshape(-1, s.top_k)
    if s.held != s.n_experts and (axes is not None or not dropless):
        raise ValueError("an expert share (n_held < n_experts) is served "
                         "on one chip only; training and mesh serving hold "
                         "every expert")
    if dropless and axes is not None and s.dispatch != "dense":
        with jax.named_scope("moe.experts"):
            y = _moe_sort(p, s, x, gates.astype(x.dtype), idx,
                          dropless=True, axes=axes)
        aux = jnp.float32(b * sl * s.top_k)
    elif dropless:
        with jax.named_scope("moe.experts"):
            if s.dispatch == "dense":
                y = _moe_dense(p, s, x_flat, g_flat, i_flat)
                aux = jnp.sum(_held_slot(s, i_flat)[1], dtype=jnp.float32)
            else:
                y, aux = _moe_held(p, s, x_flat, g_flat, i_flat)
        y = y.astype(x.dtype).reshape(b, sl, d)
    elif s.dispatch == "dense":
        with jax.named_scope("moe.experts"):
            y = _moe_dense(p, s, x_flat, g_flat.astype(x.dtype),
                           i_flat).reshape(b, sl, d)
    else:
        with jax.named_scope("moe.experts"):
            y = _moe_sort(p, s, x, gates.astype(x.dtype), idx, axes=axes)
    if s.n_shared:
        with jax.named_scope("moe.shared"):
            y = y + mlp(p["shared"], x, s.activation, impl=s.ffn_impl)
    return y, aux
