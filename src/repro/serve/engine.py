"""Batched serving engine: continuous batching over a paged (or slotted
contiguous) KV cache.

Two cache modes, same host scheduler skeleton:

``paged`` (the default wherever the architecture supports it) — the
vLLM-style layout: per-layer (N, block_size, ...) block pools shared by
every request, one (B, max_blocks) int32 block table, and a host-side
:class:`repro.serve.paged_cache.BlockPool` doing admission/retire as
pure block alloc/free.  Three properties fall out:

  * zero-copy admission: a request is admitted by writing integers into
    its table row — no cache-tree splice, no row copy (``_splice_slot``
    only survives on the contiguous path, and ``stats['cache_copies']``
    counts it);
  * prefix-cache sharing: full prompt blocks are chain-hashed and
    ref-counted, so a request whose prompt extends an already-prefilled
    prefix starts decoding from the shared blocks without recomputing
    (or re-storing) them;
  * chunked prefill: prompts are consumed ``prefill_chunk`` tokens per
    engine step, interleaved with the decode tick, so a long prompt
    never stalls decode traffic.  Compiled-program count stays bounded:
    ONE chunk shape (1, C) + ONE decode shape (B, 1).

``contiguous`` — the seed layout: per-slot (n_slots, max_seq, ...) rows,
bucketed whole-prompt prefill at batch 1, caches spliced per admission.
State-carrying mixers (mamba/rwkv), cross-attention caches and encoders
have nothing to page and stay here; ``cache_mode='auto'`` picks per
architecture.

Serving under pressure (paged mode): ``admission='reactive'`` (the
default) reserves only a request's PROMPT reach at admission and grows
its block table one block at a time from inside the decode loop
(``BlockPool.ensure_reach``) — the table must always cover the next
write position, because out-of-table scatters clamp to the sentinel
block and silently lose data.  On growth shortfall the engine preempts
a victim (``preempt_policy``: lowest priority first, youngest admission
by default) by either dropping its blocks for recompute-on-resume (the
prompt + generated prefix re-enters the queue HEAD as one prefill) or
swapping the block contents to a host-side store (``preempt_mode``).
Backpressure is bounded by ``hol_window`` skip-ahead admission, wall
clocks by per-request ``deadline_s``, and a per-step isfinite sentry
quarantines a slot whose logits go non-finite without touching its
neighbours.  Every request leaves the engine with a reason code in
``engine.reasons``.  All of it is fault-injectable — see
``repro.serve.faults``.

Attention impls are selected PER PHASE through the kernel dispatch
registry exactly as before; on the paged path the resolved decode impl
additionally picks up its block-table native variant from
``dispatch.get_paged_attention`` (flash_decode's scalar-prefetch gather)
inside the model, while impls without one read through a dense gather.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import cache_pspecs, named
from repro.kernels import dispatch, tiling
from repro.models.transformer import (encoder_apply, init_caches,
                                      init_paged_caches, lm_apply,
                                      paged_supported)
from .paged_cache import BlockPool, chain_hashes

Params = Any


# ---------------- compiled steps ----------------

def make_prefill_step(cfg: ModelConfig, act_pspec=None):
    """(params, caches, tokens(B,S), last_idx(B,)[, cross_src]) ->
    (logits(B,V) at each row's last REAL prompt position, caches).

    Logits are computed only at `last_idx` — prompts shorter than the
    padded bucket sample from the right position, and the (B,S,vocab)
    prefill logits tensor never exists.  `act_pspec` pins the residual
    stream on a production mesh (batch over dp; MoE dispatch pins)."""
    def prefill(params, caches, tokens, last_idx, cross_src=None):
        logits, caches, _ = lm_apply(params, cfg, tokens, pos=0,
                                     caches=caches, cross_src=cross_src,
                                     last_pos=last_idx, act_pspec=act_pspec)
        return logits[:, -1, :], caches
    return prefill


def make_decode_step(cfg: ModelConfig, act_pspec=None):
    """(params, caches, tokens(B,1), pos(B,)) -> (logits(B,V), caches).

    `pos` is the current depth of every slot (vector => slots advance
    independently).  Inside the model the vector becomes each row's
    ragged `kv_valid` mask and `q_pos` — which is exactly what the
    split-KV flash-decode kernel keys its per-row tile skip on, so a
    shallow slot does not pay for the deepest slot's cache sweep.
    Cross-attention KV (VLM/enc-dec) is read from the cache written at
    prefill time.
    """
    def decode(params, caches, tokens, pos):
        logits, caches, _ = lm_apply(params, cfg, tokens, pos=pos,
                                     caches=caches, act_pspec=act_pspec)
        return logits[:, -1, :], caches
    return decode


def make_chunk_prefill_step(cfg: ModelConfig, act_pspec=None):
    """(params, caches, tokens(1,C), pos, tables(1,max_blocks),
    last_idx(1,)) -> (logits(1,V), caches) — ONE prompt chunk written
    through the slot's block table at traced offset ``pos``.

    One compiled shape serves every chunk of every prompt: position is a
    traced scalar, the table a traced operand.  ``last_idx`` picks the
    logits row (the chunk's last REAL token) — only the final chunk's
    logits are consumed, the others are (1, V) throwaways."""
    def prefill_chunk(params, caches, tokens, pos, tables, last_idx):
        logits, caches, _ = lm_apply(params, cfg, tokens, pos=pos,
                                     caches=caches, last_pos=last_idx,
                                     act_pspec=act_pspec, paged=tables)
        return logits[:, -1, :], caches
    return prefill_chunk


def make_paged_decode_step(cfg: ModelConfig, act_pspec=None):
    """(params, caches, tokens(B,1), pos(B,), tables(B,max_blocks)) ->
    (logits(B,V), caches) — the lockstep decode tick reading/writing
    K/V through per-slot block tables.  Rows that must not write (free
    slots, slots mid-prefill) are handed all-sentinel table rows, so
    their scatter lands in block 0 and touches nothing live."""
    def decode(params, caches, tokens, pos, tables):
        logits, caches, _ = lm_apply(params, cfg, tokens, pos=pos,
                                     caches=caches, act_pspec=act_pspec,
                                     paged=tables)
        return logits[:, -1, :], caches
    return decode


def _splice_slot(full_tree, row_tree, slot: int):
    """Write batch=1 cache `row_tree` into slot index `slot` of the batched
    cache (CONTIGUOUS mode only — the paged path admits by table writes
    and never copies cache trees).  The batch axis is 1 for
    stacked-period leaves ('periods' in the path carries a leading
    n_periods dim), else 0."""
    def write(path, full, one):
        names = [str(getattr(e, "key", getattr(e, "idx", ""))) for e in path]
        axis = 1 if "periods" in names else 0
        start = [0] * full.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(full, one.astype(full.dtype),
                                            tuple(start))
    return jax.tree_util.tree_map_with_path(write, full_tree, row_tree)


def sample_token(key, logits, temperature: float):
    greedy = jnp.argmax(logits, axis=-1)
    if temperature <= 0.0:
        return greedy
    return jax.random.categorical(key, logits / temperature, axis=-1)


# ---------------- engine ----------------

@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 32
    temperature: float = 0.0
    cross_src: Any = None            # stub frontend embeddings (VLM/encdec)
    deadline_s: float | None = None  # wall-clock budget from submission
    priority: int = 0                # higher = preempted later


@dataclasses.dataclass
class _QEntry:
    """Internal queue record: a fresh submission or a preempted request
    waiting to resume.  Recompute resumes carry ``resume_prompt`` (the
    original prompt + every token generated so far — one prefill redoes
    the dropped KV); swap resumes carry the saved block contents and
    re-enter decode directly at ``pos``."""
    req: Request
    deadline_at: float | None = None
    prior_out: list = dataclasses.field(default_factory=list)
    resume_prompt: list | None = None
    swap: Any = None                 # {'saved': host tree, 'n': #blocks}
    pos: int = 0                     # swap resume: decode depth
    out: list = dataclasses.field(default_factory=list)  # swap resume

    @property
    def is_resume(self) -> bool:
        return self.resume_prompt is not None or self.swap is not None


@dataclasses.dataclass
class _Slot:
    rid: int = -1
    pos: int = 0
    remaining: int = 0
    out: list = dataclasses.field(default_factory=list)
    temperature: float = 0.0
    # paged-mode fields: while `prompt` is set the slot is mid-prefill
    # (`filled` tokens written so far); `blocks` are the table entries
    # this slot holds references on (shared prefix + private).
    prompt: list | None = None
    filled: int = 0
    blocks: list = dataclasses.field(default_factory=list)
    seq: int = 0                     # admission order (FCFS prefill)
    # pressure fields: the ORIGINAL prompt and the tokens generated in
    # earlier incarnations (before a preemption) — `finished[rid]` is
    # always prior_out + out, so resumes are invisible to the caller
    full_prompt: list = dataclasses.field(default_factory=list)
    prior_out: list = dataclasses.field(default_factory=list)
    priority: int = 0
    deadline_at: float | None = None

    @property
    def free(self) -> bool:
        return self.rid < 0

    @property
    def decoding(self) -> bool:
        return self.rid >= 0 and self.prompt is None


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: Params, *,
                 n_slots: int = 4, max_seq: int = 512,
                 eos_id: int | None = None, dtype=jnp.float32,
                 prefill_buckets: tuple[int, ...] = (32, 128, 512),
                 prefill_attn_impl: str | None = None,
                 decode_attn_impl: str | None = None,
                 prefill_softmax_impl: str | None = None,
                 decode_softmax_impl: str | None = None,
                 mesh=None, seed: int = 0,
                 cache_mode: str = "auto",
                 block_size: int | None = None,
                 num_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 admission: str = "reactive",
                 preempt_policy: str = "youngest",
                 preempt_mode: str = "recompute",
                 hol_window: int = 4,
                 faults=None, clock=None):
        self.cfg, self.params = cfg, params
        self.n_slots, self.max_seq = n_slots, max_seq
        self.eos_id = eos_id
        self.dtype = dtype
        # optional device mesh: per-phase resolution AND the compiled
        # programs trace under `jax.set_mesh(mesh)`, so a cfg with ring_axis set
        # resolves long-context prefill to the sequence-parallel ring
        # path (decode stays s_q=1 -> naive/flash_decode) and the
        # flash_ring provider finds the same mesh ambient at trace time
        self.mesh = mesh
        if cache_mode not in ("auto", "paged", "contiguous"):
            raise ValueError(f"unknown cache_mode {cache_mode!r}")
        if admission not in ("reactive", "worst_case"):
            raise ValueError(f"unknown admission {admission!r}")
        if preempt_policy not in ("youngest", "oldest"):
            raise ValueError(f"unknown preempt_policy {preempt_policy!r}")
        if preempt_mode not in ("recompute", "swap"):
            raise ValueError(f"unknown preempt_mode {preempt_mode!r}")
        if cache_mode == "paged":
            if not paged_supported(cfg):
                raise ValueError(
                    "cache_mode='paged' requires attention-only cached "
                    "layers (no mamba/rwkv state, cross-attention, or "
                    "encoder) — use 'auto' or 'contiguous'")
            if mesh is not None:
                raise ValueError(
                    "cache_mode='paged' does not compose with a device "
                    "mesh yet (pools are unsharded) — ROADMAP item 4")
        self.cache_mode = ("paged" if cache_mode == "paged" or
                           (cache_mode == "auto" and paged_supported(cfg)
                            and mesh is None)
                           else "contiguous")
        self.admission = admission
        self.preempt_policy = preempt_policy
        self.preempt_mode = preempt_mode
        self.hol_window = max(1, hol_window)
        self.faults = faults
        self._now = clock or time.monotonic
        self.buckets = tuple(b for b in sorted(prefill_buckets)
                             if b <= max_seq) or (max_seq,)
        # state-carrying mixers (mamba/rwkv) integrate every input token —
        # right-padding a bucket would corrupt their state, so those archs
        # prefill at exact prompt length (one compile per distinct length)
        self._exact_prefill = any(
            s.mixer in ("mamba", "rwkv")
            for s in tuple(cfg.pattern) + tuple(cfg.prefix))

        if self.cache_mode == "paged":
            self.block_size = block_size or tiling.paged_block_size(max_seq)
            self.max_blocks = tiling.cdiv(max_seq, self.block_size)
            # default pool = the contiguous HBM budget (+1 sentinel): at
            # EQUAL memory, admission only reserves what a request can
            # actually reach (prompt+max_new), so more requests fit
            self.num_blocks = num_blocks or (n_slots * self.max_blocks + 1)
            self.prefill_chunk = min(prefill_chunk or 64, max_seq)
            self.pool = BlockPool(self.num_blocks, self.block_size)
            self.caches = init_paged_caches(cfg, self.num_blocks,
                                            self.block_size, dtype)
            self._tables = np.zeros((n_slots, self.max_blocks), np.int32)
        else:
            self.pool = None
            self.caches = init_caches(cfg, n_slots, max_seq, dtype)
        if mesh is not None:
            # state lives on the whole mesh: params replicate, KV caches
            # shard their sequence over the ring axis (the layout the
            # ring prefill rotates), never all on the first device
            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            self.caches = jax.device_put(self.caches, named(
                mesh, cache_pspecs(self.caches, mesh, n_slots,
                                   ring_axis=cfg.ring_axis or None)))

        # per-phase attention impls, resolved once through the dispatch
        # registry at each phase's representative shape (prefill: widest
        # q tile vs the full cache; decode: one q row vs the full cache —
        # long max_seq resolves 'auto' to the split-KV flash_decode
        # kernel, short caches to whole-row naive).  None defers to
        # cfg.attn_impl, so a config that pins a concrete impl keeps it
        # for both phases; resolution is softmax-aware, so a dualmode
        # config routes to the bit-accurate paths instead of silently
        # running the float ones (snapped one-sweep kernel on blocked
        # prefill, the int split-KV path inside flash_decode at decode —
        # the unit no longer forces a whole-row naive fallback anywhere).
        # The softmax impl is ALSO per-phase overridable: float prefill +
        # dualmode decode is a real serving mix (prompt ingestion at
        # float speed, generated words bit-accurate), and each phase's
        # resolution must see the softmax it will actually compile with.
        self.prefill_softmax_impl = (prefill_softmax_impl
                                     or cfg.softmax_impl)
        self.decode_softmax_impl = decode_softmax_impl or cfg.softmax_impl
        if self.cache_mode == "paged":
            prefill_sq = self.prefill_chunk
            t_kv = self.max_blocks * self.block_size
        else:
            prefill_sq = max_seq if self._exact_prefill else self.buckets[-1]
            t_kv = max_seq
        with self._mesh_ctx():
            # the compiled prefill runs at EVERY bucket, so the ring is
            # only offered to 'auto' when each bucket (and the cache
            # depth) divides the ring width — resolving on the widest
            # bucket alone would bake flash_ring into a program that a
            # smaller bucket then crashes.  Exact-length prefill
            # (mamba/rwkv hybrids) sees arbitrary prompt lengths and
            # never rings; decode is s_q=1 and can't either.
            n = dispatch.ring_axis_size(cfg.ring_axis)
            ring_ok = (self.cache_mode == "contiguous"
                       and not self._exact_prefill and n > 1
                       and max_seq % n == 0
                       and all(b % n == 0 for b in self.buckets))
            self.prefill_attn_impl = dispatch.resolve_attention(
                prefill_attn_impl or cfg.attn_impl, prefill_sq, t_kv,
                softmax_impl=self.prefill_softmax_impl,
                ring_axis=cfg.ring_axis if ring_ok else "")
            self.decode_attn_impl = dispatch.resolve_attention(
                decode_attn_impl or cfg.attn_impl, 1, t_kv,
                softmax_impl=self.decode_softmax_impl)
        prefill_cfg = cfg.replace(attn_impl=self.prefill_attn_impl,
                                  softmax_impl=self.prefill_softmax_impl)
        decode_cfg = cfg.replace(attn_impl=self.decode_attn_impl,
                                 softmax_impl=self.decode_softmax_impl)
        if self.cache_mode == "paged":
            self._prefill = jax.jit(make_chunk_prefill_step(prefill_cfg))
            self._decode = jax.jit(make_paged_decode_step(decode_cfg))
        else:
            self._prefill = jax.jit(make_prefill_step(prefill_cfg))
            self._decode = jax.jit(make_decode_step(decode_cfg))
        self._slots = [_Slot() for _ in range(n_slots)]
        self._admit_seq = 0
        self._queue: list[_QEntry] = []
        self._key = jax.random.PRNGKey(seed)
        self.finished: dict[int, list[int]] = {}
        self.reasons: dict[int, str] = {}
        self._last_tok = jnp.zeros((n_slots, 1), jnp.int32)
        self.stats = {"prefills": 0, "decode_steps": 0, "admitted": 0,
                      "prefill_chunks": 0, "cache_copies": 0,
                      "shared_blocks": 0, "blocks_hwm": 0,
                      "admit_time_s": 0.0, "engine_steps": 0,
                      "preemptions": 0, "swap_outs": 0, "swap_ins": 0,
                      "resumes": 0, "hol_skips": 0, "admit_blocked": 0,
                      "numeric": 0, "corrupt": 0, "deadlines": 0,
                      "starved": []}

    def _mesh_ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None else (
            contextlib.nullcontext())

    # ---- host-side bookkeeping ----

    def submit(self, req: Request) -> None:
        # validate at submission so an over-long prompt fails fast instead
        # of being popped mid-run (both prefill flavors: the bucketed path
        # AND the exact-length mamba/rwkv path, which used to skip every
        # length check and silently overrun the cache)
        if self.cache_mode == "paged":
            n = len(req.prompt)
            if n > self.max_seq:
                raise ValueError(f"prompt length {n} exceeds max_seq "
                                 f"{self.max_seq}")
            if self._blocks_needed(req) > self.num_blocks - 1:
                raise ValueError(
                    f"request needs {self._blocks_needed(req)} blocks, "
                    f"exceeds pool of {self.num_blocks - 1}")
        else:
            self._bucket(len(req.prompt))
        ddl = (None if req.deadline_s is None
               else self._now() + req.deadline_s)
        self._queue.append(_QEntry(req=req, deadline_at=ddl))

    def _bucket(self, n: int) -> int:
        if n > self.max_seq:
            raise ValueError(f"prompt length {n} exceeds max_seq "
                             f"{self.max_seq}")
        if self._exact_prefill:
            return n
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket "
                         f"{self.buckets[-1]}")

    def _blocks_needed(self, req: Request) -> int:
        """Worst-case table entries: the request can reach at most
        prompt+max_new tokens, clipped by the max_seq retire guard."""
        cap = min(len(req.prompt) + max(req.max_new, 0), self.max_seq)
        return tiling.cdiv(max(cap, 1), self.block_size)

    def _finish_queued(self, e: _QEntry, reason: str) -> None:
        self.finished[e.req.rid] = e.prior_out + e.out
        self.reasons[e.req.rid] = reason

    def _drain_zero_tokens(self) -> None:
        """Finish queued max_new<=0 requests with EMPTY completions —
        they never consume a slot, a prefill, or emit the prefill-sampled
        token.  ONE pass at the queue head, hoisted out of the per-slot
        admission loop (the drain used to re-run — and re-read the queue
        head — once per slot, a burst of zero-token requests cost
        O(queue·slots) head scans instead of O(queue)).  Resume entries
        always have tokens left (a done slot retires instead of
        preempting) and are never drained."""
        while (self._queue and not self._queue[0].is_resume
               and self._queue[0].req.max_new <= 0):
            done = self._queue.pop(0)
            self._finish_queued(done, "max_new")
            self.stats["admitted"] += 1

    def _expire_queue_deadlines(self) -> None:
        """Retire queued entries whose wall-clock budget ran out before
        they reached a slot — reason 'deadline', partial output for
        preempted resumes (the tokens they DID produce are not lost)."""
        if not any(e.deadline_at is not None for e in self._queue):
            return
        now = self._now()
        kept = []
        for e in self._queue:
            if e.deadline_at is not None and now >= e.deadline_at:
                self._finish_queued(e, "deadline")
                self.stats["deadlines"] += 1
            else:
                kept.append(e)
        self._queue = kept

    def _expire_running_deadlines(self) -> None:
        now = None
        for i, s in enumerate(self._slots):
            if s.free or s.deadline_at is None:
                continue
            now = self._now() if now is None else now
            if now >= s.deadline_at:
                self.stats["deadlines"] += 1
                self._finish_slot(i, "deadline")

    def _admit(self) -> None:
        t0 = time.perf_counter()
        self._expire_queue_deadlines()
        self._drain_zero_tokens()
        for i, slot in enumerate(self._slots):
            if not self._queue:
                break
            if not slot.free:
                continue
            if self.cache_mode == "paged":
                if not self._admit_paged_window(i):
                    # nothing in the skip-ahead window fits the pool
                    self.stats["admit_blocked"] += 1
                    break
            else:
                self._admit_contiguous(i)
            self._drain_zero_tokens()
        self.stats["admit_time_s"] += time.perf_counter() - t0

    def _admit_paged_window(self, i: int) -> bool:
        """Admit the first queue entry within ``hol_window`` that the
        pool can satisfy — a small request may skip past a blocked giant
        (stats['hol_skips']).  FCFS prefix registration is preserved:
        admission seq is assigned at admission and the prefill tick is
        seq-ordered, so whoever admits first registers first."""
        window = min(len(self._queue), self.hol_window)
        for j in range(window):
            entry = self._queue[j]
            if (j > 0 and not entry.is_resume
                    and entry.req.max_new <= 0):
                continue            # drains at the head, never via a slot
            if self._admit_entry(i, entry):
                self._queue.pop(j)
                if j > 0:
                    self.stats["hol_skips"] += 1
                return True
        return False

    def _admit_entry(self, i: int, entry: _QEntry) -> bool:
        if entry.swap is not None:
            return self._admit_swapped(i, entry)
        return self._admit_paged(i, entry)

    def _admit_contiguous(self, i: int) -> None:
        entry = self._queue.pop(0)
        req = entry.req
        L = self._bucket(len(req.prompt))
        toks = jnp.asarray(req.prompt + [0] * (L - len(req.prompt)),
                           jnp.int32)[None, :]
        row = init_caches(self.cfg, 1, self.max_seq, self.dtype)
        cross = None
        if req.cross_src is not None:
            cross = (encoder_apply(self.params, self.cfg, req.cross_src)
                     if self.cfg.family == "encdec" else req.cross_src)
        last_idx = jnp.asarray([len(req.prompt) - 1], jnp.int32)
        with self._mesh_ctx():
            logits, row = self._prefill(self.params, row, toks,
                                        last_idx, cross)
        # splice the prefilled row caches into the batch at slot i —
        # stacked-period leaves are (n_periods, B, ...): batch axis 1
        self.caches = _splice_slot(self.caches, row, i)
        self.stats["cache_copies"] += 1
        self._slots[i] = _Slot(rid=req.rid, pos=len(req.prompt),
                               remaining=req.max_new, out=[],
                               temperature=req.temperature,
                               full_prompt=list(req.prompt),
                               priority=req.priority,
                               deadline_at=entry.deadline_at)
        self._key, k = jax.random.split(self._key)
        first = sample_token(k, logits[0], req.temperature)
        self._slots[i].out.append(int(first))
        self._slots[i].remaining -= 1
        self._last_tok = self._last_tok.at[i, 0].set(first)
        self.stats["prefills"] += 1
        self.stats["admitted"] += 1
        self._retire(i)

    def _admit_paged(self, i: int, entry: _QEntry) -> bool:
        """Zero-copy admission: reserve this request's block reach
        (shared prefix by reference, the rest from the pool) and write
        its table row.  NO model compute, NO cache copies — prefill
        happens chunk-at-a-time in subsequent engine steps.  Reactive
        admission (default) reserves only the PROMPT reach and lets the
        decode loop grow the table; 'worst_case' reserves
        prompt+max_new up front so nothing ever preempts.  Returns
        False (leaving the request queued) when the pool is short."""
        req = entry.req
        prompt = (entry.resume_prompt if entry.resume_prompt is not None
                  else req.prompt)
        plen = len(prompt)
        budget = max(req.max_new, 0) - len(entry.prior_out)
        if self.admission == "worst_case":
            cap = min(plen + max(budget, 0), self.max_seq)
            total = tiling.cdiv(max(cap, 1), self.block_size)
        else:
            total = tiling.cdiv(max(plen, 1), self.block_size)
        # shareable prefix: FULL prompt blocks only, and never the block
        # holding the last prompt token — at least one token must run
        # through prefill to produce the first-sample logits (this also
        # guarantees writes never target a shared block)
        if (self.faults is not None and self.faults.alloc_shortfall(
                "admit", self.stats["engine_steps"])):
            return False
        hashes = chain_hashes(prompt, self.block_size)
        got = self.pool.reserve(hashes[:(plen - 1) // self.block_size],
                                total)
        if got is None:             # pool byte-identical: nothing to undo
            return False
        shared, fresh = got
        blocks = shared + fresh
        self._tables[i, :] = 0
        self._tables[i, :len(blocks)] = blocks
        self._slots[i] = _Slot(rid=req.rid, pos=plen,
                               remaining=budget, out=[],
                               temperature=req.temperature,
                               prompt=list(prompt),
                               filled=len(shared) * self.block_size,
                               blocks=blocks, seq=self._admit_seq,
                               full_prompt=list(req.prompt),
                               prior_out=list(entry.prior_out),
                               priority=req.priority,
                               deadline_at=entry.deadline_at)
        self._admit_seq += 1
        if entry.is_resume:
            self.stats["resumes"] += 1
        else:
            self.stats["admitted"] += 1
        self.stats["shared_blocks"] += len(shared)
        self.stats["blocks_hwm"] = max(self.stats["blocks_hwm"],
                                       self.pool.in_use())
        return True

    def _admit_swapped(self, i: int, entry: _QEntry) -> bool:
        """Resume a swapped-out request: re-allocate its block count,
        restore the saved contents, and re-enter decode at the exact
        position it left — no recompute, at the price of holding the
        block bytes on the host while preempted."""
        n = entry.swap["n"]
        forced = (self.faults is not None and self.faults.alloc_shortfall(
            "admit", self.stats["engine_steps"]))
        fresh = None if forced else self.pool.alloc(n)
        if fresh is None:
            return False
        self._swap_in(fresh, entry.swap["saved"])
        req = entry.req
        self._tables[i, :] = 0
        self._tables[i, :n] = fresh
        remaining = req.max_new - len(entry.prior_out) - len(entry.out)
        self._slots[i] = _Slot(rid=req.rid, pos=entry.pos,
                               remaining=remaining, out=list(entry.out),
                               temperature=req.temperature,
                               blocks=fresh, seq=self._admit_seq,
                               full_prompt=list(req.prompt),
                               prior_out=list(entry.prior_out),
                               priority=req.priority,
                               deadline_at=entry.deadline_at)
        self._admit_seq += 1
        self._last_tok = self._last_tok.at[i, 0].set(entry.out[-1])
        self.stats["swap_ins"] += 1
        self.stats["resumes"] += 1
        self.stats["blocks_hwm"] = max(self.stats["blocks_hwm"],
                                       self.pool.in_use())
        return True

    # ---- preemption ----

    def _swap_out(self, blocks: list[int]):
        """Gather the slot's block rows from every cache pool to host
        numpy — the swap store.  Stacked-period leaves carry a leading
        n_periods dim, so their block axis is 1."""
        idx = jnp.asarray(blocks, jnp.int32)

        def take(path, leaf):
            names = [str(getattr(e, "key", getattr(e, "idx", "")))
                     for e in path]
            axis = 1 if "periods" in names else 0
            return np.asarray(jnp.take(leaf, idx, axis=axis))
        return jax.tree_util.tree_map_with_path(take, self.caches)

    def _swap_in(self, blocks: list[int], saved) -> None:
        idx = jnp.asarray(blocks, jnp.int32)

        def put(path, leaf, rows):
            names = [str(getattr(e, "key", getattr(e, "idx", "")))
                     for e in path]
            if "periods" in names:
                return leaf.at[:, idx].set(rows.astype(leaf.dtype))
            return leaf.at[idx].set(rows.astype(leaf.dtype))
        self.caches = jax.tree_util.tree_map_with_path(
            put, self.caches, saved)

    def _pick_victim(self, i: int) -> int | None:
        """Choose a slot to preempt so slot ``i`` can grow: lowest
        priority first, then youngest (or oldest) admission seq.  None
        when no candidate exists or every candidate outranks the grower
        (the grower should yield instead of evicting its better)."""
        s = self._slots[i]
        sign = -1 if self.preempt_policy == "youngest" else 1
        cands = [(c.priority, sign * c.seq, j)
                 for j, c in enumerate(self._slots)
                 if j != i and not c.free]
        if not cands:
            return None
        prio, _, j = min(cands)
        if prio > s.priority:
            return None
        return j

    def _preempt(self, i: int) -> None:
        """Evict slot ``i`` back to the queue HEAD.  Decoding slots under
        preempt_mode='swap' keep their KV on the host and resume in
        place; everything else (and every mid-prefill slot) drops its
        blocks and resumes by re-prefilling prompt + generated prefix —
        greedy decode makes the recompute token-for-token identical."""
        s = self._slots[i]
        gen = s.prior_out + s.out
        req = Request(rid=s.rid, prompt=list(s.full_prompt),
                      max_new=len(gen) + max(s.remaining, 0),
                      temperature=s.temperature, priority=s.priority)
        if self.preempt_mode == "swap" and s.decoding:
            entry = _QEntry(req=req, deadline_at=s.deadline_at,
                            prior_out=list(s.prior_out), out=list(s.out),
                            pos=s.pos,
                            swap={"saved": self._swap_out(s.blocks),
                                  "n": len(s.blocks)})
            self.stats["swap_outs"] += 1
        else:
            base = s.prompt if s.prompt is not None else (
                s.full_prompt + s.prior_out)
            entry = _QEntry(req=req, deadline_at=s.deadline_at,
                            prior_out=s.prior_out + s.out,
                            resume_prompt=list(base) + list(s.out))
        for b in s.blocks:
            self.pool.decref(b)
        self._tables[i, :] = 0
        self._slots[i] = _Slot()
        self._queue.insert(0, entry)
        self.stats["preemptions"] += 1

    def _grow_decode_tables(self) -> None:
        """Reactive growth, oldest admission first: every decoding slot's
        table must cover position ``pos`` BEFORE the decode tick writes
        there (out-of-table scatters clamp to the sentinel block and the
        token's K/V would be silently lost).  Worst-case admission makes
        this a no-op — the reach is already reserved."""
        order = sorted((s.seq, i) for i, s in enumerate(self._slots)
                       if s.decoding)
        for seq, i in order:
            s = self._slots[i]
            if not s.decoding or s.seq != seq:
                continue            # preempted by an earlier grower
            self._grow_or_preempt(i)

    def _grow_or_preempt(self, i: int) -> bool:
        s = self._slots[i]
        while True:
            forced = (self.faults is not None and
                      self.faults.alloc_shortfall(
                          "grow", self.stats["engine_steps"]))
            fresh = (None if forced
                     else self.pool.ensure_reach(s.blocks, s.pos + 1))
            if fresh is not None:
                if fresh:
                    self._tables[i, :len(s.blocks)] = s.blocks
                    self.stats["blocks_hwm"] = max(
                        self.stats["blocks_hwm"], self.pool.in_use())
                return True
            v = self._pick_victim(i)
            if v is None:
                self._preempt(i)    # nobody cheaper to evict: yield
                return False
            self._preempt(v)

    def _validate_tables(self) -> None:
        """Per-step integrity check: every occupied slot's device-bound
        table row must mirror its host block list exactly.  A mismatch
        (bit flip, buggy writer, injected corruption) retires the slot
        with reason 'corrupt' — blocks are refunded from the HOST list,
        which is the allocation truth."""
        for i, s in enumerate(self._slots):
            if s.free:
                continue
            row = self._tables[i]
            want = np.zeros_like(row)
            want[:len(s.blocks)] = s.blocks
            if not np.array_equal(row, want):
                self.stats["corrupt"] += 1
                self._finish_slot(i, "corrupt")

    def _prefill_tick(self) -> None:
        """Advance ONE mid-prefill slot by ONE chunk.  Bounded work per
        engine step — a 32k prompt costs 32k/C steps, each sharing the
        step with a full decode tick, so decode traffic never stalls
        behind a long prompt.  FCFS by admission order: always the
        OLDEST prefilling request, so a fresh admission into a lower
        slot index cannot starve a half-prefilled one (and the first
        completion registers its prefix blocks before later duplicates
        finish privately)."""
        filling = [(s.seq, i, s) for i, s in enumerate(self._slots)
                   if not s.free and s.prompt is not None]
        for _, i, s in sorted(filling)[:1]:
            c0 = s.filled
            real = s.prompt[c0:c0 + self.prefill_chunk]
            toks = jnp.asarray(
                real + [0] * (self.prefill_chunk - len(real)),
                jnp.int32)[None, :]
            last_idx = jnp.asarray([len(real) - 1], jnp.int32)
            tables = jnp.asarray(self._tables[i:i + 1])
            with self._mesh_ctx():
                logits, self.caches = self._prefill(
                    self.params, self.caches, toks, jnp.int32(c0), tables,
                    last_idx)
            s.filled = c0 + len(real)
            self.stats["prefill_chunks"] += 1
            if s.filled >= len(s.prompt):
                if self.faults is not None:
                    logits = self.faults.prefill_logits(
                        self.stats["engine_steps"], s.rid, logits)
                if not bool(np.asarray(jnp.isfinite(logits).all())):
                    self.stats["numeric"] += 1
                    self._finish_slot(i, "numeric")
                    return
                # prefill complete: the prompt's full blocks are now
                # written and immutable — index them for prefix sharing
                n_full = len(s.prompt) // self.block_size
                hashes = chain_hashes(s.prompt, self.block_size)
                self.pool.register(hashes[:n_full],
                                   [int(b) for b in
                                    self._tables[i, :n_full]])
                s.prompt = None
                self._key, k = jax.random.split(self._key)
                first = sample_token(k, logits[0], s.temperature)
                s.out.append(int(first))
                s.remaining -= 1
                self._last_tok = self._last_tok.at[i, 0].set(first)
                self.stats["prefills"] += 1
                self._retire(i)
            return                          # one chunk per step

    def _finish_slot(self, i: int, reason: str) -> None:
        """Unconditional retirement with a reason code: output so far is
        delivered (prior incarnations included), blocks refunded."""
        s = self._slots[i]
        self.finished[s.rid] = s.prior_out + s.out
        self.reasons[s.rid] = reason
        if self.cache_mode == "paged":
            for b in s.blocks:
                self.pool.decref(b)
            self._tables[i, :] = 0
        self._slots[i] = _Slot()

    def _retire(self, i: int) -> None:
        s = self._slots[i]
        if s.free:
            return
        eos = (self.eos_id is not None and s.out and
               s.out[-1] == self.eos_id)
        if eos:
            reason = "eos"
        elif s.remaining <= 0:
            reason = "max_new"
        elif s.pos >= self.max_seq - 1:
            reason = "max_seq"
        else:
            return
        self._finish_slot(i, reason)

    @property
    def active(self) -> int:
        return sum(not s.free for s in self._slots)

    def pending(self) -> int:
        return len(self._queue) + self.active

    # ---- one engine step = admit + prefill chunk + one lockstep decode ----

    def step(self) -> None:
        self.stats["engine_steps"] += 1
        if self.cache_mode == "paged":
            if self.faults is not None:
                self.faults.corrupt_tables(self.stats["engine_steps"],
                                           self._tables, self._slots)
            self._validate_tables()
        self._expire_running_deadlines()
        self._admit()
        if self.cache_mode == "paged":
            self._prefill_tick()
            self._grow_decode_tables()
        decoding = [s.decoding for s in self._slots]
        if not any(decoding):
            return
        pos = jnp.asarray([s.pos if s.decoding else 0
                           for s in self._slots], jnp.int32)
        with self._mesh_ctx():
            if self.cache_mode == "paged":
                # non-decoding rows get all-sentinel tables: their writes
                # land in block 0, never in a mid-prefill slot's blocks
                masked = np.where(np.asarray(decoding)[:, None],
                                  self._tables, 0)
                logits, self.caches = self._decode(
                    self.params, self.caches, self._last_tok, pos,
                    jnp.asarray(masked))
            else:
                logits, self.caches = self._decode(
                    self.params, self.caches, self._last_tok, pos)
        if self.faults is not None:
            logits = self.faults.decode_logits(
                self.stats["engine_steps"],
                [s.rid if s.decoding else -1 for s in self._slots], logits)
        # numeric sentry: one (B,) host pull per tick.  A non-finite row
        # quarantines ONLY that slot (reason 'numeric', blocks refunded);
        # the per-slot sampling keys below are split from the step key by
        # slot INDEX, so the neighbours' token streams are bitwise
        # unaffected by the quarantine.
        finite = np.asarray(jnp.isfinite(logits).all(axis=-1))
        self.stats["decode_steps"] += 1
        self._key, k = jax.random.split(self._key)
        keys = jax.random.split(k, self.n_slots)
        for i, s in enumerate(self._slots):
            if not s.decoding:
                continue
            if not bool(finite[i]):
                self.stats["numeric"] += 1
                self._finish_slot(i, "numeric")
                continue
            tok = int(sample_token(keys[i], logits[i], s.temperature))
            s.out.append(tok)
            s.pos += 1
            s.remaining -= 1
            self._last_tok = self._last_tok.at[i, 0].set(tok)
            self._retire(i)

    def run(self, requests: list[Request], max_steps: int = 10_000
            ) -> dict[int, list[int]]:
        for r in requests:
            self.submit(r)
        steps = 0
        while self.pending() and steps < max_steps:
            self.step()
            steps += 1
        if self.pending():
            # max_steps exhausted: flush everything still live with
            # reason 'starved' (partial output delivered, blocks
            # refunded) and SAY SO — the old contract silently returned
            # a short dict and leaked the pool
            starved = []
            for i, s in enumerate(self._slots):
                if not s.free:
                    starved.append(s.rid)
                    self._finish_slot(i, "starved")
            while self._queue:
                e = self._queue.pop(0)
                starved.append(e.req.rid)
                self._finish_queued(e, "starved")
            self.stats["starved"].extend(starved)
        return dict(self.finished)
