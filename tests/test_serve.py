"""Serving engine: continuous batching == full-reforward oracle; EOS,
temperature, slot reuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models.transformer import init_caches, init_lm, lm_apply
from repro.serve import Request, ServeEngine


def _oracle(cfg, params, prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        caches = init_caches(cfg, 1, len(toks))
        logits, _, _ = lm_apply(params, cfg,
                                jnp.asarray(toks, jnp.int32)[None],
                                pos=0, caches=caches)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "rwkv6-1.6b",
                                  "jamba-v0.1-52b", "minicpm3-4b",
                                  "granite-moe-3b-a800m"])
def test_continuous_batching_matches_oracle(arch):
    cfg = registry.reduced_config(arch)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=48,
                      prefill_buckets=(8, 16))
    reqs = [Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new=5),
            Request(rid=1, prompt=[7, 8, 9], max_new=7),
            Request(rid=2, prompt=[4] * 10, max_new=4),
            Request(rid=3, prompt=[2, 3], max_new=3)]
    outs = eng.run(reqs)
    for r in reqs:
        assert outs[r.rid] == _oracle(cfg, params, r.prompt, r.max_new), r.rid
    assert eng.stats["prefills"] == 4
    assert eng.active == 0


def test_eos_stops_generation():
    cfg = registry.reduced_config("yi-6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ref = ServeEngine(cfg, params, n_slots=1, max_seq=32)
    out = ref.run([Request(rid=0, prompt=[1, 2, 3], max_new=10)])[0]
    eos = out[2] if len(out) > 2 else out[0]
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, eos_id=eos)
    out2 = eng.run([Request(rid=0, prompt=[1, 2, 3], max_new=10)])[0]
    assert len(out2) <= len(out)
    assert out2[-1] == eos or len(out2) == 10


def test_temperature_sampling_varies():
    cfg = registry.reduced_config("yi-6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    outs = set()
    for seed in range(3):
        eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, seed=seed)
        o = eng.run([Request(rid=0, prompt=[1, 2], max_new=8,
                             temperature=2.0)])[0]
        outs.add(tuple(o))
    assert len(outs) > 1                      # stochastic
    for o in outs:
        assert all(0 <= t < cfg.vocab for t in o)


def test_max_new_zero_emits_no_tokens():
    """A max_new=0 request finishes with an EMPTY completion — it used to
    emit the prefill-sampled token unconditionally (and burn a prefill)."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32,
                      prefill_buckets=(8,))
    outs = eng.run([Request(rid=0, prompt=[1, 2, 3], max_new=0),
                    Request(rid=1, prompt=[4, 5], max_new=3)])
    assert outs[0] == []
    assert outs[1] == _oracle(cfg, params, [4, 5], 3)
    assert eng.stats["prefills"] == 1          # zero request never prefilled
    assert eng.stats["admitted"] == 2
    assert eng.active == 0


def test_overlong_prompt_raises_bucketed():
    # bucket semantics are a CONTIGUOUS-path concept (paged prefill is
    # chunked and has no buckets) — pin the mode under test
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                      prefill_buckets=(8,), cache_mode="contiguous")
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(rid=0, prompt=list(range(9)), max_new=1))
    assert eng.pending() == 0                  # nothing left half-queued


def test_overlong_prompt_raises_exact_prefill():
    """The exact-length (mamba/rwkv) prefill path used to skip the length
    check entirely and silently overrun the cache."""
    cfg = registry.reduced_config("rwkv6-1.6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(Request(rid=0, prompt=list(range(17)), max_new=1))
    assert eng.pending() == 0


def test_per_phase_attn_impl_selection():
    """Prefill and decode pin their own registry-resolved attention impls;
    an explicit per-phase choice is honored and still matches the oracle."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                      prefill_buckets=(8,))
    assert eng.decode_attn_impl == "naive"     # s_q=1 rows stay whole-row
    # a config that PINS an impl keeps it for both phases (the engine's
    # per-phase defaults defer to cfg.attn_impl rather than clobber it)
    pinned = ServeEngine(cfg.replace(attn_impl="naive"), params, n_slots=1,
                         max_seq=32, prefill_buckets=(8,))
    assert pinned.prefill_attn_impl == "naive"
    assert pinned.decode_attn_impl == "naive"
    eng2 = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                       prefill_buckets=(8,),
                       prefill_attn_impl="flash_pallas",
                       decode_attn_impl="naive")
    assert eng2.prefill_attn_impl == "flash_pallas"
    out = eng2.run([Request(rid=0, prompt=[1, 2, 3], max_new=4)])[0]
    assert out == _oracle(cfg, params, [1, 2, 3], 4)


def test_dualmode_engine_refuses_float_blocked_prefill():
    """softmax_impl='dualmode' + an explicit float blocked prefill impl
    must fail at engine construction, not silently drop the unit."""
    cfg = registry.reduced_config("qwen1.5-0.5b").replace(
        softmax_impl="dualmode")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="dualmode"):
        ServeEngine(cfg, params, n_slots=1, max_seq=32,
                    prefill_buckets=(8,), prefill_attn_impl="flash")


def test_slot_reuse_more_requests_than_slots():
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32,
                      prefill_buckets=(8,))
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new=3)
            for i in range(7)]
    outs = eng.run(reqs)
    assert sorted(outs) == list(range(7))
    assert all(len(v) == 3 for v in outs.values())
    assert eng.stats["admitted"] == 7


# ---------------- paged KV cache ----------------

def test_paged_matches_contiguous_mixed_workload():
    """Token-level equivalence of the two cache layouts over a mixed
    greedy workload: ragged prompt lengths, EOS retires mid-stream, a
    repeated prompt that exercises prefix sharing, more requests than
    slots.  Same seed, same params — completions must be IDENTICAL."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)

    def mk_reqs():
        return [Request(rid=0, prompt=list(range(5, 25)), max_new=6),
                Request(rid=1, prompt=list(range(7, 40)), max_new=8),
                Request(rid=2, prompt=[3, 1, 4, 1, 5, 9, 2, 6], max_new=5),
                Request(rid=3, prompt=list(range(5, 25)), max_new=4),
                Request(rid=4, prompt=list(range(40, 44)), max_new=0),
                Request(rid=5, prompt=list(range(10, 48)), max_new=7)]

    paged = ServeEngine(cfg, params, n_slots=3, max_seq=64, seed=0,
                        cache_mode="paged", prefill_chunk=16)
    assert paged.cache_mode == "paged"
    contig = ServeEngine(cfg, params, n_slots=3, max_seq=64, seed=0,
                         cache_mode="contiguous", prefill_buckets=(16, 64))
    out_p = paged.run(mk_reqs())
    out_c = contig.run(mk_reqs())
    assert out_p == out_c
    # paged admission never copies a cache tree; contiguous splices one
    # row per prefill
    assert paged.stats["cache_copies"] == 0
    assert contig.stats["cache_copies"] == contig.stats["prefills"]
    # every block went back: retirement = pure decref, no leaks
    assert paged.pool.in_use() == 0
    assert paged.active == 0 and contig.active == 0


class _DecodeLogitsTap:
    """A fault injector that injects nothing and keeps each decode tick's
    logits."""

    def __init__(self):
        self.logits = []

    def alloc_shortfall(self, where, step):
        return False

    def decode_logits(self, step, rids, logits):
        self.logits.append(np.asarray(logits, np.float32))
        return logits

    def prefill_logits(self, step, rid, logits):
        return logits

    def corrupt_tables(self, step, tables, slots):
        return None


@pytest.mark.parametrize("mode", ["paged", "contiguous"])
def test_mixed_phase_decode_matches_naive_snap(mode):
    """Float prefill with dual-mode decode through the split-KV kernel: a
    generated token's attention runs the snapped int split path, so the
    greedy tokens equal the same engine decoding on the naive whole-row
    snapped unit, and so do the decode logits (float decode sits about
    3e-4 away from them, the classic unit about 1e-3)."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)

    def mk_reqs():
        return [Request(rid=0, prompt=list(range(5, 25)), max_new=6),
                Request(rid=1, prompt=[3, 1, 4, 1, 5, 9, 2, 6], max_new=8),
                Request(rid=2, prompt=list(range(40, 77)), max_new=5)]

    kw = dict(n_slots=2, max_seq=128, seed=0, cache_mode=mode,
              prefill_softmax_impl="float", decode_softmax_impl="dualmode")
    if mode == "paged":
        kw["prefill_chunk"] = 16
    else:
        kw["prefill_buckets"] = (16, 64)
    tap, ref_tap = _DecodeLogitsTap(), _DecodeLogitsTap()
    eng = ServeEngine(cfg, params, decode_attn_impl="flash_decode",
                      faults=tap, **kw)
    assert (eng.prefill_softmax_impl, eng.decode_softmax_impl,
            eng.decode_attn_impl) == ("float", "dualmode", "flash_decode")
    ref = ServeEngine(cfg, params, decode_attn_impl="naive", faults=ref_tap,
                      **dict(kw, decode_softmax_impl="dualmode_snap"))
    assert ref.decode_attn_impl == "naive"
    out = eng.run(mk_reqs())
    assert out == ref.run(mk_reqs())
    assert all(len(out[r]) > 0 for r in out)
    assert len(tap.logits) == len(ref_tap.logits) > 0
    np.testing.assert_allclose(np.stack(tap.logits),
                               np.stack(ref_tap.logits), atol=5e-5)


def test_paged_outbatches_contiguous_at_equal_hbm():
    """The paged pool holds exactly the contiguous layout's n_slots x
    max_seq token rows, yet runs twice the slots: admission reserves only
    what a request can reach, so more requests decode at once, with the
    same tokens."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    n_slots, max_seq = 2, 64

    def mk_reqs():
        return [Request(rid=i, prompt=[7 * i + j + 1 for j in range(4 + i)],
                        max_new=6) for i in range(6)]

    def run_peak(eng):
        for r in mk_reqs():
            eng.submit(r)
        peak = 0
        while eng.pending():
            eng.step()
            peak = max(peak, eng.active)
        return dict(eng.finished), peak

    contig = ServeEngine(cfg, params, n_slots=n_slots, max_seq=max_seq,
                         seed=0, cache_mode="contiguous",
                         prefill_buckets=(16, max_seq))
    paged = ServeEngine(cfg, params, n_slots=2 * n_slots, max_seq=max_seq,
                        seed=0, cache_mode="paged", prefill_chunk=16,
                        num_blocks=n_slots * (max_seq // 8) + 1)
    assert paged.block_size == 8          # the pool = contiguous's rows
    out_c, peak_c = run_peak(contig)
    out_p, peak_p = run_peak(paged)
    assert out_p == out_c
    assert peak_p > peak_c
    assert paged.pool.in_use() == 0


def test_paged_eos_retire_matches_contiguous():
    cfg = registry.reduced_config("yi-6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ref = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                      cache_mode="contiguous")
    out = ref.run([Request(rid=0, prompt=[1, 2, 3], max_new=10)])[0]
    eos = out[2]
    for mode in ("paged", "contiguous"):
        eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, eos_id=eos,
                          cache_mode=mode)
        got = eng.run([Request(rid=0, prompt=[1, 2, 3], max_new=10)])[0]
        assert got == out[:out.index(eos) + 1], mode


def test_paged_prefix_sharing_blocks_accounted():
    """A second request extending an already-prefilled prompt reuses its
    full blocks by reference: shared_blocks counts them, the shared
    prefill is a single chunk, and the tokens still match contiguous."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    base = list(range(5, 45))                        # 40 toks = 5 blocks(8)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=128, seed=0,
                      cache_mode="paged", prefill_chunk=16)
    assert eng.block_size == 8
    eng.run([Request(rid=0, prompt=base, max_new=4)])
    assert eng.stats["shared_blocks"] == 0
    chunks_before = eng.stats["prefill_chunks"]
    out = eng.run([Request(rid=1, prompt=base + [77, 78], max_new=4)])
    # usable prefix = hashes[:(42-1)//8] = 5 full blocks, all registered
    assert eng.stats["shared_blocks"] == 5
    assert eng.stats["prefill_chunks"] == chunks_before + 1
    contig = ServeEngine(cfg, params, n_slots=2, max_seq=128, seed=0,
                         cache_mode="contiguous")
    contig.run([Request(rid=0, prompt=base, max_new=4)])
    ref = contig.run([Request(rid=1, prompt=base + [77, 78], max_new=4)])
    assert out[1] == ref[1]


def test_paged_chunked_prefill_interleaves_decode():
    """A long prompt admitted while another slot is decoding must not
    stall it: decode ticks keep firing between prefill chunks."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=128, seed=0,
                      cache_mode="paged", prefill_chunk=8)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=30))
    eng.step()                                   # admit + first decode
    assert eng._slots[0].decoding
    eng.submit(Request(rid=1, prompt=list(range(5, 85)), max_new=8))
    decoded_before = len(eng._slots[0].out)
    steps = 0
    while not eng._slots[1].decoding:
        eng.step()                               # rid 1 prefills 80/8 chunks
        steps += 1
        assert steps < 50
    # rid 0 decoded one token per engine step THROUGHOUT rid 1's prefill
    assert len(eng._slots[0].out) - decoded_before >= 80 // 8
    out = eng.run([])                            # drain
    contig = ServeEngine(cfg, params, n_slots=2, max_seq=128, seed=0,
                         cache_mode="contiguous")
    ref = contig.run([Request(rid=0, prompt=[1, 2, 3], max_new=30),
                      Request(rid=1, prompt=list(range(5, 85)), max_new=8)])
    assert out == ref


def test_paged_overlong_and_overcapacity_raise():
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                      cache_mode="paged")
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(rid=0, prompt=list(range(33)), max_new=1))
    # within max_seq but over the pool's worst-case reach
    small = ServeEngine(cfg, params, n_slots=1, max_seq=32,
                        cache_mode="paged", num_blocks=2)
    with pytest.raises(ValueError, match="exceeds"):
        small.submit(Request(rid=0, prompt=list(range(20)), max_new=8))
    assert eng.pending() == 0 and small.pending() == 0


def test_paged_rejects_unsupported_arch():
    cfg = registry.reduced_config("rwkv6-1.6b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, n_slots=1, max_seq=16, cache_mode="paged")
    # auto quietly falls back for state-carrying mixers
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=16)
    assert eng.cache_mode == "contiguous"


class _CountingInt(int):
    """int that counts how often it is compared via <= (the admission
    loop's drain predicate reads `req.max_new <= 0`)."""
    reads = 0

    def __le__(self, other):
        _CountingInt.reads += 1
        return int(self) <= other


def test_zero_token_drain_cost_is_per_queue_not_per_slot():
    """The max_new<=0 drain runs ONCE per admission pass, not once per
    slot: with every slot busy, the queue head's max_new is read O(1)
    times per step — the old in-loop drain re-read it once per slot."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=4, max_seq=32, seed=0,
                      prefill_buckets=(8,))
    eng.run([Request(rid=i, prompt=[i + 1], max_new=2)
             for i in range(4)])                 # warm compile caches
    for i in range(4):                           # occupy every slot
        eng.submit(Request(rid=10 + i, prompt=[i + 1], max_new=50))
    for _ in range(4):              # paged prefill: one chunk per step
        eng.step()
    assert eng.active == 4 and all(s.decoding for s in eng._slots)
    _CountingInt.reads = 0
    eng.submit(Request(rid=99, prompt=[7], max_new=_CountingInt(3)))
    eng._admit()
    # one drain pass reads the head once; the slot loop (4 busy slots)
    # must not re-read it
    assert _CountingInt.reads <= 2, _CountingInt.reads


# ---------------- batched sampling ----------------

TEMPS = [0.0, 0.7, 2.0, 0.0, 1.0, 0.3, -1.0, 1.3]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_sampler_is_bitwise_per_row(dtype):
    """One batched call gives, row by row, exactly what a call on that
    row alone gives: the argmax at temperature <= 0, else the categorical
    draw of the row's key over logits / t in the logits' dtype."""
    from repro.serve.engine import sample_token
    vocab = 151936
    logits = (3 * jax.random.normal(jax.random.PRNGKey(1),
                                    (8, vocab))).astype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    got = np.asarray(sample_token(keys, logits, TEMPS))
    assert got.dtype == np.int32
    want = [int(jnp.argmax(logits[i])) if t <= 0 else
            int(jax.random.categorical(keys[i], logits[i] / t))
            for i, t in enumerate(TEMPS)]
    assert got.tolist() == want
    # an all-greedy batch, as every decode tick of greedy traffic is,
    # runs the same program and takes each row's argmax
    greedy = np.asarray(sample_token(keys, logits, [0.0] * 8))
    assert greedy.tolist() == [int(jnp.argmax(row)) for row in logits]


def _per_slot_sampler(seed: int, calls: list):
    """Per-slot sampling as a stand-in for ``sample_token``: its
    own copy of the engine's key stream (a prompt's first token takes
    the split key whole, a decode tick splits it by slot index), one
    eager draw per row at the row's Python-float temperature."""
    state = {"key": jax.random.PRNGKey(seed)}

    def sample(keys, logits, temperature):
        calls.append(logits.shape[0])
        state["key"], k = jax.random.split(state["key"])
        rows = ([k] if logits.shape[0] == 1
                else jax.random.split(k, logits.shape[0]))
        out = []
        for i, t in enumerate(np.asarray(temperature).tolist()):
            out.append(int(jnp.argmax(logits[i])) if t <= 0.0 else int(
                jax.random.categorical(rows[i], logits[i] / t, axis=-1)))
        return jnp.asarray(out, jnp.int32)
    return sample


@pytest.mark.parametrize("mode", ["paged", "contiguous"])
def test_engine_samples_each_tick_once_and_matches_per_slot(mode,
                                                            monkeypatch):
    """Four slots mixing greedy and temperature-2 requests: the engine
    calls ``sample_token`` once per decode tick plus once per completed
    prompt, whatever the slot count, and its token streams equal those
    of per-slot sampling over the same key stream."""
    import repro.serve.engine as engine
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)

    def run():
        eng = ServeEngine(cfg, params, n_slots=4, max_seq=64, seed=3,
                          cache_mode=mode, prefill_chunk=16,
                          prefill_buckets=(16, 64))
        reqs = [Request(rid=i, prompt=list(range(2 + i, 12 + 5 * i)),
                        max_new=5 + i % 3,
                        temperature=2.0 if i % 2 else 0.0)
                for i in range(6)]
        return eng, eng.run(reqs)

    eng, got = run()
    assert eng.stats["sample_calls"] == (eng.stats["decode_steps"]
                                         + eng.stats["prefills"])
    calls: list = []
    monkeypatch.setattr(engine, "sample_token", _per_slot_sampler(3, calls))
    ref_eng, want = run()
    assert got == want
    assert len(calls) == ref_eng.stats["sample_calls"] == (
        ref_eng.stats["decode_steps"] + ref_eng.stats["prefills"])
    assert set(calls) == {1, 4}            # a prompt, or a whole tick
    assert len(calls) < sum(len(v) for v in want.values())
