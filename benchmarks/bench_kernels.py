"""Kernel micro-benchmarks: dual-mode unit vs native ops at model shapes."""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import softmax_unit as unit
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention_int import flash_attention_pallas_int
from repro.models.attention import _naive_sdpa
from repro.models.flash import flash_attention

from .common import emit, time_fn


def main() -> None:
    rng = np.random.default_rng(0)
    # softmax at attention-row shapes
    for rows, cols in ((512, 128), (1024, 1024)):
        x = jnp.asarray(rng.normal(size=(rows, cols)) * 3, jnp.float32)
        t_unit = time_fn(jax.jit(unit.softmax_dualmode), x)
        t_nat = time_fn(jax.jit(lambda t: jax.nn.softmax(t, -1)), x)
        emit(f"kernels/softmax_unit_{rows}x{cols}_us", t_unit,
             f"native={t_nat:.1f}us ratio={t_unit/t_nat:.2f}")
    # GELU at FFN shapes
    z = jnp.asarray(rng.normal(size=(512, 2816)), jnp.float32)
    t_unit = time_fn(jax.jit(unit.gelu_dualmode), z)
    t_nat = time_fn(jax.jit(jax.nn.gelu), z)
    emit("kernels/gelu_unit_512x2816_us", t_unit,
         f"native={t_nat:.1f}us ratio={t_unit/t_nat:.2f}")
    # flash attention vs naive at a mid shape
    b, s, k, g, h = 1, 1024, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = jnp.ones((b, s), bool)
    f = jax.jit(lambda q, kk, v: flash_attention(
        q, kk, v, q_pos=q_pos, kv_valid=valid, block=256))
    emit("kernels/flash_attn_1k_us", time_fn(f, q, kk, v), "block=256")


def main_flash(json_path: str | None = None) -> None:
    """Flash-attention shoot-out: naive vs pure-JAX flash vs Pallas flash.

    Records a BENCH_flash.json baseline so later PRs (backward kernel,
    int-path flash, sharded attention) have a reference.  Off-TPU the
    Pallas kernel runs in interpret mode — the number is a correctness
    checkpoint, not a speed claim; on TPU the same entry measures the
    compiled kernel.
    """
    rng = np.random.default_rng(0)
    b, s, k, g, h = 1, 1024, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = jnp.ones((b, s), bool)

    impls = {
        "naive": jax.jit(lambda q_, k_, v_: _naive_sdpa(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid)),
        "flash_jax": jax.jit(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid, block=256)),
        "flash_pallas": lambda q_, k_, v_: flash_attention_pallas(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid),
    }
    results = {"shape": {"b": b, "s": s, "kv_heads": k, "groups": g,
                         "head_dim": h},
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu", "us_per_call": {}}
    for name, fn in impls.items():
        t = time_fn(fn, q, kk, v)
        results["us_per_call"][name] = t
        emit(f"kernels/flash_shootout_{name}_us", t,
             f"backend={jax.default_backend()}")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def main_flash_int(json_path: str | None = None) -> None:
    """Int-path shoot-out: the one-sweep snapped kernel and the
    three-sweep classic oracle vs their neighbours — naive dual-mode
    (whole-row unit, O(S*T) scores materialized) and float blocked flash
    (same streaming, float words).

    Records BENCH_flash_int.json.  Off-TPU the Pallas numbers are
    interpret mode — a correctness checkpoint, not a speed claim.  The
    ``sweeps_rows`` section carries one row per int kernel (sweeps: 1 =
    snapped one-sweep, sweeps: 3 = classic oracle) with its word-parity
    residual against the matching whole-row unit, measured through an
    identity-v probe (output rows ARE the normalized prob words, so the
    residual is exactly 0.0 when the words are bit-identical — no f32
    prob@v reduction-order noise in the way).
    """
    rng = np.random.default_rng(0)
    b, s, k, g, h = 1, 512, 2, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = jnp.ones((b, s), bool)

    from repro.kernels.flash_attention_int import flash_attention_pallas_int3

    impls = {
        "naive_dualmode": jax.jit(lambda q_, k_, v_: _naive_sdpa(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid,
            softmax_impl="dualmode")),
        "flash_jax_float": jax.jit(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid, block=128)),
        "flash_pallas_int": lambda q_, k_, v_: flash_attention_pallas_int(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid),
        "flash_pallas_int3": lambda q_, k_, v_: flash_attention_pallas_int3(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid),
    }
    results = {"shape": {"b": b, "s": s, "kv_heads": k, "groups": g,
                         "head_dim": h},
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu", "us_per_call": {}}
    outs = {}
    for name, fn in impls.items():
        outs[name] = jax.block_until_ready(fn(q, kk, v))  # warm + capture
        t = time_fn(fn, q, kk, v)
        results["us_per_call"][name] = t
        emit(f"kernels/flash_int_{name}_us", t,
             f"backend={jax.default_backend()}")
    parity = float(jnp.abs(outs["flash_pallas_int3"]
                           - outs["naive_dualmode"]).max())
    results["parity_max_abs_vs_naive_dualmode"] = parity
    emit("kernels/flash_int_parity_max_abs", parity * 1e6,
         "combine reduction-order residual, x1e-6 (prob words identical)")

    # word-parity rows: identity-v probe (output rows = normalized prob
    # words) at a small square shape, each kernel vs its whole-row oracle
    sp = 128
    qp = jnp.asarray(rng.normal(size=(1, sp, 1, 1, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(1, sp, 1, 32)), jnp.float32)
    vp = jnp.eye(sp, dtype=jnp.float32)[None, :, None, :]
    probe_pos = jnp.arange(sp)[None]
    probe_valid = jnp.ones((1, sp), bool)

    def probe(kern, oracle_softmax):
        got = kern(qp, kp, vp, q_pos=probe_pos, kv_valid=probe_valid)
        want = _naive_sdpa(qp, kp, vp, q_pos=probe_pos,
                           kv_valid=probe_valid,
                           softmax_impl=oracle_softmax)
        return float(jnp.abs(got - want).max())

    results["sweeps_rows"] = [
        {"impl": "flash_pallas_int", "sweeps": 1,
         "oracle": "whole-row softmax_snap (naive dualmode_snap)",
         "word_parity_residual": probe(flash_attention_pallas_int,
                                       "dualmode_snap")},
        {"impl": "flash_pallas_int3", "sweeps": 3,
         "oracle": "whole-row softmax_int (naive dualmode)",
         "word_parity_residual": probe(flash_attention_pallas_int3,
                                       "dualmode")},
    ]
    for row in results["sweeps_rows"]:
        emit(f"kernels/flash_int_sweeps{row['sweeps']}_word_parity",
             row["word_parity_residual"],
             f"{row['impl']} vs {row['oracle']}")
        assert row["word_parity_residual"] == 0.0, row
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def check_flash_int_schema(json_path: str) -> None:
    """BENCH_flash_int.json contract: both a sweeps-1 (snapped one-sweep)
    and a sweeps-3 (classic oracle) row, each with an exactly-zero
    word-parity residual vs its whole-row reference."""
    from repro.analysis import schema
    schema.validate_file(json_path, schema.FLASH_INT_SPEC,
                         schema.FLASH_INT_RULES, "BENCH_flash_int.json")
    print(f"# BENCH_flash_int schema OK: {json_path}")


def main_flash_bwd(json_path: str | None = None) -> None:
    """Backward shoot-out: one full (dq, dk, dv) grad step through naive /
    pure-JAX flash / the Pallas kernel, whose VJP now runs the dedicated
    dq and dk/dv backward kernels (kernels/flash_attention_bwd.py) from
    the saved (m, l) residuals — plus the fused-GLU backward kernel next
    to the unfused reference VJP.

    Records BENCH_flash_bwd.json.  Off-TPU the Pallas numbers are
    interpret mode — a correctness checkpoint, not a speed claim; the max
    |pallas - reference| grad residuals are recorded alongside.
    """
    rng = np.random.default_rng(0)
    b, s, k, g, h = 1, 512, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = jnp.ones((b, s), bool)

    from repro.kernels.fused_ffn import _glu_reference, fused_glu_pallas

    def grad_of(fn):
        return jax.jit(jax.grad(
            lambda q_, k_, v_: fn(q_, k_, v_).sum(), argnums=(0, 1, 2)))

    impls = {
        "naive_bwd": grad_of(lambda q_, k_, v_: _naive_sdpa(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid)),
        "flash_jax_bwd": grad_of(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid, block=256)),
        "flash_pallas_bwd": grad_of(lambda q_, k_, v_:
                                    flash_attention_pallas(
                                        q_, k_, v_, q_pos=q_pos,
                                        kv_valid=valid)),
    }
    results = {"shape": {"b": b, "s": s, "kv_heads": k, "groups": g,
                         "head_dim": h},
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu", "us_per_call": {}}
    grads = {}
    for name, fn in impls.items():
        grads[name] = jax.block_until_ready(fn(q, kk, v))  # warm + capture
        t = time_fn(fn, q, kk, v, iters=5)
        results["us_per_call"][name] = t
        emit(f"kernels/flash_bwd_{name}_us", t,
             f"backend={jax.default_backend()}")
    residual = max(
        float(jnp.abs(a - b_).max())
        for a, b_ in zip(grads["flash_pallas_bwd"], grads["flash_jax_bwd"]))
    results["grad_parity_max_abs_vs_flash_jax"] = residual
    emit("kernels/flash_bwd_parity_max_abs", residual * 1e6,
         "max |dq/dk/dv pallas - pure-JAX flash VJP|, x1e-6")

    # fused GLU backward: the VMEM d_gate/d_up kernel vs the unfused graph
    m_, k_, f_ = 256, 512, 1024
    x = jnp.asarray(rng.normal(size=(m_, k_)) * 0.5, jnp.float32)
    wg = jnp.asarray(rng.normal(size=(k_, f_)) / k_ ** 0.5, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(k_, f_)) / k_ ** 0.5, jnp.float32)
    interp = jax.default_backend() != "tpu"
    glu = {
        "glu_ref_bwd": jax.jit(jax.grad(
            lambda *a: _glu_reference(*a, "silu").sum(), argnums=(0, 1, 2))),
        "glu_fused_bwd": jax.jit(jax.grad(
            lambda *a: fused_glu_pallas(
                *a, mode="silu", interpret=interp).sum(),
            argnums=(0, 1, 2))),
    }
    gouts = {}
    for name, fn in glu.items():
        gouts[name] = jax.block_until_ready(fn(x, wg, wu))
        t = time_fn(fn, x, wg, wu, iters=5)
        results["us_per_call"][name] = t
        emit(f"kernels/{name}_us", t, f"backend={jax.default_backend()}")
    glu_res = max(float(jnp.abs(a - b_).max()) for a, b_ in
                  zip(gouts["glu_fused_bwd"], gouts["glu_ref_bwd"]))
    results["glu_grad_parity_max_abs_vs_reference"] = glu_res
    emit("kernels/glu_bwd_parity_max_abs", glu_res * 1e6,
         "max |d(x,wg,wu) fused - unfused reference VJP|, x1e-6")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def main_flash_ring(json_path: str | None = None, ring_devices: int = 8
                    ) -> None:
    """Ring shoot-out: sequence-parallel flash_ring on a ring of up to
    ring_devices devices vs the single-device Pallas kernel, with the
    per-hop-count parity residual recorded.

    Runs in this process on the devices it has — the chips on a TPU
    host, or the CPU backend's emulated devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with fewer
    than two devices it fails rather than starting another process,
    which could not reach a chip this one holds.  Records
    BENCH_flash_ring.json: tokens/s for both paths (interpret mode on the
    CPU backend — a correctness checkpoint, not a speed claim) and the
    max |ring - single-device| output residual per ring width (1/2/4/8
    hops), i.e. the merge's split-point invariance at kernel scale.
    """
    if len(jax.devices()) < 2:
        raise SystemExit(
            f"flash_ring bench needs >= 2 devices, found "
            f"{len(jax.devices())} — on the CPU backend set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ring_devices}")

    from repro.kernels.ring_attention import ring_flash_attention
    from repro.launch.mesh import auto_mesh

    rng = np.random.default_rng(0)
    b, s, k, g, h = 1, 1024, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, k, g, h)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, k, h)), jnp.float32)
    q_pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    valid = jnp.ones((b, s), bool)

    single = lambda q_, k_, v_: flash_attention_pallas(
        q_, k_, v_, q_pos=q_pos, kv_valid=valid)
    out_single = jax.block_until_ready(single(q, kk, v))
    t_single = time_fn(single, q, kk, v, iters=3)

    results = {"shape": {"b": b, "s": s, "kv_heads": k, "groups": g,
                         "head_dim": h},
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu",
               "n_devices": len(jax.devices()),
               "us_per_call": {"flash_pallas_1dev": t_single},
               "tokens_per_s": {"flash_pallas_1dev": b * s / t_single * 1e6},
               "parity_max_abs_vs_1dev_by_hops": {}}
    emit("kernels/flash_ring_single_us", t_single,
         f"backend={jax.default_backend()}")
    hops = 2
    while hops <= min(results["n_devices"], ring_devices):
        mesh = auto_mesh((hops,), ("model",))
        ring = lambda q_, k_, v_: ring_flash_attention(
            q_, k_, v_, q_pos=q_pos, kv_valid=valid, mesh=mesh)
        out_ring = jax.block_until_ready(ring(q, kk, v))
        parity = float(jnp.abs(out_ring - out_single).max())
        t_ring = time_fn(ring, q, kk, v, iters=3)
        results["us_per_call"][f"flash_ring_{hops}dev"] = t_ring
        results["tokens_per_s"][f"flash_ring_{hops}dev"] = \
            b * s / t_ring * 1e6
        results["parity_max_abs_vs_1dev_by_hops"][str(hops)] = parity
        emit(f"kernels/flash_ring_{hops}dev_us", t_ring,
             f"parity_vs_1dev={parity:.2e}")
        hops *= 2
    assert max(results["parity_max_abs_vs_1dev_by_hops"].values()) <= 1e-5
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def main_decode(json_path: str | None = None,
                cache_lens: tuple[int, ...] = (4096, 16384, 65536),
                splits: tuple[int, ...] = (1, 2, 4, 8),
                engine_max_seq: int = 2048, engine_requests: int = 6,
                engine_max_new: int = 8) -> None:
    """Decode shoot-out: naive s_q=1 attention vs the split-KV
    flash-decode kernel across cache lengths and split counts, plus
    engine-level continuous-batching throughput with mixed-length slots.

    Records BENCH_decode.json — the serving-throughput trajectory file:
    us/token per (cache length, impl, split count), the max
    |flash_decode - naive| output residual per cache length, and
    tokens/sec through a reduced ServeEngine whose decode program runs
    each impl.  Off-TPU the Pallas numbers are interpret mode — a
    correctness checkpoint, not a speed claim; on TPU the same entries
    measure the compiled kernel.
    """
    from repro.configs import registry
    from repro.kernels.flash_decode import flash_decode_pallas
    from repro.models.transformer import init_lm
    from repro.serve import Request, ServeEngine

    rng = np.random.default_rng(0)
    b, kh, g, h = 1, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, kh, g, h)), jnp.float32)
    results = {"shape": {"b": b, "kv_heads": kh, "groups": g, "head_dim": h},
               "backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu",
               "cache_lens": list(cache_lens), "splits": list(splits),
               "us_per_token": {"naive": {}, "flash_decode": {}},
               "parity_max_abs_vs_naive": {}, "engine": {}}
    for t in cache_lens:
        kk = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
        q_pos = jnp.full((b, 1), t - 1, jnp.int32)
        valid = jnp.ones((b, t), bool)
        naive = jax.jit(lambda q_, k_, v_, qp=q_pos, va=valid: _naive_sdpa(
            q_, k_, v_, q_pos=qp, kv_valid=va))
        out_naive = jax.block_until_ready(naive(q, kk, v))
        t_naive = time_fn(naive, q, kk, v, iters=5)
        results["us_per_token"]["naive"][str(t)] = t_naive
        emit(f"kernels/decode_naive_{t}_us", t_naive,
             f"backend={jax.default_backend()}")
        per_split, parity = {}, 0.0
        for ns in splits:
            fn = lambda q_, k_, v_, ns_=ns, qp=q_pos, va=valid: \
                flash_decode_pallas(q_, k_, v_, q_pos=qp, kv_valid=va,
                                    num_splits=ns_)
            out = jax.block_until_ready(fn(q, kk, v))
            parity = max(parity, float(jnp.abs(out - out_naive).max()))
            t_fd = time_fn(fn, q, kk, v, iters=5)
            per_split[str(ns)] = t_fd
            emit(f"kernels/decode_flash_{t}_splits{ns}_us", t_fd,
                 f"parity_vs_naive={parity:.2e}")
        results["us_per_token"]["flash_decode"][str(t)] = per_split
        results["parity_max_abs_vs_naive"][str(t)] = parity
    assert max(results["parity_max_abs_vs_naive"].values()) <= 1e-5

    # engine-level: continuous batching with MIXED-length slots, decode
    # program pinned to each impl — tokens/sec over the full run (the
    # ragged per-slot tile skip is what flash_decode adds here)
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    lens = [2 + 5 * (i % 4) for i in range(engine_requests)]  # 2..17 mixed
    tps = {}
    for impl in ("naive", "flash_decode"):
        eng = ServeEngine(cfg, params, n_slots=4, max_seq=engine_max_seq,
                          prefill_buckets=(32,), decode_attn_impl=impl)
        reqs = [Request(rid=i, prompt=list(range(1, n + 1)),
                        max_new=engine_max_new)
                for i, n in enumerate(lens)]
        t0 = time.perf_counter()
        outs = eng.run(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(o) for o in outs.values())
        tps[impl] = toks / dt
        emit(f"serve/decode_engine_{impl}_tok_s", dt / max(toks, 1) * 1e6,
             f"{toks} tokens, max_seq={engine_max_seq}")
    results["engine"] = {"arch": cfg.name, "max_seq": engine_max_seq,
                         "n_slots": 4, "prompt_lens": lens,
                         "max_new": engine_max_new, "tokens_per_s": tps}
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def check_decode_schema(json_path: str) -> None:
    """BENCH_decode.json contract: per-cache-length us/token for naive and
    per-split flash_decode, a parity residual per cache length, and engine
    tokens/sec for both decode impls.  Lengths/splits themselves may vary
    (the CI smoke runs a reduced sweep)."""
    from repro.analysis import schema
    schema.validate_file(json_path, schema.DECODE_SPEC,
                         schema.DECODE_RULES, "BENCH_decode.json")
    print(f"# BENCH_decode schema OK: {json_path}")



def _run_engine_traced(eng, reqs):
    """Drive the engine step-by-step, tracking concurrency high-water and
    decode progress on the steps where a prefill chunk also ran."""
    for r in reqs:
        eng.submit(r)
    conc_hwm = 0
    prefill_steps_with_decoders = 0
    decode_ticks_during_prefill = 0
    t0 = time.perf_counter()
    steps = 0
    while eng.pending() and steps < 10_000:
        chunks0 = eng.stats["prefill_chunks"]
        decodes0 = eng.stats["decode_steps"]
        had_decoders = any(sl.decoding for sl in eng._slots)
        eng.step()
        steps += 1
        conc_hwm = max(conc_hwm, eng.active)
        if eng.stats["prefill_chunks"] > chunks0 and had_decoders:
            prefill_steps_with_decoders += 1
            decode_ticks_during_prefill += (eng.stats["decode_steps"]
                                            - decodes0)
    dt = time.perf_counter() - t0
    toks = sum(len(o) for o in eng.finished.values())
    return {"tokens": toks, "wall_s": dt,
            "tokens_per_s": toks / dt,
            "concurrent_hwm": conc_hwm,
            "decode_ticks_per_prefill_step":
                (decode_ticks_during_prefill / prefill_steps_with_decoders
                 if prefill_steps_with_decoders else None)}


def main_serve(json_path: str | None = None, *, n_requests: int = 12,
               n_slots: int = 4, max_seq: int = 256,
               max_new: int = 8, prefill_chunk: int = 32) -> None:
    """Serving shoot-out: paged block-table KV cache vs the slotted
    contiguous layout AT EQUAL HBM (the paged pool holds exactly the
    contiguous cache's token capacity, but gets 2x the scheduler slots —
    worst-case-reach admission is what lets it use them).

    Records BENCH_serve.json: tokens/s per cache mode, the paged pool's
    blocks-in-use high-water, mean admission latency, cache-tree copies
    per admission (paged must be ZERO — that is the tentpole claim),
    concurrency high-water at equal HBM, and decode ticks per
    chunked-prefill step (1.0 = decode never stalled behind a prompt).
    Off-TPU everything here is interpret/CPU timing — a scheduling and
    correctness checkpoint, not a speed claim.
    """
    from repro.configs import registry
    from repro.models.transformer import init_lm
    from repro.serve import Request, ServeEngine

    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    def mk_reqs():
        reqs = []
        shared = list(range(100, 124))                # 24-token base
        for i in range(n_requests):
            if i % 3 == 2:       # every third request extends the shared
                prompt = shared + [int(x) for x in
                                   rng.integers(1, 200, size=i % 5 + 1)]
            else:
                plen = int(rng.integers(4, 28))
                prompt = [int(x) for x in rng.integers(1, 200, size=plen)]
            reqs.append(Request(rid=i, prompt=prompt, max_new=max_new))
        return reqs

    results = {"backend": jax.default_backend(),
               "interpret": jax.default_backend() != "tpu",
               "arch": cfg.name,
               "workload": {"n_requests": n_requests, "max_new": max_new,
                            "max_seq": max_seq,
                            "prefill_chunk": prefill_chunk},
               "equal_hbm_tokens": n_slots * max_seq, "modes": {}}
    for mode in ("contiguous", "paged"):
        kw = dict(cache_mode=mode, max_seq=max_seq, seed=0)
        if mode == "paged":
            # 2x slots, same token budget: the pool is sized to the
            # contiguous cache (n_slots rows of max_seq tokens)
            bs = __import__("repro.kernels.tiling",
                            fromlist=["x"]).paged_block_size(max_seq)
            kw.update(n_slots=2 * n_slots, prefill_chunk=prefill_chunk,
                      num_blocks=n_slots * (max_seq // bs) + 1)
        else:
            kw.update(n_slots=n_slots, prefill_buckets=(32, max_seq))
        eng = ServeEngine(cfg, params, **kw)
        run = _run_engine_traced(eng, mk_reqs())
        st = eng.stats
        run.update({
            "cache_copies": st["cache_copies"],
            "admit_latency_us_mean":
                st["admit_time_s"] / max(st["admitted"], 1) * 1e6,
            "prefill_chunks": st["prefill_chunks"],
            "shared_blocks": st["shared_blocks"],
            "blocks_hwm": (eng.pool.hwm if eng.pool is not None else None),
            "n_slots": kw["n_slots"]})
        results["modes"][mode] = run
        emit(f"serve/{mode}_tok_s", run["wall_s"] / max(run["tokens"], 1)
             * 1e6, f"{run['tokens']} tokens, conc_hwm="
             f"{run['concurrent_hwm']}, copies={run['cache_copies']}")

    # mixed per-phase impls (ISSUE 7 / ROADMAP carried item): float
    # prefill + dual-mode decode — prompt ingestion at float speed, every
    # GENERATED token's attention through the bit-accurate snapped int
    # split-KV path.  Needs a cache deep enough for the decode resolution
    # to pick flash_decode (not whole-row naive), hence its own max_seq.
    mixed_seq = 2048
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=mixed_seq, seed=0,
                      cache_mode="contiguous", prefill_buckets=(32,),
                      decode_softmax_impl="dualmode")
    run = _run_engine_traced(eng, mk_reqs())
    run.update({"max_seq": mixed_seq,
                "prefill_attn_impl": eng.prefill_attn_impl,
                "prefill_softmax_impl": eng.prefill_softmax_impl,
                "decode_attn_impl": eng.decode_attn_impl,
                "decode_softmax_impl": eng.decode_softmax_impl})
    assert eng.decode_attn_impl == "flash_decode", run
    results["mixed_phase"] = run
    emit("serve/mixed_float_prefill_dualmode_decode_tok_s",
         run["wall_s"] / max(run["tokens"], 1) * 1e6,
         f"{run['tokens']} tokens, decode={eng.decode_attn_impl}"
         f"/{eng.decode_softmax_impl}")

    # pressure rows (ISSUE 10): a decode-heavy workload on a pool sized
    # at 0.5x the worst-case block demand of a full slot complement,
    # worst-case reservation vs reactive allocation + preemption.
    # Reserving only prompt reach must buy strictly more concurrency at
    # equal memory, and preemption/recompute must be invisible in the
    # token counts — every request terminates, nothing leaks.  The
    # requests are deterministic and UNIFORM (2-block prompts, 6-block
    # worst-case reach, no shared prefixes): mixed sizes would let
    # worst-case reservation sneak small requests into the pool and tie
    # the concurrency high-water it is supposed to lose.
    from repro.kernels import tiling
    bs = tiling.paged_block_size(max_seq)
    press_slots = n_slots
    press_reqs = [Request(rid=i, prompt=[1000 * i + j + 1
                                         for j in range(2 * bs)],
                          max_new=4 * bs)
                  for i in range(2 * press_slots)]
    worst = tiling.cdiv(6 * bs, bs)                   # 6 blocks apiece
    press_blocks = (press_slots * worst) // 2 + 1
    results["pressure"] = {"num_blocks": press_blocks - 1,
                           "worst_case_demand": press_slots * worst,
                           "modes": {}}
    for adm in ("worst_case", "reactive"):
        eng = ServeEngine(cfg, params, cache_mode="paged", seed=0,
                          n_slots=press_slots, max_seq=max_seq,
                          prefill_chunk=prefill_chunk,
                          num_blocks=press_blocks, admission=adm)
        run = _run_engine_traced(eng, [Request(**vars(r))
                                       for r in press_reqs])
        st = eng.stats
        run.update({"preemptions": st["preemptions"],
                    "resumes": st["resumes"],
                    "admit_blocked": st["admit_blocked"],
                    "hol_skips": st["hol_skips"],
                    "unterminated": sum(1 for r in press_reqs
                                        if r.rid not in eng.finished),
                    "leaked_blocks": eng.pool.in_use()})
        results["pressure"]["modes"][adm] = run
        emit(f"serve/pressure_{adm}_tok_s",
             run["wall_s"] / max(run["tokens"], 1) * 1e6,
             f"{run['tokens']} tokens, conc_hwm={run['concurrent_hwm']}, "
             f"preempts={run['preemptions']}, "
             f"blocked={run['admit_blocked']}")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def main_block(json_path: str | None = None, *, m: int = 1024,
               d: int = 512, f: int = 2048, kind: str = "rms",
               eps: float = 1e-6) -> None:
    """Block norm-seam shoot-out: each fused Pallas seam
    (kernels/fused_norm.py) vs its dense two-kernel composition, with the
    analytic HBM-bytes-per-block saving recorded per seam.

    The saving is analytic f32 stream accounting of the intermediate the
    fusion never materializes in HBM:

      * norm1 -> QKV prologue: dense writes, then re-reads, the
        normalized activations h (m x d) -> 2*m*d*4 bytes saved;
      * residual-add + norm2 epilogue: dense re-reads the residual sum
        it just wrote before normalizing -> m*d*4 saved;
      * norm2 -> gate/up GLU prologue: same stream shape as the QKV
        seam -> 2*m*d*4 saved.

    Off-TPU the fused timings are interpret mode — a correctness
    checkpoint, not a speed claim; the parity columns are the real
    content there.  Records BENCH_block.json, validated by
    ``analysis.schema.BLOCK_SPEC``/``BLOCK_RULES``.
    """
    from repro.kernels import datapath as dp
    from repro.kernels.fused_norm import (fused_norm_glu, fused_norm_linear,
                                          fused_residual_norm)

    rng = np.random.default_rng(0)
    interp = jax.default_backend() != "tpu"
    itemsize = 4
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    g = jnp.asarray(1.0 + 0.1 * rng.normal(size=(d,)), jnp.float32)
    b = (jnp.asarray(0.1 * rng.normal(size=(d,)), jnp.float32)
         if kind == "layer" else None)
    w_qkv = jnp.asarray(rng.normal(size=(d, 3 * d)) / d ** 0.5, jnp.float32)
    wg = jnp.asarray(rng.normal(size=(d, f)) / d ** 0.5, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(d, f)) / d ** 0.5, jnp.float32)

    def norm_dense(t):
        return (dp.rmsnorm(t, g, eps) if kind == "rms"
                else dp.layernorm(t, g, b, eps))

    md = m * d * itemsize
    impls = {
        "attn_qkv_prologue": (
            jax.jit(lambda x_: norm_dense(x_) @ w_qkv),
            lambda x_: fused_norm_linear(x_, g, b, w_qkv, kind=kind,
                                         eps=eps, interpret=interp),
            (x,),
            # read x, write+read h, read w, write y  /  h never lands
            3 * md + (d * 3 * d + m * 3 * d) * itemsize, 2 * md),
        "attn_out_epilogue": (
            jax.jit(lambda x_, r_: (x_ + r_, norm_dense(x_ + r_))),
            lambda x_, r_: fused_residual_norm(x_, r_, g, b, kind=kind,
                                               eps=eps, interpret=interp),
            (x, r),
            # read x+r, write x_new, re-read x_new, write h
            5 * md, md),
        "ffn_glu_prologue": (
            jax.jit(lambda x_: dp.pair_act(norm_dense(x_) @ wg, "gelu")
                    * (norm_dense(x_) @ wu)),
            lambda x_: fused_norm_glu(x_, g, b, wg, wu, kind=kind,
                                      eps=eps, mode="gelu",
                                      interpret=interp),
            (x,),
            3 * md + (2 * d * f + m * f) * itemsize, 2 * md),
    }
    results = {"backend": jax.default_backend(), "interpret": interp,
               "shape": {"m": m, "d": d, "f": f}, "norm_kind": kind,
               "seams": {}}
    for name, (dense_fn, fused_fn, args, dense_bytes, saved) in impls.items():
        out_d = jax.tree_util.tree_leaves(
            jax.block_until_ready(dense_fn(*args)))
        out_f = jax.tree_util.tree_leaves(
            jax.block_until_ready(fused_fn(*args)))
        parity = max(float(jnp.abs(a - b_).max())
                     for a, b_ in zip(out_f, out_d))
        us_d = time_fn(dense_fn, *args, iters=5)
        us_f = time_fn(fused_fn, *args, iters=5)
        results["seams"][name] = {
            "dense_hbm_bytes": dense_bytes,
            "fused_hbm_bytes": dense_bytes - saved,
            "saved_bytes": saved,
            "us_dense": us_d, "us_fused": us_f,
            "parity_max_abs": parity}
        emit(f"kernels/block_{name}_us", us_f,
             f"dense={us_d:.1f}us saved={saved}B parity={parity:.2e}")
    dense_total = sum(s["dense_hbm_bytes"]
                      for s in results["seams"].values())
    saved_total = sum(s["saved_bytes"] for s in results["seams"].values())
    results["block_total"] = {
        "dense_hbm_bytes": dense_total,
        "fused_hbm_bytes": dense_total - saved_total,
        "saved_bytes": saved_total,
        "saved_frac": saved_total / dense_total}
    emit("kernels/block_hbm_saved_pct",
         results["block_total"]["saved_frac"] * 100,
         f"{saved_total} of {dense_total} bytes per block (m={m} d={d})")
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")


def check_block_schema(json_path: str) -> None:
    """BENCH_block.json contract: every fused seam records a positive
    HBM-bytes saving consistent with its dense/fused accounting, the
    epilogue holds the pinned 1e-5 dense-contract parity, and the matmul
    prologues stay within small-ULP reassociation (5e-5)."""
    from repro.analysis import schema
    schema.check_block_json(json_path)
    print(f"# BENCH_block schema OK: {json_path}")


def check_serve_schema(json_path: str) -> None:
    """BENCH_serve.json contract: zero cache copies on paged admission,
    strictly more concurrent slots than contiguous at equal HBM, and
    decode not stalling during chunked prefill (>= 1 decode tick per
    prefill-chunk step)."""
    from repro.analysis import schema
    schema.validate_file(json_path, schema.SERVE_SPEC,
                         schema.SERVE_RULES, "BENCH_serve.json")
    print(f"# BENCH_serve schema OK: {json_path}")


if __name__ == "__main__":
    if "--check-audit" in sys.argv:
        # validate an existing AUDIT.json through the same declarative
        # engine the bench schemas use (CI pairs this with the audit job)
        from repro.analysis import schema
        i = sys.argv.index("--check-audit")
        path = (sys.argv[i + 1] if len(sys.argv) > i + 1
                else "AUDIT.json")
        schema.check_audit_json(path)
        print(f"# AUDIT schema OK: {path}")
        sys.exit(0)
    if "--ring-only" in sys.argv:
        i = sys.argv.index("--ring-only")
        main_flash_ring(sys.argv[i + 1] if len(sys.argv) > i + 1
                        else "BENCH_flash_ring.json")
        sys.exit(0)
    if "--flash-int-only" in sys.argv:
        i = sys.argv.index("--flash-int-only")
        path = (sys.argv[i + 1] if len(sys.argv) > i + 1
                else "BENCH_flash_int.json")
        main_flash_int(path)
        check_flash_int_schema(path)
        sys.exit(0)
    if "--serve-only" in sys.argv:
        i = sys.argv.index("--serve-only")
        path = (sys.argv[i + 1] if len(sys.argv) > i + 1
                else "BENCH_serve.json")
        if "--quick" in sys.argv:   # CI smoke: fewer requests, same schema
            main_serve(path, n_requests=8, n_slots=2, max_seq=128,
                       max_new=4, prefill_chunk=16)
        else:
            main_serve(path)
        check_serve_schema(path)
        sys.exit(0)
    if "--block-only" in sys.argv:
        i = sys.argv.index("--block-only")
        path = (sys.argv[i + 1] if len(sys.argv) > i + 1
                else "BENCH_block.json")
        if "--quick" in sys.argv:   # CI smoke: small shapes, same schema
            main_block(path, m=128, d=128, f=256)
        else:
            main_block(path)
        check_block_schema(path)
        sys.exit(0)
    if "--decode-only" in sys.argv:
        i = sys.argv.index("--decode-only")
        path = (sys.argv[i + 1] if len(sys.argv) > i + 1
                else "BENCH_decode.json")
        if "--quick" in sys.argv:   # CI smoke: reduced sweep, same schema
            main_decode(path, cache_lens=(2048, 4096), splits=(1, 2),
                        engine_max_seq=1024, engine_requests=3,
                        engine_max_new=3)
        else:
            main_decode(path)
        check_decode_schema(path)
        sys.exit(0)
    main()
    main_flash("BENCH_flash.json")
    main_flash_int("BENCH_flash_int.json")
    main_flash_bwd("BENCH_flash_bwd.json")
    main_decode("BENCH_decode.json")
    main_serve("BENCH_serve.json")
    main_block("BENCH_block.json")
    main_flash_ring("BENCH_flash_ring.json")   # needs >= 2 devices
