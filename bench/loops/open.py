"""Open-loop serving: requests arrive on a fixed Poisson schedule whatever
the engine does, so a slow engine meets a growing queue.

One process, one thread.  Set-up makes the weights on the device from
the seed, builds ``ServeEngine`` as the configuration says, and runs one
request through every slot so that each program and eager sampling op
has compiled.  Arrivals then start; after ``warmup_s`` the measured
window opens.  Each ``eng.step()`` is a span (``engine.step``); waits for
the next arrival are ``bench.wait_arrival``, submissions ``bench.submit``.
A token's time is the end of the engine step that produced it, which is
what a streaming client sees; a request's latencies count from when it
was due.

Correctness: once the window has closed and the engine is freed, a sample
of the finished requests (the longest among them) runs through the plain
float32 reference, and each served token is judged by how far its logit
lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import generate, harness


def slot_progress(eng) -> dict:
    """rid -> (tokens served so far, prompt tokens written while the
    request is still in prefill, else None), for every occupied slot.

    The engine has no public per-token hook yet, so this is the one
    place that reads its slots."""
    out = {}
    for s in eng._slots:
        if s.rid >= 0:
            out[s.rid] = (len(s.prior_out) + len(s.out),
                          s.filled if s.prompt is not None else None)
    return out


class Tracker:
    """Per-request times and per-step kernel shapes, from the engine's
    slots and finished map after every step."""

    def __init__(self, eng, requests):
        from repro.kernels import dispatch
        self.eng = eng
        # the kernels the engine resolved, by the names the readers use
        self.prefill_kernel = eng.prefill_attn_impl
        self.decode_kernel = (
            f"{eng.decode_attn_impl}_paged"
            if dispatch.get_paged_attention(eng.decode_attn_impl)
            else eng.decode_attn_impl)
        self.req = {r.rid: r for r in requests}
        self.seen: dict[int, int] = {}
        self.filled: dict[int, int] = {}
        self.first: dict[int, float] = {}
        self.times: dict[int, list[float]] = {}
        self.reason: dict[int, str] = {}
        self.n_finished = 0
        self.calls = {self.prefill_kernel: [], self.decode_kernel: []}
        self.tokens = {"prefill": 0, "decode": 0}
        self.busy: list[tuple[float, int]] = []     # (step end, slots held)

    def after_step(self, t: float, record: bool) -> None:
        eng = self.eng
        prog = slot_progress(eng)
        self.busy.append((t, sum(1 for rid in prog if rid in self.req)))
        fin = list(eng.finished)[self.n_finished:]
        self.n_finished = len(eng.finished)
        for rid in fin:
            prog[rid] = (len(eng.finished[rid]), None)
            self.reason[rid] = eng.reasons[rid]
        decode_kv = []
        for rid, (n, filled) in prog.items():
            r = self.req.get(rid)
            if r is None:                   # the compile warm-up's requests
                continue
            last = self.filled.get(rid, 0)
            if filled is not None and filled > last:
                self._chunk(last, filled - last, record)
                self.filled[rid] = filled
            for k in range(self.seen.get(rid, 0) + 1, n + 1):
                self.times.setdefault(rid, []).append(t)
                if k == 1:
                    self.first[rid] = t
                    self._chunk(last, len(r.prompt) - last, record)
                    self.filled[rid] = len(r.prompt)
                else:
                    decode_kv.append(len(r.prompt) + k - 1)
            self.seen[rid] = n
        if record and decode_kv:
            self.calls[self.decode_kernel].append({"kv": decode_kv})
            self.tokens["decode"] += len(decode_kv)

    def _chunk(self, c0: int, n: int, record: bool) -> None:
        if record and n > 0:
            self.calls[self.prefill_kernel].append({"c0": c0, "n": n})
            self.tokens["prefill"] += n


def build(cell, seed: int):
    """(engine, model config): weights from the seed on the device in one
    jitted call, in the type they are served in."""
    import jax.numpy as jnp

    from repro.serve import ServeEngine
    conf = cell.config
    mcfg = harness.load_by_name("adapters", conf["family"]).model_config(
        conf)
    params = harness.program_weights(conf, mcfg, seed)
    e = conf["run"]["engine"]
    eng = ServeEngine(mcfg, params, n_slots=e["n_slots"],
                      max_seq=conf["max_position_embeddings"],
                      dtype=jnp.dtype(conf["run"]["dtype"]),
                      cache_mode="paged", num_blocks=e["num_blocks"],
                      prefill_chunk=e["prefill_chunk"], seed=0)
    return eng, mcfg


def warm_up(eng, vocab: int, seed: int) -> None:
    """One short request per slot, all at once, so every slot index runs
    the prefill, the decode tick and the eager sampling ops."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    for i in range(eng.n_slots):
        eng.submit(Request(rid=-1 - i, max_new=2,
                           prompt=rng.integers(0, vocab, 16).tolist()))
    while eng.pending():
        eng.step()


def serve(eng, requests, *, warmup_s: float, seconds: float, spans,
          profiler=None, clock=None) -> dict:
    """Drive the open loop; returns the window and the tracker."""
    from repro.serve import Request
    tr = Tracker(eng, requests)
    t_start = time.perf_counter()
    due = [t_start + r.due_s for r in requests]
    ws, we = t_start + warmup_s, None
    late, i, in_window = [], 0, False
    compiles0 = None
    win_ann = None
    while True:
        now = time.perf_counter()
        if not in_window and now >= ws:
            if profiler is not None:
                profiler.start()
                import jax
                win_ann = jax.profiler.TraceAnnotation("bench.window")
                win_ann.__enter__()
            ws, in_window = time.perf_counter(), True
            we = ws + seconds
            compiles0 = clock.count if clock else None
        if in_window and now >= we:
            break
        if i < len(requests) and due[i] <= now:
            with spans.span("bench.submit"):
                while i < len(requests) and due[i] <= now:
                    r = requests[i]
                    eng.submit(Request(rid=r.rid, prompt=r.prompt,
                                       max_new=r.max_new))
                    late.append(now - due[i])
                    i += 1
        if eng.pending():
            with spans.span("engine.step") as sp:
                eng.step()
            tr.after_step(sp.t1, in_window and sp.t0 >= ws)
        else:
            nxt = due[i] if i < len(requests) else float("inf")
            until = min(nxt, we if in_window else ws)
            with spans.span("bench.wait_arrival"):
                time.sleep(max(0.0, until - time.perf_counter()))
    if win_ann is not None:
        win_ann.__exit__(None, None, None)
    trace_rec = profiler.stop() if profiler is not None else None
    return {"tracker": tr, "window": (ws, we), "due": due,
            "late_s": late, "trace": trace_rec,
            "compiles": (clock.count - compiles0) if clock else None}


def window_numbers(out: dict) -> dict:
    """Inter-token gaps, and TTFT, attempted and failed over the requests
    due in the window (a request still waiting at the close counts its
    wait so far)."""
    tr, (ws, we) = out["tracker"], out["window"]
    ttft, gaps, attempted, failed = [], [], 0, 0
    for rid in tr.req:
        due = out["due"][rid]
        if ws <= due < we:
            attempted += 1
            f = tr.first.get(rid)
            ttft.append((f if f is not None and f <= we else we) - due)
            if tr.reason.get(rid, "max_new") != "max_new":
                failed += 1
        ts = [t for t in tr.times.get(rid, []) if ws <= t <= we]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return {"ttft": ttft, "gaps": gaps, "attempted": attempted,
            "failed": failed}


def in_system(out: dict, t: float) -> int:
    """Requests due by ``t`` and not finished by then."""
    tr = out["tracker"]
    n = 0
    for rid, due in enumerate(out["due"]):
        if due <= t:
            times = tr.times.get(rid)
            n += not (rid in tr.reason and times and times[-1] <= t)
    return n


def slots_held(out: dict, t: float) -> int:
    """Slots the cell's requests held after the last step ending by ``t``."""
    held = [n for s, n in out["tracker"].busy if s <= t]
    return held[-1] if held else 0


def occupancy(out: dict) -> str:
    ws, we = out["window"]
    return (f"in system {in_system(out, ws)} -> {in_system(out, we)}, "
            f"slots held {slots_held(out, ws)} -> {slots_held(out, we)}")


def sample_served(out: dict, check: dict, seed: int) -> list:
    """Finished requests for the reference: the one with the most served
    tokens, then others drawn from the seed until the sample holds
    ``check['tokens']`` served tokens or ``check['requests']`` requests."""
    tr = out["tracker"]
    done = [rid for rid, why in tr.reason.items() if why == "max_new"]
    if not done:
        return []
    finished = tr.eng.finished
    longest = max(done, key=lambda r: len(finished[r]))
    rng = np.random.default_rng([seed, 7])
    rest = [r for r in rng.permutation(done).tolist() if r != longest]
    picked, n = [], 0
    for rid in [longest] + rest:
        if len(picked) >= check["requests"] or n >= check["tokens"]:
            break
        picked.append((tr.req[rid].prompt, list(finished[rid])))
        n += len(finished[rid])
    return picked


def reference_gaps(cell, seed: int, served, control: bool = False):
    """Per served token, how far its logit lies below the reference's
    best at that position (the widest over all tokens is what is judged).
    With ``control``, the same for the token that the control puts
    first, read on the same prompts and served tokens."""
    import jax
    import jax.numpy as jnp
    conf = cell.config
    ref = harness.load_by_name("references", conf["family"])
    w = jax.jit(lambda k: ref.init_weights(
        conf, k, jnp.dtype(conf["run"]["dtype"])))(harness.jax_key(seed))
    seq = conf["max_position_embeddings"]
    rows_n = cell.traffic["output"]["max"]

    @jax.jit
    def gaps(w, toks, rows, served):
        lg = ref.logits_at(w, conf, toks, rows)
        best = lg.max(-1)
        g = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        if control:
            pick = jnp.argmax(ref.logits_at(w, conf, toks, rows,
                                            ref.CONTROL), -1)
            g = best - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return g

    worst, n = [], 0
    for prompt, out in served:
        toks = np.zeros(seq, np.int32)
        full = prompt + out[:-1]
        toks[:len(full)] = full
        rows = np.clip(np.arange(rows_n) + len(prompt) - 1, 0, seq - 1)
        tgt = np.zeros(rows_n, np.int32)
        tgt[:len(out)] = out
        g = np.asarray(gaps(w, jnp.asarray(toks), jnp.asarray(rows),
                            jnp.asarray(tgt)))[:len(out)]
        worst.append(float(g.max()))
        n += len(out)
    return worst, n


def run(cell, devs, t0: float, clock) -> dict:
    conf, traffic = cell.config, cell.traffic
    spans = harness.Spans()
    eng, mcfg = build(cell, cell.seed)
    warm_up(eng, mcfg.vocab, cell.seed)
    requests = generate.open_loop(traffic, cell.seconds, cell.seed,
                                  mcfg.vocab)
    profiler = harness.Profiler(spans) if cell.trace else None
    out = serve(eng, requests, warmup_s=traffic["warmup_s"],
                seconds=cell.seconds, spans=spans, profiler=profiler,
                clock=clock)
    ws, we = out["window"]
    nums = window_numbers(out)
    tr = out["tracker"]
    harness.log(f"kernels: prefill {tr.prefill_kernel}, decode "
                f"{tr.decode_kernel}")
    harness.log(f"window {we - ws:.3f}s: {nums['attempted']} requests due, "
                f"TTFT p50 {harness.percentile(nums['ttft'], 50)}s p90 "
                f"{harness.percentile(nums['ttft'], 90)}s, "
                f"{occupancy(out)} (window start -> end), "
                f"{len(nums['gaps'])} token gaps, compiles in window "
                f"{out['compiles']}, generator late p99 "
                f"{harness.percentile(out['late_s'], 99)}s, engine stats "
                f"{ {k: v for k, v in eng.stats.items() if k != 'starved'} }")
    device = harness.device_info(devs, out["trace"])
    ref = harness.load_by_name("references", conf["family"])
    counts = ref.n_active(conf)
    run_rec = harness.Run(
        spans=spans, window=(ws, we), counters={**eng.stats, **{
            f"{k}_tokens": v for k, v in tr.tokens.items()}},
        calls=tr.calls, dims=_dims(conf), n_active=counts,
        device_kind=devs[0].device_kind, trace=out["trace"])
    served = sample_served(out, conf["run"]["check"], cell.seed)
    del eng, tr, out
    gc.collect()
    worst, n_tok = reference_gaps(cell, cell.seed, served)
    gap = max(worst) if worst else float("inf")
    limit = cell.limits["logit_gap"]
    harness.log(f"reference: {len(served)} requests, {n_tok} served "
                f"tokens, widest gap per request {worst}")
    itl = harness.percentile(nums["gaps"], 95)
    e2e = {"setup_s": ws - t0,
           "itl_p95_ms": None if itl is None else 1e3 * itl}
    return harness.result(
        cell, correct=bool(n_tok > 0 and gap <= limit),
        attempted=nums["attempted"], failed=nums["failed"],
        end_to_end=e2e, run=run_rec, device=device,
        compared={"logit_gap": {"value": gap, "limit": limit},
                  "served_tokens_checked": {"value": n_tok,
                                            "limit": "> 0"}})


def _dims(conf: dict) -> dict:
    import jax.numpy as jnp
    h = conf["num_attention_heads"]
    return {"heads": h, "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["hidden_size"] // h,
            "layers": conf["num_hidden_layers"],
            "itemsize": jnp.dtype(conf["run"]["dtype"]).itemsize}


def readings(cell, seed: int, faults=()) -> dict:
    """The program's widest gap, the control's on the same served
    tokens, and each planted fault's, for bench/calibrate.py: a run at
    the cell's load and length, without timing anything."""
    import contextlib

    from bench import faults as planted
    out = {}
    for name in ("program",) + tuple(faults):
        ctx = (planted.FAULTS[name]() if name != "program"
               else contextlib.nullcontext())
        with ctx:
            eng, mcfg = build(cell, seed)
            warm_up(eng, mcfg.vocab, seed)
            reqs = generate.open_loop(cell.traffic, cell.seconds, seed,
                                      mcfg.vocab)
            got = serve(eng, reqs, warmup_s=cell.traffic["warmup_s"],
                        seconds=cell.seconds, spans=harness.Spans())
            served = sample_served(got, cell.config["run"]["check"], seed)
            del eng, got
            gc.collect()
        worst, n = reference_gaps(cell, seed, served)
        out[name] = {"logit_gap": max(worst), "tokens": n}
        if name == "program":
            worst, _ = reference_gaps(cell, seed, served, control=True)
            out["control"] = {"logit_gap": max(worst), "tokens": n}
    return out
