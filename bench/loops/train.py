"""Training: the program's ``Trainer`` steps through batches made from
the seed, as fast as it goes, for the measured window.

Set-up builds one ``Trainer``, gives it the benchmark's weights (made on
the device from the seed, so the reference starts from the same numbers
and takes nothing the program made), and drives it through its first
``check_steps`` steps with the same call and feed the window uses.  Those
steps are what the reference follows: each step's loss, the first
gradient as the optimizer got it (read back from Adam's first moment,
m1 = (1 - b1) g), and the change of every parameter after the last of
them.  The window then goes on from there; each step is a
``trainer.step`` span.
"""
from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time

import numpy as np

from bench import generate, harness


def build(cell, seed: int):
    """A Trainer with the benchmark's weights in its state."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import TrainConfig
    from repro.train import Trainer
    conf, traffic = cell.config, cell.traffic
    mcfg = harness.load_by_name("adapters", conf["family"]).model_config(
        conf)
    tcfg = TrainConfig(**conf["run"]["trainer"],
                       checkpoint_dir=tempfile.mkdtemp(prefix="bench_ckpt_"))
    trainer = Trainer(mcfg, tcfg, traffic["batch"], traffic["seq"],
                      data=generate.TrainData(traffic, seed, mcfg.vocab),
                      dtype=jnp.dtype(conf["run"]["dtype"]), resume=False,
                      log=lambda m: None)
    params = harness.program_weights(conf, mcfg, seed)
    old = trainer.state.params
    trainer.state = trainer.state._replace(params=jax.device_put(
        params, jax.tree.map(lambda a: a.sharding, old)))
    del old
    return trainer, tcfg


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in
            zip(flat, norms)}


def first_steps(cell, trainer, spans, seed: int) -> dict:
    """Drive the first steps; read what the reference will follow."""
    import jax
    conf = cell.config
    ad = harness.load_by_name("adapters", conf["family"])
    ref = harness.load_by_name("references", conf["family"])
    b1 = conf["run"]["trainer"]["b1"]
    losses, grad_norms = [], None
    for k in range(cell.traffic["check_steps"]):
        with spans.span("trainer.step"):
            losses.append(trainer.run(1)["loss"])
        if k == 0:
            grad_norms = _leaf_norms(jax.tree.map(
                lambda m: m / (1 - b1), ad.from_program(
                    trainer.state.opt.m)))
    dtype = trainer.dtype
    p0 = jax.jit(lambda k: ref.init_weights(conf, k, dtype))(
        harness.jax_key(seed))
    change = _leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        ad.from_program(trainer.state.params), p0))
    return {"losses": losses, "grad": grad_norms, "change": change}


def reference_readings(cell, seed: int, dt: str = "f32") -> dict:
    """The same readings from the plain reference (``dt`` its
    ``CONTROL``: the control), on the same weights and batches."""
    import jax
    import jax.numpy as jnp
    conf, traffic = cell.config, cell.traffic
    ref = harness.load_by_name("references", conf["family"])
    w0 = jax.jit(lambda k: ref.init_weights(conf, k, jnp.float32))(
        harness.jax_key(seed))
    data = generate.TrainData(traffic, seed, conf["vocab_size"])
    batches = [data.batch(i) for i in range(traffic["check_steps"])]
    losses, g, p = ref.train(w0, conf, batches, conf["run"]["trainer"], dt)
    change = _leaf_norms(jax.tree.map(jnp.subtract, p, w0))
    return {"losses": losses, "grad": _leaf_norms(g), "change": change}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` is judged by.

    loss_gap: worst relative gap of a step's loss.  grad_gap, change_gap:
    worst leaf gap between the program's and the reference's norm, over
    the larger of the reference leaf's norm and the median leaf's.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone under Adam and are left out of change_gap."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))

    def worst(kind, keep):
        med = float(np.median([ref[kind][k] for k in keep]))
        return max(abs(prog[kind][k] - ref[kind][k]) / max(ref[kind][k], med)
                   for k in keep)
    g_med = float(np.median(list(ref["grad"].values())))
    moving = [k for k, v in ref["grad"].items() if v >= 1e-3 * g_med]
    return {"loss_gap": loss, "grad_gap": worst("grad", list(ref["grad"])),
            "change_gap": worst("change", moving)}


def run(cell, devs, t0: float, clock) -> dict:
    traffic = cell.traffic
    spans = harness.Spans()
    trainer, tcfg = build(cell, cell.seed)
    prog = first_steps(cell, trainer, spans, cell.seed)
    tokens_per_step = traffic["batch"] * traffic["seq"]
    profiler = harness.Profiler(spans) if cell.trace else None
    steps, failed = 0, 0
    c0 = clock.count
    win_ann = None
    if profiler is not None:
        import jax
        profiler.start()
        win_ann = jax.profiler.TraceAnnotation("bench.window")
        win_ann.__enter__()
    ws = time.perf_counter()
    while time.perf_counter() < ws + cell.seconds:
        with spans.span("trainer.step"):
            loss = trainer.run(1)["loss"]
        steps += 1
        failed += not math.isfinite(loss)
    we = time.perf_counter()
    if win_ann is not None:
        win_ann.__exit__(None, None, None)
    trace_rec = profiler.stop() if profiler is not None else None
    harness.log(f"window {we - ws:.3f}s: {steps} steps, compiles in "
                f"window {clock.count - c0}, first losses {prog['losses']}")
    device = harness.device_info(devs, trace_rec)
    ref = harness.load_by_name("references", cell.config["family"])
    run_rec = harness.Run(
        spans=spans, window=(ws, we), counters={"steps": steps},
        calls={}, dims={}, n_active=ref.n_active(cell.config),
        device_kind=devs[0].device_kind, trace=trace_rec,
        extra={"tokens_per_s": steps * tokens_per_step / (we - ws)})
    del trainer
    gc.collect()
    shutil.rmtree(tcfg.checkpoint_dir, ignore_errors=True)
    nums = compare(prog, reference_readings(cell, cell.seed))
    compared = {k: {"value": v, "limit": cell.limits[k]}
                for k, v in nums.items()}
    ok = all(v["value"] <= v["limit"] for v in compared.values())
    e2e = {"setup_s": ws - t0,
           "train_tok_s": steps * tokens_per_step / (we - ws)}
    return harness.result(cell, correct=ok and failed == 0,
                          attempted=steps, failed=failed, end_to_end=e2e,
                          run=run_rec, device=device, compared=compared)


def readings(cell, seed: int, faults=()) -> dict:
    """The program's numbers, the control's (the reference one precision
    step down) and each planted fault's, against the reference, for
    bench/calibrate.py."""
    import contextlib

    from bench import faults as planted
    control = harness.load_by_name("references",
                                   cell.config["family"]).CONTROL
    ref = reference_readings(cell, seed)
    out = {"control": compare(reference_readings(cell, seed, control), ref)}
    for name in ("program",) + tuple(faults):
        ctx = (planted.FAULTS[name]() if name != "program"
               else contextlib.nullcontext())
        with ctx:
            trainer, tcfg = build(cell, seed)
            prog = first_steps(cell, trainer, harness.Spans(), seed)
            del trainer
            shutil.rmtree(tcfg.checkpoint_dir, ignore_errors=True)
            gc.collect()
        out[name] = compare(prog, ref)
    return out
