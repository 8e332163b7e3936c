"""The program's phase spans, read back the way the benchmark reads a
device trace: ``ServeEngine.step`` and ``Trainer.run`` tile the caller's
``engine.step`` / ``trainer.step`` span with consecutive, disjoint
phases, and the unit's ops carry their activation's name."""
import os
import sys

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.models.transformer import init_lm
from repro.serve import Request, ServeEngine
from repro.train import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from bench import trace  # noqa: E402

ENGINE = ("engine.admit", "engine.prefill", "engine.decode",
          "engine.sample")


def _traced(tmp_path, caller: str, body, steps: int) -> dict:
    """``steps`` calls of ``body``, each inside a ``caller`` span, inside
    the benchmark's window span, under the profiler; the reduced trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(steps):
                with jax.profiler.TraceAnnotation(caller):
                    body()
    finally:
        jax.profiler.stop_trace()
    return trace.load(str(tmp_path))


def _phases_per_step(rec: dict, caller: str, prefix: str) -> list:
    """Per ``caller`` span, the phase spans inside it in order; checks
    that they are disjoint, that none lies outside a caller span, and
    that they leave under 5% of the caller's time uncovered."""
    steps = sorted((s, e) for n, s, e in rec["spans"] if n == caller)
    kids = sorted(((s, e, n) for n, s, e in rec["spans"]
                   if n.startswith(prefix) and n != caller))
    per, held, uncovered = [], 0, 0
    for a, b in steps:
        inside = [k for k in kids if a <= k[0] and k[1] <= b]
        for (_, e1, _), (s2, _, _) in zip(inside, inside[1:]):
            assert e1 <= s2, inside
        held += len(inside)
        uncovered += (b - a) - sum(e - s for s, e, _ in inside)
        per.append([n for _, _, n in inside])
    assert held == len(kids), "a phase span lies outside the caller's"
    assert uncovered < 0.05 * sum(b - a for a, b in steps)
    return per


def test_engine_step_is_tiled_by_its_phases(tmp_path):
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, seed=0,
                      cache_mode="paged", prefill_chunk=16)
    assert eng.cache_mode == "paged"
    eng.submit(Request(rid=0, prompt=list(range(1, 30)), max_new=4))
    eng.submit(Request(rid=1, prompt=list(range(3, 12)), max_new=6))
    rec = _traced(tmp_path, "engine.step", eng.step, steps=5)
    per = _phases_per_step(rec, "engine.step", "engine.")
    assert len(per) == 5
    # a step in which no slot decodes ends inside engine.decode
    assert all(p in (list(ENGINE), list(ENGINE[:3])) for p in per), per
    assert per[0] == list(ENGINE[:3]) and list(ENGINE) in per


def test_decode_tick_pulls_from_the_device_once():
    """With every slot decoding, a step's one device->host transfer is
    the decode tick's pull of the tokens and the sentry's flags."""
    cfg = registry.reduced_config("qwen1.5-0.5b")
    params = init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=64, seed=0,
                      cache_mode="paged", prefill_chunk=16)
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=list(range(2, 9 + rid)),
                           max_new=8, temperature=float(rid % 2)))
    while not all(s.decoding for s in eng._slots):
        eng.step()
    pulls = []
    pull = eng._pull
    eng._pull = lambda x: pulls.append(x) or pull(x)
    for _ in range(3):
        before = len(pulls)
        eng.step()
        assert len(pulls) == before + 1
    assert all(len(x) == 2 for x in pulls)      # tokens with the flags
    assert eng.stats["sample_calls"] == (eng.stats["decode_steps"]
                                         + eng.stats["prefills"])


def test_trainer_step_is_tiled_by_its_phases(tmp_path):
    tcfg = TrainConfig(total_steps=50, checkpoint_every=2,
                       checkpoint_dir=str(tmp_path / "ck"))
    cfg = registry.reduced_config("qwen1.5-0.5b").replace(vocab=96)
    tr = Trainer(cfg, tcfg, global_batch=4, seq_len=16,
                 log=lambda *_: None)
    rec = _traced(tmp_path / "trace", "trainer.step",
                  lambda: tr.run(1), steps=4)
    per = _phases_per_step(rec, "trainer.step", "trainer.")
    plain = ["trainer.feed", "trainer.compute", "trainer.checkpoint"]
    # steps 2 and 4 save: a checkpoint span for the save, one for the wait
    assert per == [plain, plain + ["trainer.checkpoint"]] * 2, per


def test_straggler_watch_counts_steps_without_keeping_them(tmp_path):
    tcfg = TrainConfig(total_steps=50, checkpoint_every=1000,
                       checkpoint_dir=str(tmp_path / "ck"))
    tr = Trainer(registry.reduced_config("qwen1.5-0.5b").replace(vocab=96),
                 tcfg, global_batch=4, seq_len=16, log=lambda *_: None)
    assert not hasattr(tr, "step_times")
    tr._watch_straggler(0, 0.1)
    tr._watch_straggler(1, 0.9)         # the first three steps are
    tr._watch_straggler(2, 0.9)         # never judged
    assert tr.straggler_steps == []
    tr._watch_straggler(3, 0.9)
    assert tr.straggler_steps == [3]


def test_unit_ops_carry_the_activation_name(tmp_path):
    """The lowered bert training step names the unit's GELU ops, so a
    profiler trace shows them under ``unit.gelu_dualmode``."""
    cfg = registry.reduced_config("bert-base").replace(
        activation="gelu_dualmode")
    tcfg = TrainConfig(total_steps=10, checkpoint_dir=str(tmp_path / "ck"))
    tr = Trainer(cfg, tcfg, global_batch=2, seq_len=16,
                 log=lambda *_: None)
    tokens, labels = tr.data.batch(0)
    with jax.set_mesh(tr.mesh):
        text = tr.step_fn.lower(tr.state, {"tokens": tokens,
                                           "labels": labels}
                                ).as_text(debug_info=True)
    assert "unit.gelu_dualmode" in text
    assert "unit.silu_dualmode" not in text
