"""The phase readers (``engine_idle.*``, ``trainer_idle.*``) on synthetic
records whose program spans nest inside the benchmark's step spans."""
import json
from pathlib import Path

import pytest

from bench import harness, trace

FIXTURE = Path(__file__).parent / "data" / "trace_chat_phases.json"

CHAT = ("admit", "prefill", "decode", "sample")
TRAIN = ("feed", "compute", "checkpoint")


def _run(rec) -> harness.Run:
    return harness.Run(spans=harness.Spans(), window=(0, 1), counters={},
                       calls={}, dims={}, n_active={},
                       device_kind="TPU v5 lite", trace=rec)


def _read(name: str, rec):
    return harness.load_by_name("metrics", name).read(_run(rec))


def _chat_rec():
    # two engine steps tiled by their phases, a wait between them; ops
    # in the prefill [60,120), in the decode [160,280) and [530,790)
    spans = [["bench.window", 0, 1000],
             ["engine.step", 0, 400], ["engine.admit", 0, 50],
             ["engine.prefill", 50, 150], ["engine.decode", 150, 300],
             ["engine.sample", 300, 400],
             ["bench.wait_arrival", 400, 500],
             ["engine.step", 500, 900], ["engine.admit", 500, 520],
             ["engine.prefill", 520, 521], ["engine.decode", 521, 800],
             ["engine.sample", 800, 900],
             ["bench.wait_arrival", 900, 1000]]
    ops = [[0, "fusion.1", 60, 120], [0, "_flash_decode_paged_jit.2", 160,
                                      280],
           [0, "copy.3", 530, 790]]
    return {"device_ops": ops, "spans": spans, "window": [0, 1000],
            "chips": 1}


def _train_rec(save: bool = True):
    # four steps of 250 tiled by feed [0,20), compute [20,240) and the
    # wait for a save [240,250); the step op runs [40,230); the last
    # step saves for 100 before its wait
    spans, ops = [["bench.window", 0, 1000]], []
    for k in range(4):
        a = 250 * k
        spans += [["trainer.step", a, a + 250], ["trainer.feed", a, a + 20],
                  ["trainer.compute", a + 20, a + 240]]
        ops.append([0, f"fusion.{k}", a + 40, a + 230])
        if save:
            spans.append(["trainer.checkpoint", a + 240, a + 250])
    if save:
        spans[-2:] = [["trainer.compute", 770, 890],
                      ["trainer.checkpoint", 890, 990],
                      ["trainer.checkpoint", 990, 1000]]
        ops[-1] = [0, "fusion.3", 790, 880]
    return {"device_ops": ops, "spans": spans, "window": [0, 1000],
            "chips": 1}


def test_chat_phases_sum_to_device_idle():
    rec = _chat_rec()
    got = {p: _read(f"engine_idle.{p}", rec) for p in CHAT}
    # idle inside engine.step: 800 - 440 busy; prefill idles 100 - 60 + 1
    assert got["prefill"] == pytest.approx(100 * 41 / 800)
    assert got["admit"] == pytest.approx(100 * 70 / 800)
    assert got["decode"] == pytest.approx(100 * (30 + 19) / 800)
    assert sum(got.values()) == pytest.approx(_read("device_idle.chat", rec))


def test_train_phases_sum_to_device_idle():
    rec = _train_rec()
    got = {p: _read(f"trainer_idle.{p}", rec) for p in TRAIN}
    assert got["feed"] == pytest.approx(100 * 80 / 1000)
    assert got["checkpoint"] == pytest.approx(100 * 140 / 1000)
    assert sum(got.values()) == pytest.approx(
        _read("device_idle.train", rec))


def test_checkpoint_reads_zero_without_a_save_in_the_window():
    rec = _train_rec(save=False)
    assert _read("trainer_idle.checkpoint", rec) == 0.0
    rec["spans"].append(["trainer.checkpoint", 1100, 1300])  # after it
    assert _read("trainer_idle.checkpoint", rec) == 0.0
    # the steps' spans still tile the window up to the waits left out
    idle = _read("device_idle.train", rec)
    assert _read("trainer_idle.feed", rec) + \
        _read("trainer_idle.compute", rec) == pytest.approx(idle - 4.0)


def test_a_program_without_phase_spans_reads_nothing():
    chat, train = _chat_rec(), _train_rec()
    chat["spans"] = [s for s in chat["spans"]
                     if s[0] in ("bench.window", "engine.step")]
    train["spans"] = [s for s in train["spans"]
                      if s[0] in ("bench.window", "trainer.step")]
    for p in CHAT:
        assert _read(f"engine_idle.{p}", chat) is None
        assert _read(f"engine_idle.{p}", None) is None
    for p in TRAIN:
        assert _read(f"trainer_idle.{p}", train) is None
        assert _read(f"trainer_idle.{p}", None) is None


def test_recorded_step_names_its_phases():
    """One engine step of the chat cell as the chip traced it, with the
    program's phase spans: the idle falls to the four phases, and the
    benchmark's own span and the gaps between phases hold under 2%."""
    rec = json.loads(FIXTURE.read_text())
    idle = dict(trace.idle_by_label(rec))
    assert {f"engine.{p}" for p in CHAT} <= set(idle), idle
    whole = sum(idle.values())
    assert whole + trace.busy_s(rec) == pytest.approx(trace.window_s(rec))
    assert idle.get("engine.step", 0) + idle.get("outside_spans", 0) < \
        0.02 * whole
    _, n = trace.kernel_seconds(rec, "_flash_decode_paged_jit")
    assert n == 24
