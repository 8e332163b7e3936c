"""Whole runs of the tiny cells on the CPU, past the look for a chip:
``correct`` comes out true on the sound program and false with each
fault the cell can have planted under the timed path; the controls (the
reference one precision step down) fail the same limits.

The limits here are for the tiny sizes, set from readings at those sizes
(program, control, fault):
  chat  widest logit gap: 0-0.007, 0.04-0.11, 0.59-0.68
  train loss gap 2.1e-7-6.4e-7, 1.4e-4-2.6e-4 (bf16 control),
        1.2e-2-2.4e-2 (half batch); grad gap 1.9e-6-5.4e-6,
        2.3e-3-5.5e-3, 0.097-0.13 (half batch), 1.0 (state unchanged)
"""
import json

import numpy as np
import pytest

from bench import faults, harness
from bench.tests.tiny import run_tiny, tiny_cell

CHAT, TRAIN = "qwen1.5-0.5b.chat", "bert-base.train_s512"
CHAT_LIMITS = {"logit_gap": 0.02}
TRAIN_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-3}


def _cell(name):
    cell = tiny_cell(name)
    cell.limits = dict(CHAT_LIMITS if name == CHAT else TRAIN_LIMITS)
    return cell


def test_chat_run_is_correct_and_prints_its_line():
    out = run_tiny(_cell(CHAT))
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)


def test_chat_altered_token_is_not_correct():
    with faults.token_altered():
        out = run_tiny(_cell(CHAT))
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_train_run_correct_only_when_sound(fault):
    cell = _cell(TRAIN)
    if fault is None:
        out = run_tiny(cell)
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"setup_s", "train_tok_s"}
        return
    with faults.FAULTS[fault]():
        out = run_tiny(cell)
    assert not out["correct"], out["compared"]


def test_chat_control_fails_the_limit_the_program_meets():
    """Served tokens of a fixed batch (no clock involved), judged by the
    reference: the program within the limit, the control not."""
    from repro.serve import Request
    loop = harness.load_by_name("loops", "open")
    cell = _cell(CHAT)
    eng, mcfg = loop.build(cell, 21)
    rng = np.random.default_rng(21)
    reqs = [Request(rid=i, max_new=12,
                    prompt=rng.integers(0, mcfg.vocab, 30 + 7 * i).tolist())
            for i in range(6)]
    got = eng.run(reqs)
    served = [(r.prompt, got[r.rid]) for r in reqs]
    prog, _ = loop.reference_gaps(cell, 21, served)
    ctrl, _ = loop.reference_gaps(cell, 21, served, control=True)
    assert max(prog) <= CHAT_LIMITS["logit_gap"] < max(ctrl)


def test_train_control_fails_the_limits_the_program_meets():
    loop = harness.load_by_name("loops", "train")
    cell = _cell(TRAIN)
    got = loop.readings(cell, 22)
    assert all(got["program"][k] <= v for k, v in TRAIN_LIMITS.items())
    assert any(got["control"][k] > v for k, v in TRAIN_LIMITS.items())
