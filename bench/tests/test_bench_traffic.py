"""The traffic generator, and that the harness finds a mix by its name."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import generate, harness


def _chat():
    return harness.load_cell("qwen1.5-0.5b.chat").traffic


def test_same_seed_same_requests():
    t = _chat()
    a = generate.open_loop(t, 51, 2 ** 33 + 5, 1000)
    b = generate.open_loop(t, 51, 2 ** 33 + 5, 1000)
    assert a == b
    assert a != generate.open_loop(t, 51, 2 ** 33 + 6, 1000)


def test_lengths_stay_in_their_clips():
    t = _chat()
    reqs = generate.open_loop(t, 51, 3, 1000)
    plens = [len(r.prompt) for r in reqs]
    outs = [r.max_new for r in reqs]
    assert t["prompt"]["min"] <= min(plens) <= max(plens) <= \
        t["prompt"]["max"]
    assert t["output"]["min"] <= min(outs) <= max(outs) <= \
        t["output"]["max"]
    assert all(0 <= x < 1000 for r in reqs for x in r.prompt)


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = _chat()
    a = generate.open_loop(t, 51, 1, 1000)
    b = generate.open_loop(t, 51, 2, 1000)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga = np.diff([0.0] + [r.due_s for r in a])
    gb = np.diff([0.0] + [r.due_s for r in b])
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_poisson_schedule_has_the_stated_mean():
    t = dict(_chat(), rate_per_s=4.0, warmup_s=0.0, tail_s=0.0)
    reqs = generate.open_loop(t, 2000, 9, 100)
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    assert gaps.mean() == pytest.approx(0.25, rel=0.05)
    assert np.median(np.log(np.clip([len(r.prompt) for r in reqs],
                                    1, None))) == \
        pytest.approx(np.log(t["prompt"]["median"]), abs=0.1)


def test_train_batches_are_seeded_and_rows_differ():
    t = harness.load_cell("bert-base.train_s512").traffic
    t = dict(t, batch=4, seq=32)
    a = generate.TrainData(t, 2 ** 34, 500)
    tok, lab = a.batch(0)
    tok2, lab2 = generate.TrainData(t, 2 ** 34, 500).batch(0)
    assert (np.asarray(tok) == np.asarray(tok2)).all()
    assert not (np.asarray(a.batch(1)[1]) == np.asarray(lab)).all()
    rows = {tuple(r) for r in np.asarray(lab).tolist()}
    assert len(rows) == 4
    masked = np.asarray(tok) == t["mask_id"]
    assert (np.asarray(tok)[~masked] == np.asarray(lab)[~masked]).all()


def test_harness_finds_a_new_traffic_file_by_name(tmp_path):
    """A new mix is a new data file and a new entry; no file that exists
    is edited."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    new = dict(_chat(), rate_per_s=0.25)
    (tmp_path / "bench" / "traffic" / "slow_chat.json").write_text(
        json.dumps(new))
    (tmp_path / "bench" / "limits" / "qwen1.5-0.5b.slow_chat.json"
     ).write_text(json.dumps({"logit_gap": 1.0}))
    spec["workloads"].append({"name": "qwen1.5-0.5b.slow_chat",
                              "config": "qwen1.5-0.5b",
                              "traffic": "slow_chat", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    code = ("from bench import harness; "
            "c = harness.load_cell('qwen1.5-0.5b.slow_chat'); "
            "print(c.traffic['rate_per_s'], c.traffic['loop'], "
            "harness.load_by_name('loops', c.traffic['loop']).__name__)")
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["0.25", "open", "bench_loops_open"]


def test_no_result_without_the_program(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark's own
    files, a run exits non-zero and prints no result."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                          "qwen1.5-0.5b.chat", "--seed", "1", "--seconds",
                          "1"], cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_window_defaults_to_the_specs_run_seconds():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell("qwen1.5-0.5b.chat")
    assert cell.seconds == spec["run_seconds"]
    assert harness.load_cell("qwen1.5-0.5b.chat", seconds=3.0).seconds == 3.0


def test_requests_in_the_system_and_slots_held():
    """Due and unfinished requests at a time, and the slots the cell's
    requests held after the last step by then."""
    import types
    loop = harness.load_by_name("loops", "open")
    tr = types.SimpleNamespace(
        times={0: [1.0, 2.0], 1: [3.0, 6.0]}, reason={0: "max_new",
                                                     1: "max_new"},
        busy=[(1.0, 1), (3.0, 2), (6.0, 1)])
    out = {"tracker": tr, "due": [0.5, 2.5, 4.0], "window": (2.0, 5.0)}
    assert [loop.in_system(out, t) for t in (0.4, 1.5, 2.0, 5.0, 7.0)] == \
        [0, 1, 0, 2, 1]
    assert [loop.slots_held(out, t) for t in (0.5, 2.0, 5.0, 6.0)] == \
        [0, 1, 2, 1]
    assert loop.occupancy(out) == "in system 0 -> 2, slots held 1 -> 2"
