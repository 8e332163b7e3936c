"""The trace reduction, the kernels' operation and byte counts, and the
peaks table, checked against counts made by hand."""
import json
from pathlib import Path

import pytest

from bench import harness, peaks, trace

FIXTURE = Path(__file__).parent / "data" / "trace_chat_step.json"


def _rec():
    # chip 0: ops A [0,10) and B [5,20) overlap, the decode kernel
    # [30,40), C [60,70); host: one engine step [0,50), then a wait
    return {"device_ops": [[0, "A.1", 0, 10], [0, "B.2", 5, 20],
                           [0, "_flash_decode_paged_jit.3", 30, 40],
                           [0, "C.4", 60, 70]],
            "spans": [["bench.window", 0, 100], ["engine.step", 0, 50],
                      ["bench.wait_arrival", 50, 100]],
            "window": [0, 100], "chips": 1}


def test_busy_union_and_window():
    rec = _rec()
    assert trace.union([(5, 20), (0, 10), (30, 40)]) == [(0, 20), (30, 40)]
    assert trace.busy_s(rec) == pytest.approx(40e-9)
    assert trace.window_s(rec) == pytest.approx(100e-9)
    busy, inside = trace.busy_within(rec, "engine.step")
    assert (busy, inside) == (pytest.approx(30e-9), pytest.approx(50e-9))


def test_idle_gaps_labelled_by_span():
    got = dict(trace.idle_by_label(_rec()))
    # engine.step: [20,30) + [40,50); wait: [50,60) + [70,100)
    assert got == {"engine.step": pytest.approx(20e-9),
                   "bench.wait_arrival": pytest.approx(40e-9)}


def test_per_kernel_time_and_top_ops():
    rec = _rec()
    assert trace.kernel_seconds(rec, "_flash_decode_paged_jit") == (
        pytest.approx(10e-9), 1)
    ops = dict(trace.op_seconds(rec))
    assert ops["B"] == pytest.approx(15e-9)
    assert trace.breakdown(rec, top=2)["device_ops"][0][0] == "B"


def test_ops_holding_others_are_not_counted_twice():
    rec = _rec()
    rec["device_ops"].append([0, "while.9", 0, 45])     # holds A, B, kernel
    ops = dict(trace.op_seconds(rec))
    assert "while" not in ops and ops["A"] == pytest.approx(10e-9)
    assert trace.busy_s(rec) == pytest.approx(55e-9)    # [0,45) + [60,70)


def test_window_clips_ops_and_two_chips_average():
    rec = _rec()
    rec["window"] = [5, 35]
    assert trace.busy_s(rec) == pytest.approx(20e-9)   # [5,20) + [30,35)
    rec["device_ops"].append([1, "A.5", 5, 35])
    rec["chips"] = 2
    assert trace.busy_s(rec) == pytest.approx(25e-9)


def test_flash_pallas_counts_live_causal_pairs():
    fl = harness.load_by_name("flops", "flash_pallas")
    dims = {"heads": 2, "kv_heads": 1, "head_dim": 4, "itemsize": 2}
    flops, moved = fl.cost({"c0": 2, "n": 3}, dims)
    # queries at positions 2, 3, 4 see 3 + 4 + 5 = 12 keys
    assert flops == 4 * 2 * 4 * 12
    # q and o: 3 rows x 2 heads x 4; k and v: 5 keys x 1 head x 4
    assert moved == 2 * (2 * 3 * 2 * 4 + 2 * 5 * 1 * 4)


def test_flash_decode_paged_counts_live_keys():
    fl = harness.load_by_name("flops", "flash_decode_paged")
    dims = {"heads": 2, "kv_heads": 1, "head_dim": 4, "itemsize": 2}
    flops, moved = fl.cost({"kv": [3, 5]}, dims)
    assert flops == 4 * 2 * 4 * 8
    assert moved == 2 * (2 * 8 * 1 * 4 + 2 * 2 * 2 * 4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.device_peaks("TPU v99")
    t, bound = peaks.least_time(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.least_time(1.0, 819e9, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_roofline_reader_needs_a_trace_and_calls():
    run = harness.Run(spans=harness.Spans(), window=(0, 1), counters={},
                      calls={"flash_decode_paged": [{"kv": [3, 5]}]},
                      dims={"heads": 2, "kv_heads": 1, "head_dim": 4,
                            "itemsize": 2, "layers": 1},
                      n_active={}, device_kind="TPU v5 lite")
    assert harness.kernel_roofline(run, "flash_decode_paged") is None
    run.trace = _rec()
    flops, moved = 256, 192
    want = 100 * max(flops / 197e12, moved / 819e9) / 10e-9
    assert harness.kernel_roofline(run, "flash_decode_paged") == \
        pytest.approx(want)


def test_recorded_decode_step():
    """One engine step of the chat cell as the chip traced it: 24 layers,
    so 24 calls of the paged decode kernel; every idle instant lies in
    the step's span; busy and idle fill the window."""
    rec = json.loads(FIXTURE.read_text())
    lo, hi = rec["window"]
    # busy by a sweep over start/end events, independent of union()
    edges = sorted([(max(s, lo), 1) for _, _, s, e in rec["device_ops"]
                    if e > lo and s < hi] +
                   [(min(e, hi), -1) for _, _, s, e in rec["device_ops"]
                    if e > lo and s < hi])
    depth, busy, last = 0, 0, lo
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth, last = depth + d, t
    assert trace.busy_s(rec) == pytest.approx(busy / 1e9)
    idle = dict(trace.idle_by_label(rec))
    assert set(idle) == {"engine.step"}
    assert idle["engine.step"] + trace.busy_s(rec) == \
        pytest.approx(trace.window_s(rec))
    t, n = trace.kernel_seconds(rec, "_flash_decode_paged_jit")
    assert n == 24 and 0 < t < trace.busy_s(rec)
    leaf_total = sum(v for _, v in trace.op_seconds(rec))
    assert leaf_total <= trace.busy_s(rec) * (1 + 1e-9)
