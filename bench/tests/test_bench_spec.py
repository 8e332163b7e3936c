"""BENCHMARK.json names only what exists: every cell's configuration,
traffic, limits and loop, every per-layer metric's reader, and every
kernel roofline's operation count."""
import json
import re

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_finds_its_files():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"], SPEC)
        assert (harness.BENCH / "loops" /
                f"{cell.traffic['loop']}.py").is_file()
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert m["moves"] in {x["name"] for x in cell.end_to_end}


def test_every_metric_has_its_reader():
    for m in SPEC["per_layer"]:
        assert callable(harness.load_by_name("metrics", m["name"]).read)
        if m["name"].endswith("_roofline"):
            kernel = m["name"][:-len("_roofline")]
            fl = harness.load_by_name("flops", kernel)
            assert callable(fl.cost) and fl.MATCH


def test_configs_name_their_reference_and_adapter():
    for c in SPEC["configs"]:
        conf = json.loads((harness.ROOT / c["file"]).read_text())
        for kind in ("references", "adapters"):
            assert (harness.BENCH / kind / f"{conf['family']}.py").is_file()
        for key in c["reduced"]:
            assert key in conf and key in conf["reduced"]
