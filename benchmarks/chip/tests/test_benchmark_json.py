"""``BENCHMARK.json`` resolves by name to the files of every cell, and keeps
to the form the benchmark's contract sets."""
import json
import re

import harness
import pytest

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert (ROOT / c["file"]).is_file()


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    cell = harness.load_cell(ROOT / "BENCHMARK.json", workload)
    assert (harness.HERE / "drivers"
            / f"{cell.config['driver']}.py").is_file()
    assert cell.traffic["kind"]
    assert cell.limits
    for m in cell.per_layer:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("workload", CELLS)
def test_moves_names_a_metric_of_the_cell(workload):
    cell = harness.load_cell(ROOT / "BENCHMARK.json", workload)
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], workload)


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
    metric_names = [m["name"] for m in _metrics()]
    assert len(set(metric_names)) == len(metric_names)
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], m["layer"])
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_check_time_fits():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
