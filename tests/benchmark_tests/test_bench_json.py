"""BENCHMARK.json against its own rules, as far as a test can hold it."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16 and 1 <= len(BENCH["command"]) <= 32
    assert BENCH["command"][1].startswith(tuple(p + "/" for p in BENCH["paths"]))
    cost = (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert cost <= 43200  # a full check with all 24 cells fits


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and 1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"]) <= 200
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"])) and (ROOT / entry["file"]).is_file()
    body = json.loads((ROOT / entry["file"]).read_text())
    assert body["source"] == entry["source"] and body["reduced"] == entry["reduced"]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json"
    body = json.loads(traffic.read_text())
    assert (ROOT / "benchmark" / "modes" / f"{body['mode']}.py").is_file()
    assert body["why"] and "trace_seconds" in body
    # every cell reports setup_s, another end-to-end metric and a per-layer metric
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_cells_are_unique_and_four_chip_cells_are_within_the_share():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(CELLS) == len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 4, 1)


@pytest.mark.parametrize("metric", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_metric_its_cells_report(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200
    assert (ROOT / "benchmark" / "layer_metrics" / f"{metric['name']}.py").is_file()
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    assert set(cells_of(metric)) <= set(cells_of(moved))
    if "_roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique_and_setup_is_bounded():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup


def test_every_file_under_paths_has_an_allowed_name():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert allowed.match(str(f.relative_to(ROOT))), f
