"""BENCHMARK.json and the files it names: allowed names and units, every
cell's data found by name, and a check that fits its time budget."""

import json
import re
from pathlib import Path

import pytest

from bench.check import LIMITED

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = MANIFEST["workloads"]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    cmd = MANIFEST["command"]
    assert len(cmd) <= 32 and (ROOT / cmd[1]).is_file()
    assert cmd[1].startswith("bench/")


@pytest.mark.parametrize("name", [m["name"] for m in METRICS]
                         + [c["name"] for c in CELLS]
                         + [c["name"] for c in MANIFEST["configs"]])
def test_names_use_allowed_characters(name):
    assert NAME.match(name)


def test_names_are_unique():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len({c["name"] for c in CELLS}) == len(CELLS)
    assert len({(c["config"], c["traffic"]) for c in CELLS}) == len(CELLS)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if metric in MANIFEST["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    for cell in metric.get("workloads", []):
        assert cell in {c["name"] for c in CELLS}


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    conf = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert (ROOT / conf["file"]).is_file()
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    params = json.loads(
        (ROOT / "bench" / "cells" / f"{cell['name']}.json").read_text())
    assert params["limits"] and set(params["limits"]) <= set(LIMITED)
    applies = lambda m: cell["name"] in m.get("workloads", [cell["name"]])
    e2e = [m["name"] for m in MANIFEST["end_to_end"] if applies(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(applies(m) for m in MANIFEST["per_layer"])
    for m in MANIFEST["per_layer"]:
        if applies(m):
            assert m["moves"] in e2e


@pytest.mark.parametrize("conf", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_files_state_what_runs(conf):
    raw = json.loads((ROOT / conf["file"]).read_text())
    assert raw["source"] == conf["source"]
    assert raw["reduced"] == conf["reduced"]
    assert set(raw["reduced"]) <= set(raw.get("published", {}))
    widths = re.compile(r"(hidden|intermediate|latent|state|projection"
                        r"|_dim$|_rank$|head|expan|per_tok)")
    assert not any(widths.search(k) for k in conf["reduced"])


def test_a_full_check_of_24_cells_fits_its_time():
    s = MANIFEST["run_seconds"]
    assert 1 <= s <= 51
    cells = 24
    total = (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_at_most_half_the_cells_take_four_chips():
    four = sum(c["chips"] == 4 for c in CELLS)
    assert four <= max(1, len(CELLS) // 2)
