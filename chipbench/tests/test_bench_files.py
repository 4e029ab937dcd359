"""BENCHMARK.json resolves to the harness's files and keeps the contract's
form, and a cell or a metric is added by adding files alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_fits_its_time_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_reduced_lists_every_departure_from_the_source(conf):
    """Each key in ``reduced`` differs from the published value the file
    keeps, and each published value the file departs from is listed, with
    its cause."""
    data = json.loads((ROOT / conf["file"]).read_text())
    published = data["published"]
    assert set(conf["reduced"]) == set(published)
    for key in conf["reduced"]:
        assert data[key] != published[key], key
        assert data["departures"][key], key


def test_names_units_and_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 0 < len(m["layer"]) <= 200 and "\n" not in m["layer"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.load_cell(cell)
    assert (ROOT / "chipbench" / "paths"
            / f"{c.traffic['path']}.py").is_file()
    assert (ROOT / "chipbench" / "reference"
            / f"{c.conf['reference']}.py").is_file()
    assert c.limits and set(c.limits) <= {
        "loss_gap", "loss1_gap", "grad_gap", "grad_median_gap", "delta_gap",
        "delta_median_gap"}
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(run.load_module("metrics", m["name"]).read)


def test_every_per_layer_metric_has_a_reader_and_listed_cells():
    for m in BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", []):
            assert cell in CELLS
            assert m["moves"] in {e["name"] for e in run.load_cell(
                cell).end_to_end}


def test_a_cell_and_a_metric_are_added_by_new_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    files = tmp_path / "chipbench"
    traffic = json.loads((files / "traffic" / "rad-dense.json").read_text())
    traffic["batch"] = 2
    (files / "traffic" / "rad-dense-b2.json").write_text(json.dumps(traffic))
    (files / "limits" / "rad-dense-b2.json").write_text(
        (files / "limits" / "rad-dense.json").read_text())
    (files / "metrics" / "steps_in_window.py").write_text(
        "def read(rec):\n    return float(rec.steps) or None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "rad-dense-b2", "config":
                               "gpt2-xl-rad12", "traffic": "rad-dense-b2",
                               "chips": 1, "why": "a throwaway cell"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "launcher", "moves": "tokens_per_s",
                               "workloads": ["rad-dense-b2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell("rad-dense-b2", root=tmp_path)
    assert cell.traffic["batch"] == 2 and cell.conf["n_layer"] == 12
    assert [m["name"] for m in cell.per_layer][-1] == "steps_in_window"
    reader = run.load_module("metrics", "steps_in_window", root=tmp_path)
    assert reader.read(run.Record(7, 1.0, 1, 1, 1.0, {}, 0.0, [])) == 7.0
