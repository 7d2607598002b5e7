"""``BENCHMARK.json`` against the contract's rules that a file can be
held to, and against the files it names."""

import json
import os
import re

import pytest

from conftest import BENCH, CHECKOUT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for entry in bench["configs"] + bench["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_configuration_has_a_cell_and_every_file_exists(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"] and cell["why"] == w["why"]
        for s in cell["statements"]:
            for folder, ext in (("sql", ".sql"), ("reference", ".py")):
                assert os.path.exists(os.path.join(
                    BENCH, folder, w["config"], s["name"] + ext))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_each_per_layer_metric_moves_a_metric_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in reports
        where = set(m.get("workloads", cells))
        assert where and where <= set(cells)
        assert where <= reports[m["moves"]]
    for cell in cells:
        assert cell in reports["setup_s"]
        assert any(cell in r for n, r in reports.items() if n != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters(bench):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), CHECKOUT)
            assert allowed.match(rel), rel
