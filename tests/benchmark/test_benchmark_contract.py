"""BENCHMARK.json against the builder's schema, as far as a test can see:
names, units, lengths, and every file a cell needs found by name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert sorted(bench) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["name"] not in names
        names.add(m["name"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for text in [w["why"] for w in bench["workloads"]] + \
            [c["why"] for c in bench["configs"]] + \
            [c["source"] for c in bench["configs"]] + bench["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_cells_find_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        cfg = configs[w["config"]]
        assert any(cfg["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == cfg["reduced"] and conf["chips"] == w["chips"]
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for q in mix["queries"]:
            for ext in (".sql", ".json"):
                assert os.path.exists(os.path.join(
                    ROOT, "benchmark", "queries", q["id"] + ext))
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "reference", q["id"] + ".py"))
    assert {c["name"] for c in bench["configs"]} == {w["config"]
                                                     for w in bench["workloads"]}
    names = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= names


def test_every_metric_has_a_reader(bench):
    for kind, key in (("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")):
        for m in bench[key]:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, m["name"] + ".py")), m["name"]


def test_files_under_paths_are_named_plainly(bench):
    for p in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".cache")]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel
