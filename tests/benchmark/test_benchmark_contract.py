"""BENCHMARK.json against the builder's schema, as far as a test can see:
names, units, lengths, and every file a cell needs found by name. Each check
is a function of the parsed file and the root its files are found under: the
tests call it on the committed benchmark, and the rehearsal of an addition on
a copy to which a configuration, a cell and a per-layer metric were appended."""

import copy
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as brun  # noqa: E402
from benchmark_shared import addition  # noqa: E402,F401

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def check_top_level_keys(bench, root):
    assert sorted(bench) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024


def check_names_units_and_lines(bench, root):
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


def check_cells_find_their_files(bench, root):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        cfg = configs[w["config"]]
        assert any(cfg["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(root, cfg["file"])) as f:
            conf = json.load(f)
        assert conf["reduced"] == cfg["reduced"] and conf["chips"] == w["chips"]
        with open(os.path.join(root, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for q in mix["queries"]:
            for ext in (".sql", ".json"):
                assert os.path.exists(os.path.join(
                    root, "benchmark", "queries", q["id"] + ext))
            assert os.path.exists(os.path.join(
                root, "benchmark", "reference", q["id"] + ".py"))
    assert {c["name"] for c in bench["configs"]} == {w["config"]
                                                     for w in bench["workloads"]}
    names = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= names


def check_every_metric_has_a_reader(bench, root):
    for kind, key in (("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")):
        for m in bench[key]:
            assert os.path.exists(os.path.join(
                root, "benchmark", kind, m["name"] + ".py")), m["name"]


def check_files_under_paths_are_named_plainly(bench, root):
    for p in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(root, p)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", ".cache")]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), root)
                assert PATH.match(rel), rel


CHECKS = [check_top_level_keys, check_names_units_and_lines,
          check_cells_find_their_files, check_every_metric_has_a_reader,
          check_files_under_paths_are_named_plainly]


def test_top_level_keys(bench):
    check_top_level_keys(bench, ROOT)


def test_names_units_and_lines(bench):
    check_names_units_and_lines(bench, ROOT)


def test_cells_find_their_files(bench):
    check_cells_find_their_files(bench, ROOT)


def test_every_metric_has_a_reader(bench):
    check_every_metric_has_a_reader(bench, ROOT)


def test_files_under_paths_are_named_plainly(bench):
    check_files_under_paths_are_named_plainly(bench, ROOT)


# -- an addition, rehearsed

def cell_metrics(monkeypatch, root, cell):
    """Names of the per-layer metrics `run.py` selects for `cell` of the
    benchmark under `root`."""
    monkeypatch.setattr(brun, "ROOT", root)
    return [m["name"] for m in brun.load_cell(cell)["per_layer"]]


@pytest.mark.parametrize("check", CHECKS, ids=lambda check: check.__name__)
def test_an_addition_at_the_end_of_each_list_passes(check, addition):
    """What a PR that changes the program may bring (`tests/benchmark/
    benchmark_shared.py`, `append_an_addition`): a configuration with its file, a cell
    that names it, a per-layer metric with `workloads: [<the new cell>]` and
    its reader, each **appended at the end** of its list. That rule is the
    driver's, not the harness's, which finds everything by name: an entry
    inserted before one that is there, a list reordered, or an existing
    metric's `workloads` extended reads to the driver as a change to the
    accepted benchmark and the PR is refused unmeasured (`benchmark_edited`:
    ledger, PR 31). The declaration tests of this directory run on the same
    copy through the `declared` fixture, so a test that pins a position or a
    list's length fails here first."""
    check(addition.bench, addition.root)


def test_the_new_cell_reads_its_own_metric_and_every_unlisted_one(
        addition, monkeypatch):
    got = cell_metrics(monkeypatch, addition.root, addition.cell)
    unlisted = {m["name"] for m in addition.bench["per_layer"]
                if "workloads" not in m}
    assert unlisted and set(got) == unlisted | {addition.metric}
    assert len(got) == len(set(got))
    # its one-line reader is found by name, under the copy
    monkeypatch.setattr(brun, "HERE", os.path.join(addition.root, "benchmark"))
    read = brun.load_reader("layer_metrics", addition.metric)
    assert read({"completed": [{}, {}]}) == 2
    assert read({"completed": []}) is None


@pytest.mark.parametrize("cell", ["sf10_q6", "sf10_q1", "sf1_q6_qgen", "sf1_q3"])
def test_an_old_cell_reads_what_it_read_before_the_addition(
        cell, addition, monkeypatch):
    before = cell_metrics(monkeypatch, ROOT, cell)
    after = cell_metrics(monkeypatch, addition.root, cell)
    assert addition.metric not in after and after == before


def without_the_reader(added):
    os.remove(os.path.join(added.root, "benchmark", "layer_metrics",
                           added.metric + ".py"))


def without_the_configuration(added):
    added.bench["configs"][:] = [c for c in added.bench["configs"]
                                 if c["name"] != added.config]


def naming_a_cell_that_is_not_there(added):
    m = next(m for m in added.bench["per_layer"] if m["name"] == added.metric)
    m["workloads"] = ["sf1_q18"]


@pytest.mark.parametrize("broken, check", [
    (without_the_reader, check_every_metric_has_a_reader),
    (without_the_configuration, check_cells_find_their_files),
    (naming_a_cell_that_is_not_there, check_cells_find_their_files),
], ids=lambda f: f.__name__)
def test_an_addition_that_lacks_a_part_fails_its_check(broken, check, addition,
                                                       tmp_path):
    added = addition._replace(bench=copy.deepcopy(addition.bench),
                              root=str(tmp_path / "broken"))
    shutil.copytree(addition.root, added.root)
    check(added.bench, added.root)
    broken(added)
    with pytest.raises((AssertionError, KeyError)):
        check(added.bench, added.root)
