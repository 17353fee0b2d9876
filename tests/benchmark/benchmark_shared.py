"""What the benchmark's tests share, imported by name (`from benchmark_shared
import declared`): not a `conftest.py`, because eight files of `tests/` say
`from conftest import ...` and a second module of that name, imported later,
takes the first one's place in `sys.modules`.

`declared` hands a test `BENCHMARK.json` as parsed, with the root its files
are found under: once as committed, and once as a copy to which an addition
has been appended (`addition`), so that a test which holds what an entry
*is* passes on both, and one which holds *where* an entry stands, or which
cells happen to share it, fails here and not as a refused PR.

`agg`, `summary` and `a_run` plant a run and its statements' summaries for
the readers of the join's phases.
"""

import collections
import copy
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# What a `model_config` PR appends: a configuration with its file, a cell
# that names it and a traffic mix that is there, a per-layer metric of the
# new cell's own with its reader.
NEW_CONFIG, NEW_CELL, NEW_METRIC = "tpch_sf1_added", "sf1_added", "added_per_stmt"
LIKE_CONFIG, LIKE_CELL = "tpch_sf1_join", "sf1_q3"
Addition = collections.namedtuple("Addition", "bench root config cell metric")
NEW_READER = '"""scheduler + operators: statements the window completed."""\n' \
             '\n\ndef read(run):\n    return len(run["completed"]) or None\n'


def read_bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def append_an_addition(bench, root):
    """Appends the addition to `bench` and writes its files under `root`."""
    like = next(c for c in bench["configs"] if c["name"] == LIKE_CONFIG)
    file = f"benchmark/configs/{NEW_CONFIG}.json"
    with open(os.path.join(root, like["file"])) as f:
        config = {**json.load(f), "name": NEW_CONFIG}
    with open(os.path.join(root, file), "w") as f:
        json.dump(config, f, indent=1)
    bench["configs"].append({**like, "name": NEW_CONFIG, "file": file})
    cell = next(w for w in bench["workloads"] if w["name"] == LIKE_CELL)
    bench["workloads"].append({**cell, "name": NEW_CELL, "config": NEW_CONFIG})
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           NEW_METRIC + ".py"), "w") as f:
        f.write(NEW_READER)
    bench["per_layer"].append({
        "name": NEW_METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduler + operators",
        "moves": "statement_s", "workloads": [NEW_CELL]})


def copy_of_the_benchmark(dst):
    """`BENCHMARK.json` and the directories under its `paths`, in `dst`."""
    bench = read_bench(ROOT)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(dst, p),
                        ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    return bench


@pytest.fixture(scope="session")
def addition(tmp_path_factory):
    """A copy of the benchmark with the addition appended: the parsed file,
    the copy's root and the new entries' names. Made once a process: a test
    that breaks it works on a copy of its own."""
    root = str(tmp_path_factory.mktemp("with_an_addition"))
    bench = copy_of_the_benchmark(root)
    append_an_addition(bench, root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return Addition(read_bench(root), root, NEW_CONFIG, NEW_CELL, NEW_METRIC)


@pytest.fixture(params=["as_committed", "with_an_addition"])
def declared(request):
    """(bench, root): the committed benchmark, then the copy with an addition."""
    if request.param == "as_committed":
        return read_bench(ROOT), ROOT
    bench, root = request.getfixturevalue("addition")[:2]
    return copy.deepcopy(bench), root


# -- planted summaries

def agg(n, busy, **more):
    return {"n": n, "busy_s": busy, "self_s": busy, "max_s": busy / n, **more}


def summary(query_id, k, search=True, steps=7):
    """One statement's summary, its times stretched by `k`: two builds a
    statement however long it runs, `steps` halving rounds between them."""
    task = {"exchange_wait": agg(3 * k, 0.8 * k, wait=True),
            "join_build": agg(2, 1.5 * k, items=3 * k),
            "host_sync:join_build_rows": agg(2, 0.02 * k)}
    other = {"program_call:Project": agg(k, 0.002 * k)}
    if search:
        task["join_search"] = agg(1, 1e-5 * k, items=steps - 2)
        # a build may be observed from another thread too
        other["join_search"] = agg(1, 1e-5 * k, items=2)
    return {"queryId": query_id, "wall_s": 9.0 * k, "tasks": 5,
            "task_wall_s": 12.0 * k, "exchange_wait_s": 8.0 * k,
            "spans": 70 * k, "dropped": 0,
            "phases": {"task": task, "fragment-window-producer": other}}


def a_run(ids_and_starts, profiler_stopped_at):
    return {"traced": {"t1": profiler_stopped_at},
            "completed": [{"query_id": q, "t0": t0, "t1": t0 + 1.0}
                          for q, t0 in ids_and_starts]}
