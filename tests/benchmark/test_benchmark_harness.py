"""The harness itself, off the chip: it refuses to run without a TPU, a
rehearsal never says `correct: true`, and - the look for a chip skipped - a
run with the timed path broken underneath comes out not correct, once for
each fault a cell of this benchmark can have."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import data as bdata, run as brun  # noqa: E402

SF = 0.01


@pytest.fixture(scope="module")
def device():
    import jax

    return jax.devices()[0]


@pytest.fixture(autouse=True)
def short_warmup(monkeypatch):
    """The mixes warm up for seconds; a test run need not."""
    from benchmark import traffic

    load_mix = traffic.load_mix

    def quick(name):
        return {**load_mix(name), "warmup_seconds": 0.0}

    monkeypatch.setattr(traffic, "load_mix", quick)


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_refuses_without_a_tpu(capsys):
    rc = brun.main(["--workload", "sf10_q6", "--seed", "5", "--seconds", "1",
                    "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""                      # no result, not even a phase
    assert "no TPU" in out.err


def test_rehearsal_never_says_correct(capsys):
    rc = brun.main(["--workload", "sf10_q6", "--seed", "2147484009",
                    "--seconds", "1", "--trace", "0", "--rehearse-sf", str(SF)])
    out = capsys.readouterr()
    res = last_json(out.out)
    assert rc != 0 and res["correct"] is False and res["rehearsal"] is True
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "compared"
    assert res["compared"]["wrong_statements"] == {"value": 0, "limit": 0}
    assert set(res["metrics"]) == {"statement_s", "rows_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "compared wrong_statements: value 0 limit 0" in out.err


def test_sound_traced_run_is_correct_and_leaves_out_what_it_cannot_read(device):
    res = brun.run_cell("sf10_q1", 3000000021, 1.0, True, device, sf_override=SF)
    assert res["correct"] is True
    assert res["compared"]["double_rel_err_max"]["value"] <= 1e-12
    # no device plane on the CPU: the readers of the device trace return
    # nothing and the harness leaves those metrics out - never a 0
    for gone in ("statement_roofline", "device_idle_pct",
                 "device_launches_per_stmt"):
        assert gone not in res["metrics"]
    # nor has a cell that repeats one text a further text to read, and the
    # tail is another cell's
    assert "first_text_s" not in res["metrics"]
    assert "statement_p95_s" not in res["metrics"]
    for there in ("first_response_s", "polls_per_stmt", "plan_s",
                  "programs_minted", "compiles_in_window", "statement_max_s"):
        assert there in res["metrics"], there
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert "busy_s" not in res["device"]


def test_changing_literals_are_all_warmed_up_and_all_compared(device, monkeypatch):
    from benchmark import traffic

    load_query = traffic.load_query

    def three_sets(qid):
        meta = load_query(qid)
        meta["params"]["sets"] = meta["params"]["sets"][::8]
        return meta

    monkeypatch.setattr(traffic, "load_query", three_sets)
    res = brun.run_cell("sf1_q6_qgen", 2147484031, 1.5, True, device,
                        sf_override=SF)
    assert res["correct"] is True and res["attempted"] >= 3
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["first_text_s"]["value"] > 0
    assert "statement_p95_s" in res["metrics"]


def test_fault_half_of_the_rows_left_out(device, monkeypatch):
    install = bdata.install

    def half(catalog, sf, seed, data):
        cut = {t: {c: ((v[0], v[1][::2]) if isinstance(v, tuple) else v[::2])
                   for c, v in cols.items()} for t, cols in data.items()}
        install(catalog, sf, seed, cut)

    monkeypatch.setattr(bdata, "install", half)
    res = brun.run_cell("sf10_q6", 17, 1.0, False, device, sf_override=SF)
    assert res["correct"] is False
    assert res["compared"]["wrong_statements"]["value"] == res["attempted"] > 0


def test_fault_an_answer_altered_where_it_is_produced(device, monkeypatch):
    import decimal

    from presto_tpu.server import protocol

    json_value = protocol._json_value

    def altered(v, type_name):
        if isinstance(v, decimal.Decimal):  # one unit in the last place
            v = v + decimal.Decimal(1).scaleb(v.as_tuple().exponent)
        return json_value(v, type_name)

    monkeypatch.setattr(protocol, "_json_value", altered)
    res = brun.run_cell("sf10_q6", 18, 1.0, False, device, sf_override=SF)
    assert res["correct"] is False
    assert res["compared"]["wrong_statements"]["value"] == res["attempted"] > 0


def test_fault_a_statement_that_fails(device, monkeypatch):
    from presto_tpu import client

    rows = client.StatementClient.rows
    calls = {"n": 0}

    def failing(self):
        calls["n"] += 1
        if calls["n"] == 4:                   # after the warm-up's two
            raise client.QueryError("injected")
        return rows(self)

    monkeypatch.setattr(client.StatementClient, "rows", failing)
    res = brun.run_cell("sf10_q6", 19, 1.0, False, device, sf_override=SF)
    assert res["correct"] is False and res["failed"] == 1
    assert res["compared"]["wrong_statements"]["value"] == 1


def test_exits_nonzero_where_the_program_is_not(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", "sf10_q6",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
