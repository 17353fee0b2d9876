"""chip_smoke.py off the chip: its contract (no TPU → non-zero, no result),
its comparison rules, and its pandas reference held to the engine at a small
scale factor — so the reference cannot drift from the generator unnoticed
between chip runs. The chip run itself is `python chip_smoke.py` through the
chip tool; nothing here is a device reading."""

import decimal
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
import chip_smoke  # noqa: E402

D = decimal.Decimal


def test_default_invocation_without_a_tpu_fails_and_prints_no_result(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_sf_of_one_or_more_is_no_rehearsal(capsys):
    assert chip_smoke.main(["--sf", "1"]) == 1
    assert capsys.readouterr().out == ""


def test_four_chips_need_four_devices(capsys, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    assert chip_smoke.main(["--sf", "0.01", "--chips", "4"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "--chips 4" in out.err


_COLS = [{"name": "k", "type": "bigint"}, {"name": "m", "type": "decimal(38,4)"},
         {"name": "x", "type": "double"}, {"name": "d", "type": "date"}]


@pytest.mark.parametrize("got,want,ok", [
    ([[1, "2.5000", 1.0, "1995-03-01"]], [[1, D("2.5"), 1.0, "1995-03-01"]], True),
    ([[1, "2.5000", 1.0 + 5e-10, "1995-03-01"]], [[1, D("2.5"), 1.0, "1995-03-01"]], True),
    ([[1, "2.5000", 1.0 + 5e-9, "1995-03-01"]], [[1, D("2.5"), 1.0, "1995-03-01"]], False),
    ([[1, "2.5001", 1.0, "1995-03-01"]], [[1, D("2.5"), 1.0, "1995-03-01"]], False),
    ([[2, "2.5000", 1.0, "1995-03-01"]], [[1, D("2.5"), 1.0, "1995-03-01"]], False),
    ([[1, "2.5000", 1.0, "1995-03-02"]], [[1, D("2.5"), 1.0, "1995-03-01"]], False),
    ([], [[1, D("2.5"), 1.0, "1995-03-01"]], False),
])
def test_compare_keys_and_decimals_exact_doubles_to_1e9(got, want, ok):
    if ok:
        chip_smoke.compare(_COLS, got, want)
    else:
        with pytest.raises(AssertionError):
            chip_smoke.compare(_COLS, got, want)


@pytest.fixture(scope="module")
def served():
    from presto_tpu.exec import ExecConfig
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    runner = DistributedRunner(build_catalog(["tpch:sf=0.01"]), n_workers=1,
                               config=ExecConfig())
    yield runner.coordinator.url, chip_smoke.Reference(0.01)
    runner.close()


@pytest.mark.parametrize("name", chip_smoke.QUERIES)
def test_reference_agrees_with_the_served_engine(served, name):
    from presto_tpu import client

    url, ref = served
    st = client.StatementClient(url, getattr(bench, name),
                                client.ClientSession(user="t"))
    got = list(st.rows())
    assert got
    chip_smoke.compare(st.columns, got, getattr(ref, name.lower())())


def test_explain_engines_reads_counters_and_verdicts(served):
    from presto_tpu import client

    url, _ = served
    scanned, engines, why = chip_smoke.explain_engines(
        url, bench.Q1, client.ClientSession(user="t"))
    assert scanned == 59997
    assert sum(engines.values()) >= 2 and set(engines) <= {"sort", "hash"}
    assert why and all("[engine=" in w for w in why)


def test_last_line_of_a_rehearsal_is_the_device_object_and_never_ok(capfd):
    assert chip_smoke.main(["--sf", "0.01"]) == 1
    lines = capfd.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    phases = [json.loads(ln).get("phase") for ln in lines[:-1]]
    assert phases == ["setup", "query", "query", "query", "total"]
    assert all(json.loads(ln)["correct"] for ln in lines[1:4])
