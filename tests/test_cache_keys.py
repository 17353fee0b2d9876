"""Cache-identity regressions for the knob-flow fixes.

The contract under test: toggling a *volatile* knob (hbo, stats,
farm arming) must reuse cached programs bit-for-bit — same
config_fingerprint, same program-registry entries, zero new misses —
while any *fingerprinted* knob (an ExecConfig field outside
_VOLATILE_CONFIG_FIELDS, or a _FINGERPRINTED_ENVS env var) must fork
the key. Plus the two concrete leaks the pass found: multiway probe
keys now carry the per-leg engine vector, and farm corpus records
carry the recording process's non-volatile config so a booting
process warms under the traffic's program identity, not its own.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from presto_tpu.catalog.memory import MemoryConnector
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.connector import Catalog
from presto_tpu.exec import ExecConfig, LocalRunner, farm, programs
from presto_tpu.exec.programs import config_fingerprint


@pytest.fixture(scope="module")
def cat():
    return tpch_catalog(0.01)


@pytest.fixture(autouse=True)
def _clean_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("PRESTO_TPU_PALLAS", raising=False)
    monkeypatch.delenv("PRESTO_TPU_FARM", raising=False)
    monkeypatch.delenv("PRESTO_TPU_PROGRAM_PERSIST", raising=False)
    farm.reset()
    programs.reset(counters_only=False)
    yield
    farm.reset()
    programs.reset(counters_only=False)


# ---------------------------------------------------------------------------
# config_fingerprint: volatile knobs are value-neutral, the rest fork


def test_volatile_knobs_keep_fingerprint():
    base = config_fingerprint(ExecConfig())
    for change in (dict(hbo="off"), dict(collect_stats=True),
                   dict(compile_farm="on"), dict(result_cache="on")):
        assert config_fingerprint(
            dataclasses.replace(ExecConfig(), **change)) == base, change


def test_nonvolatile_knob_forks_fingerprint():
    base = config_fingerprint(ExecConfig())
    assert config_fingerprint(ExecConfig(batch_rows=1 << 12)) != base
    assert config_fingerprint(ExecConfig(agg_capacity=1 << 9)) != base


def test_pallas_env_forks_fingerprint(monkeypatch):
    base = config_fingerprint(ExecConfig())
    monkeypatch.setenv("PRESTO_TPU_PALLAS", "1")
    forked = config_fingerprint(ExecConfig())
    assert forked != base
    # same value -> same key (it is the value that is hashed, not the
    # read event)
    assert config_fingerprint(ExecConfig()) == forked
    monkeypatch.delenv("PRESTO_TPU_PALLAS")
    assert config_fingerprint(ExecConfig()) == base


def test_cache_volatile_env_keeps_fingerprint(monkeypatch):
    base = config_fingerprint(ExecConfig())
    monkeypatch.setenv("PRESTO_TPU_FARM_WORKERS", "7")
    assert config_fingerprint(ExecConfig()) == base


# ---------------------------------------------------------------------------
# program-registry behavior: volatile toggle reuses entries bit-for-bit


SQL = ("select l_returnflag, sum(l_quantity) as q, count(*) as c "
       "from lineitem where l_discount > 0.02 "
       "group by l_returnflag order by l_returnflag")


def test_volatile_toggle_reuses_programs_bit_for_bit(cat):
    LocalRunner(cat, ExecConfig(hbo="observe")).run(SQL)
    fps = {e.fp for e in programs.entries()}
    assert fps, "shared entries installed"
    misses = programs.snapshot()["misses"]
    LocalRunner(cat, ExecConfig(hbo="off")).run(SQL)
    after = programs.snapshot()
    assert {e.fp for e in programs.entries()} == fps
    assert after["misses"] == misses, "volatile toggle forked a program"
    assert after["hits"] > 0


def test_fingerprinted_knob_forks_program_namespace(cat):
    LocalRunner(cat, ExecConfig(agg_capacity=1 << 10)).run(SQL)
    fps = {e.fp for e in programs.entries()}
    LocalRunner(cat, ExecConfig(agg_capacity=1 << 9)).run(SQL)
    assert {e.fp for e in programs.entries()} - fps, \
        "non-volatile knob change must create new program entries"


# ---------------------------------------------------------------------------
# multiway probe keys carry the per-leg engine vector


def _star_catalog(dup_d2=False):
    rng = np.random.default_rng(17)
    n, ndv = 800, 40
    conn = MemoryConnector()
    conn.add_table("f", pd.DataFrame({
        "k1": rng.integers(0, ndv, n),
        "k2": rng.integers(0, ndv, n),
        "v": rng.normal(0.0, 1.0, n)}))
    for name, key, dup in (("d1", "p1", False), ("d2", "p2", dup_d2)):
        p = np.arange(ndv)
        if dup:
            p = np.repeat(p, 2)
        conn.add_table(name, pd.DataFrame(
            {key: p, f"a{name[1]}": [f"{name}_{i}" for i in p]}))
    cat = Catalog()
    cat.register("mem", conn, default=True)
    return cat


# every order's customer and every customer's nation exist: the LEFT JOIN
# answers as an inner join would
_SNOWFLAKE_SQL = (
    "select o.o_orderkey, c.c_name, n.n_name from orders o "
    "join customer c on o.o_custkey = c.c_custkey "
    "left join nation n on c.c_nationkey = n.n_nationkey")

_STAR_SQL = ("select f.v, d1.a1, d2.a2 from f "
             "join d1 on f.k1 = d1.p1 join d2 on f.k2 = d2.p2")


def _mw_keys():
    return [e.fp.split("|")[2] for e in programs.entries()
            if e.fp and e.fp.split("|")[2].startswith("mw_")]


def test_multiway_unique_keys_carry_engine_vector(cat):
    # primary-key builds (customer, nation) are provably unique, which
    # selects the mw_unique fused-probe program; the LEFT JOIN keeps the
    # chain left-deep (inner joins alone are reordered: customer would
    # probe nation, and orders that join)
    cfg = ExecConfig(join_mode="multiway", batch_rows=1 << 12)
    r = LocalRunner(cat, cfg)
    r.run(_SNOWFLAKE_SQL)
    assert r.last_stats.get("multiway.joins", 0) >= 1
    keys = _mw_keys()
    probe = [k for k in keys if k.startswith("mw_unique@e")]
    assert probe, keys
    evec = probe[0].split("@e", 1)[1]
    assert len(evec) == 2 and set(evec) <= set("hus"), probe[0]


def test_multiway_expand_keys_carry_engine_vector():
    cfg = ExecConfig(join_mode="multiway", batch_rows=1 << 10)
    r = LocalRunner(_star_catalog(dup_d2=True), cfg)
    r.run(_STAR_SQL)
    assert r.last_stats.get("multiway.joins", 0) >= 1
    keys = _mw_keys()
    for prefix in ("mw_expand@e", "mw_counts@f"):
        hit = [k for k in keys if k.startswith(prefix)]
        assert hit, (prefix, keys)
        evec = hit[0].rsplit("@e", 1)[1]
        assert len(evec) == 2 and set(evec) <= set("hus"), hit[0]


# ---------------------------------------------------------------------------
# MwSpec crosses program boundaries -> it must be serialization-registered


def test_mwspec_in_pytree_registration_table():
    from jax import export as jax_export

    from presto_tpu.ops.join import MwSpec

    programs._ensure_pytree_serialization()
    with pytest.raises(ValueError, match="[Dd]uplicate"):
        jax_export.register_namedtuple_serialization(
            MwSpec, serialized_name="dup.MwSpec")




def test_multiway_programs_restore_from_artifacts(cat, tmp_path,
                                                  monkeypatch):
    """Persisted multiway programs must survive the artifact round-trip
    (serialize under one registry, restore into a cold one) — the
    failure mode unregistered operator state produces is a silent
    downgrade to re-trace."""
    monkeypatch.setenv("PRESTO_TPU_PROGRAM_PERSIST", "1")
    cfg = ExecConfig(join_mode="multiway", batch_rows=1 << 12)
    r = LocalRunner(cat, cfg)
    exp = r.run(_SNOWFLAKE_SQL)
    assert r.last_stats.get("multiway.joins", 0) >= 1
    pdir = tmp_path / "programs"
    if not (pdir.exists() and list(pdir.glob("*.jaxexp"))):
        pytest.skip("jax.export unavailable (persistence best-effort)")
    programs.reset(counters_only=False)  # cold registry, same artifacts
    out = LocalRunner(cat, cfg).run(_SNOWFLAKE_SQL)
    assert out.equals(exp)
    assert programs.snapshot()["restored"] > 0
    mw = [e for e in programs.entries()
          if e.fp and e.fp.split("|")[2].startswith("mw_")]
    assert mw, "multiway entries installed on the restored run"
    assert any(e.restored for e in mw), \
        "no multiway program restored from its persisted artifact"


# ---------------------------------------------------------------------------
# farm corpus carries the recording process's config across processes


_RECORDER = """
import sys
from presto_tpu.catalog.tpch import tpch_catalog
from presto_tpu.exec import ExecConfig, LocalRunner
from presto_tpu.exec.programs import config_fingerprint

cfg = ExecConfig(compile_farm="on", batch_rows=4096)
LocalRunner(tpch_catalog(0.01), cfg).run(
    "select count(*) as c from region")
sys.stdout.write(config_fingerprint(cfg))
"""


def test_corpus_cfg_round_trips_across_processes(tmp_path):
    """Process A records traffic under a non-default config; process B
    (this one) must re-derive the exact program fingerprint A's
    programs were cached under — not the ambient default's."""
    env = dict(os.environ, PRESTO_TPU_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    env.pop("PRESTO_TPU_PALLAS", None)
    out = subprocess.run(
        [sys.executable, "-c", _RECORDER], env=env, cwd=os.getcwd(),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    recorded_fp = out.stdout.strip()
    assert len(recorded_fp) == 16

    farm.reset()  # drop the corpus cache; re-read A's file
    corpus = farm.load_corpus()
    assert corpus["plans"], "process A recorded at least one plan"
    fp = next(iter(corpus["plans"]))
    cfg_doc = corpus["cfgs"][fp]
    assert cfg_doc.get("batch_rows") == 4096
    assert "compile_farm" not in cfg_doc, "volatile fields not recorded"

    ambient = ExecConfig()
    restored = farm._cfg_restore(ambient, cfg_doc)
    assert restored.batch_rows == 4096
    assert config_fingerprint(restored) == recorded_fp
    assert config_fingerprint(ambient) != recorded_fp
    # an empty / pre-cfg record degrades to the ambient config
    assert farm._cfg_restore(ambient, {}) is ambient
    assert farm._cfg_restore(ambient, None) is ambient
