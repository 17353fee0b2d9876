#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

    python chip_smoke.py             one TPU chip: TPC-H SF1 Q6, Q1, Q3 over
                                     POST /v1/statement, each cold and warm,
                                     every answer against a pandas reference
    python chip_smoke.py --chips 4   four chips: Q3 SF1 through MeshExecutor
                                     on a four-device mesh against
                                     LocalRunner on one device; nothing else

One process owns the chip(s); no child process is started. Without a TPU
the script exits non-zero at once and prints no result. The one exception
is the rehearsal: off the TPU an explicit ``--sf`` below 1 walks the same
phases on whatever backend there is and always ends ``"ok": false``,
non-zero. Readings printed here are smoke readings, not benchmark numbers.

Earlier lines of stdout are one JSON object per phase; the last line is
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import decimal
import json
import os
import sys
import time
import traceback

import numpy as np
import pandas as pd

_HERE = os.path.dirname(os.path.abspath(__file__))
QUERIES = ("Q6", "Q1", "Q3")  # fixed list: never cut at run time


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# the plain reference: pandas over the generator's own arrays. Decimals are
# the unscaled int64 values the generator emits (extendedprice, discount and
# tax at scale 2), so products and sums are exact integer arithmetic.


def _day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def _dec(unscaled, scale: int) -> decimal.Decimal:
    return decimal.Decimal(int(unscaled)).scaleb(-scale)


def _date(days) -> str:
    return str(np.datetime64(int(days), "D"))


def _strings(col) -> np.ndarray:
    """A generated string column: plain, or (Dictionary, codes)."""
    if isinstance(col, tuple):
        d, codes = col
        return d.decode(codes)
    return col


class Reference:
    def __init__(self, sf: float):
        from presto_tpu.catalog.tpch import TpchGenerator

        gen = TpchGenerator(sf)
        orders, lineitem = gen.orders_and_lineitem()
        cols = ("l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
                "l_tax", "l_shipdate")
        self.lineitem = pd.DataFrame({c: lineitem[c] for c in cols})
        for c in ("l_returnflag", "l_linestatus"):
            self.lineitem[c] = _strings(lineitem[c])
        self.orders = pd.DataFrame({
            c: orders[c] for c in ("o_orderkey", "o_custkey", "o_orderdate",
                                   "o_shippriority")})
        customer = gen.customer()
        self.customer = pd.DataFrame({
            "c_custkey": customer["c_custkey"],
            "c_mktsegment": _strings(customer["c_mktsegment"])})

    def q6(self):
        li = self.lineitem
        m = ((li.l_shipdate >= _day("1994-01-01"))
             & (li.l_shipdate < _day("1995-01-01"))
             & (li.l_discount >= 5) & (li.l_discount <= 7)
             & (li.l_quantity < 24))
        s = li[m]
        return [[_dec((s.l_extendedprice * s.l_discount).sum(), 4)]]

    def q1(self):
        li = self.lineitem
        s = li[li.l_shipdate <= _day("1998-12-01") - 90].copy()
        s["disc_price"] = s.l_extendedprice * (100 - s.l_discount)
        s["charge"] = s.disc_price * (100 + s.l_tax)
        g = s.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            sum_disc=("l_discount", "sum"),
            n=("l_quantity", "size")).reset_index()
        return [[r.l_returnflag, r.l_linestatus, int(r.sum_qty),
                 _dec(r.sum_base, 2), _dec(r.sum_disc_price, 4),
                 _dec(r.sum_charge, 6), r.sum_qty / r.n,
                 r.sum_base / 100 / r.n, r.sum_disc / 100 / r.n, int(r.n)]
                for r in g.itertuples()]

    def q3(self):
        c = self.customer[self.customer.c_mktsegment == "BUILDING"]
        o = self.orders[self.orders.o_orderdate < _day("1995-03-15")]
        o = o.merge(c, left_on="o_custkey", right_on="c_custkey")
        li = self.lineitem[self.lineitem.l_shipdate > _day("1995-03-15")]
        j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
        j["revenue"] = j.l_extendedprice * (100 - j.l_discount)
        g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                      sort=False)["revenue"].sum().reset_index()
        g = g.sort_values(["revenue", "o_orderdate"],
                          ascending=[False, True], kind="stable").head(10)
        return [[int(r.l_orderkey), _dec(r.revenue, 4), _date(r.o_orderdate),
                 int(r.o_shippriority)] for r in g.itertuples()]


def compare(columns, got, want) -> None:
    """Row counts, keys and decimals exact; doubles to 1e-9 relative."""
    if len(got) != len(want):
        raise AssertionError(f"row count {len(got)} != reference {len(want)}")
    for i, (g, w) in enumerate(zip(got, want)):
        for col, a, b in zip(columns, g, w):
            kind = col["type"]
            if kind.startswith("decimal"):
                same = decimal.Decimal(a) == b
            elif kind == "double":
                same = abs(float(a) - float(b)) <= 1e-9 * abs(float(b))
            else:
                same = a == b
            if not same:
                raise AssertionError(
                    f"row {i} column {col['name']} ({kind}): got {a!r}, "
                    f"reference {b!r}")


# ---------------------------------------------------------------------------
# observation helpers


class CacheEvents:
    """Counts jax's persistent-compilation-cache events in this process."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def hbm(device) -> dict:
    st = device.memory_stats() or {}
    return {"bytes_in_use": st.get("bytes_in_use"),
            "peak_bytes_in_use": st.get("peak_bytes_in_use")}


def explain_engines(url: str, sql: str, session):
    """(rows scanned, {engine: breaker dispatches}, why-strings) from what
    EXPLAIN ANALYZE returns over the protocol: the task profile's counters
    and the `-- breaker engines --` verdicts."""
    from presto_tpu import client

    _, rows = client.execute(url, "explain analyze " + sql, session)
    scanned, engines, why = 0, {}, []
    for (line,) in rows:
        parts = line.split()
        if not parts:
            continue
        if "[engine=" in line:
            why.append(line.strip())
        elif parts[0] == "TableScan" and "rows=" in line:
            scanned += int(line.split("rows=")[1].split()[0].replace(",", ""))
        elif parts[0].startswith("breaker.engine_"):
            n = int(line.split("rows=")[1].split()[0].replace(",", ""))
            eng = parts[0][len("breaker.engine_"):]
            engines[eng] = engines.get(eng, 0) + n
    return scanned, engines, why


# ---------------------------------------------------------------------------
# phases


def served_phase(sf: float, device, events: CacheEvents) -> bool:
    """Q6, Q1, Q3 through the repo's client to POST /v1/statement on an
    in-process cluster built as `python -m presto_tpu.server --catalog
    tpch:sf=<sf>` builds it: one worker, the shipped ExecConfig."""
    import jax

    import bench
    from presto_tpu import client
    from presto_tpu.exec import ExecConfig, programs
    from presto_tpu.ops import pallas_hash
    from presto_tpu.server.__main__ import build_catalog
    from presto_tpu.server.coordinator import DistributedRunner

    t0 = time.time()
    ref = Reference(sf)
    t1 = time.time()
    catalog = build_catalog([f"tpch:sf={sf:g}"])
    for table in ("customer", "orders", "lineitem"):  # generate now: set-up
        catalog.connectors["tpch"].get_table(table)
    emit({"phase": "setup", "sf": sf, "lineitem_rows": len(ref.lineitem),
          "reference_data_s": t1 - t0, "catalog_data_s": time.time() - t1})
    all_ok = True
    runner = DistributedRunner(catalog, n_workers=1, config=ExecConfig())
    try:
        url = runner.coordinator.url
        session = client.ClientSession(user="chip_smoke")
        for name in QUERIES:
            sql = getattr(bench, name)
            want = getattr(ref, name.lower())()
            snap0, hits0, miss0 = programs.snapshot(), events.hits, events.misses
            walls = []
            for _ in ("cold", "warm"):
                t0 = time.time()
                st = client.StatementClient(url, sql, session)
                got = list(st.rows())
                walls.append(time.time() - t0)
                compare(st.columns, got, want)
            snap1 = programs.snapshot()
            scanned, engines, why = explain_engines(url, sql, session)
            interpreted = (pallas_hash.use_interpret()
                           and engines.get("hash", 0) > 0)
            all_ok &= not interpreted
            emit({"phase": "query", "query": name, "sf": sf,
                  "rows_scanned": scanned, "result_rows": len(got),
                  "cold_wall_s": walls[0], "warm_wall_s": walls[1],
                  "correct": True, "engines": engines, "engine_why": why,
                  "kernels_interpreted": interpreted,
                  "compiles": snap1["compiles"] - snap0["compiles"],
                  "compile_s": snap1["trace_wall_s"] - snap0["trace_wall_s"],
                  "persistent_cache_hits": events.hits - hits0,
                  "persistent_cache_misses": events.misses - miss0,
                  "hbm": hbm(device),
                  "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    finally:
        runner.close()
    return all_ok


def mesh_phase(sf: float, chips: int) -> bool:
    """Q3 through MeshExecutor on a `chips`-device mesh (one shard_map
    program, all_to_all exchanges) against LocalRunner on one device of
    the same process."""
    import jax

    import bench
    from presto_tpu.exec import ExecConfig, LocalRunner
    from presto_tpu.parallel.mesh import make_mesh
    from presto_tpu.parallel.mesh_exec import MeshExecutor
    from presto_tpu.server.__main__ import build_catalog

    catalog = build_catalog([f"tpch:sf={sf:g}"])
    mesh = make_mesh(chips)
    mx = MeshExecutor(catalog, mesh, ExecConfig())

    # observe where the staged scans land: bytes per device, from the
    # placed arrays themselves
    placed = {d.id: 0 for d in mesh.devices.flat}
    stage = mx._stage_scan

    def observed_stage(scan, sharded):
        batch = stage(scan, sharded)
        for leaf in jax.tree_util.tree_leaves(batch):
            for sh in getattr(leaf, "addressable_shards", ()):
                placed[sh.device.id] += sh.data.nbytes
        return batch

    mx._stage_scan = observed_stage

    t0 = time.time()
    got = mx.run(bench.Q3)
    mesh_cold = time.time() - t0
    scan_bytes = dict(placed)
    t0 = time.time()
    mx.run(bench.Q3)
    mesh_warm = time.time() - t0
    t0 = time.time()
    want = LocalRunner(catalog, ExecConfig()).run(bench.Q3)
    local_cold = time.time() - t0

    equal = (list(got.columns) == list(want.columns)
             and len(got) == len(want)
             and all(got[c].tolist() == want[c].tolist() for c in got.columns))
    spread = all(b > 0 for b in scan_bytes.values())
    emit({"phase": "mesh", "query": "Q3", "sf": sf, "devices": chips,
          "mesh_cold_wall_s": mesh_cold, "mesh_warm_wall_s": mesh_warm,
          "local_cold_wall_s": local_cold, "result_rows": len(got),
          "equal_to_one_device": equal,
          "overflow_retries": (mx.last_run or {}).get("retries"),
          "scan_bytes_per_device": scan_bytes,
          "all_devices_hold_data": spread,
          "hbm_per_device": {d.id: hbm(d) for d in mesh.devices.flat},
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    if not equal:
        print("mesh:\n", got, "\none device:\n", want, file=sys.stderr)
    return equal and spread


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; below 1 only to "
                         "rehearse off the TPU, which never ends ok)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-device mesh path and what "
                         "it is compared with")
    args = ap.parse_args(argv)
    sf = 1.0 if args.sf is None else args.sf

    sys.path.insert(0, _HERE)
    import jax

    import presto_tpu  # noqa: F401  (x64 on, compile cache decided)

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    rehearsal = not on_tpu and args.sf is not None and args.sf < 1
    if not on_tpu and not rehearsal:
        print(f"chip_smoke: no TPU — jax found platform {dev.platform!r}; "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    if on_tpu and sf < 1:
        print("chip_smoke: SF1 is the floor on the chip", file=sys.stderr)
        return 1

    events = CacheEvents()
    t0 = time.time()
    try:
        if args.chips == 4:
            ok = mesh_phase(sf, 4)
        else:
            ok = served_phase(sf, dev, events)
    except BaseException:
        traceback.print_exc()
        ok = False
    emit({"phase": "total", "wall_s": time.time() - t0,
          "persistent_cache_hits": events.hits,
          "persistent_cache_misses": events.misses, "rehearsal": rehearsal})
    ok = bool(ok and on_tpu)
    count = len(devices) if args.chips == 1 else 4
    emit({"ok": ok, "device": {"platform": dev.platform,
                               "kind": dev.device_kind, "count": count}})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
