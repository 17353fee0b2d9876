#!/usr/bin/env python
"""presto-tpu demo: boot an in-process cluster and run the SQL surface.

    python examples/demo.py            # uses the real device if available
    python examples/demo.py --cpu     # force CPU

Shows: TPC-H queries, structural types + lambdas, grouping sets, window
frames, prepared statements, CTAS, and EXPLAIN ANALYZE with the
per-task stats rollup.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--sf", type=float, default=0.01)
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    from presto_tpu.catalog.tpch import tpch_catalog
    from presto_tpu.exec import ExecConfig
    from presto_tpu.server.coordinator import DistributedRunner

    print(f"booting a 2-worker cluster over TPC-H sf={args.sf} ...")
    r = DistributedRunner(tpch_catalog(args.sf), n_workers=2,
                          config=ExecConfig(batch_rows=1 << 15))
    try:
        run = r.run
        print("\n-- TPC-H Q1 --")
        print(run("""
            select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
                   sum(l_extendedprice * (1 - l_discount)) as revenue,
                   count(*) as n
            from lineitem where l_shipdate <= date '1998-09-02'
            group by l_returnflag, l_linestatus
            order by l_returnflag, l_linestatus"""))

        print("\n-- structural types + lambdas --")
        print(run("""
            select o_orderpriority,
                   array_agg(o_orderkey) as keys
            from orders where o_orderkey < 40
            group by o_orderpriority order by o_orderpriority"""))
        print(run("select transform(sequence(1, 5), x -> x * x) as squares"))

        print("\n-- grouping sets --")
        print(run("""
            select o_orderstatus, o_orderpriority, count(*) as n,
                   grouping(o_orderstatus, o_orderpriority) as gid
            from orders group by rollup (o_orderstatus, o_orderpriority)
            order by gid, o_orderstatus, o_orderpriority limit 12"""))

        print("\n-- window frames --")
        print(run("""
            select o_custkey, o_totalprice,
                   avg(o_totalprice) over (partition by o_custkey
                       order by o_orderdate
                       rows between 2 preceding and current row) as mavg
            from orders where o_custkey < 5
            order by o_custkey limit 8"""))

        print("\n-- prepared statements --")
        from presto_tpu.client import execute

        url = r.coordinator.url
        execute(url, "prepare top_nations from "
                     "select n_name from nation where n_regionkey = ? "
                     "order by n_name limit ?")
        _, rows = execute(url, "execute top_nations using 2, 3")
        print([x[0] for x in rows])

        print("\n-- EXPLAIN ANALYZE (distributed stats rollup) --")
        out = r.coordinator.explain_analyze_distributed(
            "select count(*) as n from lineitem")
        print(out[out.index("-- task execution profile --"):])
    finally:
        r.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
