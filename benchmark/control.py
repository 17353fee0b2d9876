#!/usr/bin/env python3
"""The control of "How correct is decided": the plain reference put in the
program's place, computed in float32 (the chip's native type, the step that
would tempt a later PR) instead of exact decimals - the guarantee "exact SQL
answers" broken. It has to come out NOT correct under each cell's limits.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--sf <f>]

For each seed: the cell's data at the cell's own scale, the exact reference,
the float32 answers fed to the same comparison as a window of one statement
per distinct text. Prints one JSON line per seed and exits 0 only if every
seed's control was judged not correct. Needs no chip: it is data and
arithmetic; `--sf` is for the test at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_verdict(workload: str, seed: int, sf=None, arith="float32") -> dict:
    import importlib

    from benchmark import compare, traffic
    from benchmark.run import load_cell, load_reference, load_traffic

    spec = load_cell(workload)
    config = spec["config"]
    bdata = importlib.import_module("benchmark." + config["data_module"])
    sf = float(config["scale_factor"] if sf is None else sf)
    mix, queries, limits = load_traffic(spec)
    tables = sorted({t for q in queries.values() for t in q["tables"]})
    data = bdata.generate(sf, seed, tables)
    statements, references = [], {}
    for qid, q in queries.items():
        answer = load_reference(qid)
        for params in traffic.param_sets(mix, q):
            key = traffic.params_key(params)
            references[(qid, key)] = (answer(data, params), q["result_columns"])
            rows = [[str(v) if hasattr(v, "as_tuple") else v for v in row]
                    for row in answer(data, params, arith=arith)]
            statements.append({"index": len(statements), "query": qid,
                               "params_key": key, "error": None,
                               "columns": q["result_columns"], "rows": rows})
    verdict = compare.judge(statements, references, limits)
    return {"workload": workload, "seed": seed, "sf": sf, "arith": arith,
            **verdict}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sf", type=float, default=None)
    args = ap.parse_args(argv)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        v = control_verdict(args.workload, seed, args.sf)
        print(json.dumps(v), flush=True)
        failed_all &= not v["correct"]
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
